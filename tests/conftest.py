import importlib.util
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")

# ---------------------------------------------------------------------- #
# Optional-dependency shim: the property tests import `hypothesis` at module
# scope; without this, collection of the whole suite dies on machines that
# lack the dev extras (see requirements-dev.txt).  Prefer the real package,
# fall back to the deterministic stub.
# ---------------------------------------------------------------------- #
if importlib.util.find_spec("hypothesis") is None:
    _spec = importlib.util.spec_from_file_location(
        "_hypothesis_stub", os.path.join(os.path.dirname(__file__),
                                         "_hypothesis_stub.py"))
    _stub = importlib.util.module_from_spec(_spec)
    _spec.loader.exec_module(_stub)
    _stub.install()


def run_with_devices(code: str, n_devices: int = 8, timeout: int = 560) -> str:
    """Run a python snippet in a subprocess with N host platform devices.

    Multi-device collective tests must not pollute the main pytest process
    (which keeps the default 1-device view per the project brief).
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    # Deliberately do NOT forward -O / PYTHONOPTIMIZE: pytest's assertion
    # rewriting protects only in-process test modules, so optimizing the
    # child would strip the snippet's own acceptance asserts and leave it
    # validating nothing.  The CI `python -O` leg gets its source coverage
    # from the in-process tests (kernels/models import directly there).
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout, env=env)
    if out.returncode != 0:
        raise AssertionError(
            f"subprocess failed:\nSTDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}")
    return out.stdout


@pytest.fixture(scope="session")
def subproc():
    return run_with_devices
