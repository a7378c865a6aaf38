"""What every cell shares: finding a cell's pieces by name, the device
check, the compile cache, the model configuration, window statistics and
the result line.

A cell is one ``workloads`` entry of ``BENCHMARK.json``.  Its configuration
is ``bench/configs/<config>.json`` (read through :func:`as_run`), its
traffic mix or training job is ``bench/traffic/<traffic>.json`` (whose
``kind`` picks the driver), its
limits for ``correct`` are ``bench/limits/<cell>.json``, and each per-layer
metric is read by ``bench/metrics/<metric>.py``.  The configuration file
names two modules beside it: ``"arch"``, whose ``model_config``,
``layer_specs``, ``residual_writers``, ``routing`` and ``layer_work`` are
all that the harness, the weights and the work counts know of the
architecture (the contract is in ``bench/configs/decoder_arch.py``), and
``"reference"``, the plain reference.  Nothing here names a cell, a
configuration, a metric or an architecture.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import math
import os
import sys
from typing import Any, Callable

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def load_json(*parts: str) -> Any:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def benchmark_spec(root: str = ROOT) -> dict:
    return load_json(root, "BENCHMARK.json")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict            # bench/configs/<config>.json
    traffic: dict           # bench/traffic/<traffic>.json
    limits: dict            # bench/limits/<cell>.json
    end_to_end: list[dict]  # the end-to-end metrics this cell reports
    per_layer: list[dict]   # the per-layer metrics this cell reports


def find_cell(name: str, spec: dict | None = None) -> Cell:
    spec = spec if spec is not None else benchmark_spec()
    by_name = {w["name"]: w for w in spec["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(by_name)}")
    w = by_name[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=as_run(load_json(ROOT, conf["file"])),
        traffic=load_json(BENCH, "traffic", w["traffic"] + ".json"),
        limits=load_json(BENCH, "limits", name + ".json"),
        end_to_end=e2e, per_layer=per_layer)


def as_run(c: dict) -> dict:
    """A configuration file's keys as the program runs them: the published
    values, with ``program_cannot_express`` (what the program runs where it
    cannot express a published key) laid over them."""
    return {**c, **c.get("program_cannot_express", {})}


def metric_reader(name: str) -> Callable:
    """``read(run)`` of ``bench/metrics/<name>.py``."""
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def reference_module(cfg_json: dict):
    """The plain reference named by a configuration file."""
    return _config_module(cfg_json["reference"])


def arch_module(cfg_json: dict):
    """The architecture module named by a configuration file."""
    return _config_module(cfg_json["arch"])


def _config_module(name: str):
    return _load_module(os.path.join(BENCH, "configs", name + ".py"))


@functools.lru_cache(maxsize=None)
def _load_module(path: str):
    name = os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(
        "bench_configs_" + name.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------- #
# Device and compile cache
# ---------------------------------------------------------------------- #

def set_compile_cache(root: str = ROOT) -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` when set, else
    ``<checkout>/.jax_cache`` (a fixed path: it is part of the cache key).
    Every program is kept, the small eager ones too."""
    path = os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                                 os.path.join(root, ".jax_cache"))
    import jax
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def check_devices(chips: int) -> list:
    """The first ``chips`` TPU devices, or :class:`NoChip`."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def device_info(devs) -> dict:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(max(peaks))}


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip (``bench/peaks.json``); a kind that is
    not in the table is an error, never a default."""
    table = load_json(BENCH, "peaks.json")["chips"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json")
    return table[device_kind]


# ---------------------------------------------------------------------- #
# Model configuration
# ---------------------------------------------------------------------- #

def model_config(c: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    return arch_module(c).model_config(c)


# ---------------------------------------------------------------------- #
# Statistics and the result line
# ---------------------------------------------------------------------- #

def pctl(xs, q: float) -> float | None:
    """The ``q``-th percentile by linear interpolation; None when empty."""
    xs = sorted(xs)
    if not xs:
        return None
    k = (len(xs) - 1) * q / 100.0
    lo = math.floor(k)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def judge(readings: dict[str, float], limits: dict[str, float]) -> tuple:
    """(correct, [[name, reading, limit], ...]): each number at or under
    its limit; a missing or non-finite reading fails."""
    rows, ok = [], True
    for name, limit in limits.items():
        v = readings.get(name)
        good = v is not None and math.isfinite(v) and v <= limit
        ok &= good
        rows.append([name, v, limit])
    return bool(ok), rows


def emit(result: dict, checks: list) -> None:
    """The compared numbers last on stderr, then the result line last on
    stdout with them under ``checks``."""
    for name, v, limit in checks:
        print(f"check {name} = {v!r} limit {limit!r}", file=sys.stderr)
    sys.stderr.flush()
    result = dict(result)
    result["checks"] = {n: {"value": v, "limit": lim}
                        for n, v, lim in checks}
    print(json.dumps(result), flush=True)
