"""``correct`` on the CPU at a size a test can hold: a sound run passes;
the timed path broken underneath, or the float8 control put in the
program's place, fails.  Every run goes through ``run.execute`` past the
harness's look for a chip."""
import os
import subprocess
import sys

import pytest

from bench import harness
from bench.tests import tiny

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class AlteredTokens:
    """The executor with every decoded token moved to its neighbour id."""

    def __init__(self, ex):
        self.ex = ex
        self.block_size = ex.block_size

    def prefill(self, *a):
        return self.ex.prefill(*a)

    def decode(self, *a):
        return [(t + 1) % tiny.DENSE["vocab_size"] for t in self.ex.decode(*a)]

    def extend(self, *a):
        return self.ex.extend(*a)

    def release(self, *a):
        return self.ex.release(*a)


def unchanged_state(fn):
    """A step that returns its state unchanged (the loss still computed)."""
    import jax
    import jax.numpy as jnp

    def step(p, o, b):
        _, _, loss = fn(*jax.tree.map(jnp.copy, (p, o)), b)
        return p, o, loss
    return step


def half_batch(fn):
    """A step that leaves out half of the batch, the mean over the rest."""
    def step(p, o, b):
        n = b["tokens"].shape[0] // 2
        return fn(p, o, {k: v[:n] for k, v in b.items()})
    return step


def _checks(rows):
    return {n: v for n, v, _ in rows}


def test_sound_serving_run_is_correct():
    res, rows = tiny.run("serve")
    assert res["correct"], rows
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"out_tok_per_s", "itl_p95_s", "setup_s"}
    assert res["device"]["platform"] == "cpu"


def test_altered_tokens_fail():
    res, rows = tiny.run("serve", fault=AlteredTokens)
    assert not res["correct"]
    assert _checks(rows)["max_logit_gap"] > 10 * tiny.SERVE_LIMITS[
        "max_logit_gap"]


def test_sound_training_run_is_correct():
    res, rows = tiny.run("train")
    assert res["correct"], rows
    assert set(res["metrics"]) == {"train_tok_per_s", "setup_s"}


@pytest.mark.parametrize("fault, number", [
    (unchanged_state, "update_norm_gap"),
    (half_batch, "grad_diff"),
])
def test_training_faults_fail(fault, number):
    res, rows = tiny.run("train", fault=fault)
    assert not res["correct"]
    assert _checks(rows)[number] > 3 * tiny.TRAIN_LIMITS[number], rows


EXCHANGE = r"""
import contextlib, json, sys
sys.path[:0] = [{root!r}, {src!r}]
from unittest import mock
import jax
from jax import lax
from repro.optim import adamw
from bench.tests import tiny

def no_exchange(g, ax, slow_axis, cfg, ef=None):
    # each rank keeps its own gradient: its slice, never summed
    import jax.numpy as jnp
    g = g.astype(jnp.float32)
    if ax is not None:
        n = g.shape[ax] // lax.axis_size("data")
        g = lax.dynamic_slice_in_dim(g, lax.axis_index("data") * n, n, ax)
    return g * lax.axis_size("data") * (lax.axis_size(slow_axis)
                                        if slow_axis else 1)

fault = {fault}
with mock.patch.object(adamw, "_sync_shard", no_exchange) if fault \
        else contextlib.nullcontext():
    res, rows = tiny.run("train", chips=4, mesh=(2, 2, 1))
print(json.dumps({{"correct": res["correct"], "rows": rows}}))
"""


@pytest.mark.parametrize("fault", [False, True])
def test_exchange_between_chips(fault):
    """On four virtual CPU devices (2 pods x 2 data): sound multilevel
    training is correct, and with the gradient exchange left out it is
    not."""
    import json
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = EXCHANGE.format(root=ROOT, src=os.path.join(ROOT, "src"),
                           fault=fault)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["correct"] is (not fault), got["rows"]


@pytest.mark.parametrize("kind", ["serve", "train"])
def test_float8_control_fails(kind):
    """The reference in the program's place, computed in float8 (the
    precision below the configuration's bfloat16), read on the same sample
    as a sound run: some number fails its limit.  Every committed cell of
    the kind compares each number the tiny limits hold."""
    import time

    import jax

    from bench import run as R
    res, _ = R.execute(tiny.cell(kind), 2 ** 33 + 1, 1.0, False,
                       jax.devices()[:1], t_start=time.perf_counter(),
                       peak=None, control=True)
    assert res["correct"]
    limits = tiny.SERVE_LIMITS if kind == "serve" else tiny.TRAIN_LIMITS
    for w in harness.benchmark_spec()["workloads"]:
        cell = harness.find_cell(w["name"])
        if cell.traffic["kind"] == kind:
            assert set(limits) <= set(cell.limits["limits"]), w["name"]
    control = {k: res["control"]["control_" + k] for k in limits}
    assert not harness.judge(control, limits)[0], control
