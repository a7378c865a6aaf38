"""Run one benchmark cell once on the chips of this machine.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

The cell is a ``workloads`` entry of ``BENCHMARK.json``; its pieces are
found by name (see ``bench/harness.py``).  Weights and inputs come from
``--seed``.  Set-up warms up every shape the window uses; the window then
measures for ``--seconds``.  With ``--trace 0`` the result carries the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics, read
from the profiler's trace of the window and from the host clock.  Every
run then checks the window's output against the plain reference and
prints each compared number beside its limit, last on stderr and under
``checks`` in the result, which is the last line on stdout.

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


class View:
    """What a metric reader reads: the run's clocks, calls and trace."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def make_mesh(cell, devs):
    from repro.launch.mesh import make_test_mesh
    pods, data, model = cell.traffic.get("mesh", [1, 1, 1])
    if pods * data * model != len(devs):
        raise ValueError(f"mesh {pods}x{data}x{model} on {len(devs)} chips")
    return make_test_mesh(pods, data, model)


def execute(cell, seed: int, seconds: float, trace: bool, devs, *,
            t_start: float, peak: dict | None, fault=None,
            control: bool = False) -> tuple:
    """One run of ``cell``.  Returns (result, checks).  ``fault`` breaks
    the timed path underneath (for the tests): a callable that wraps the
    serving executor or the training step.  ``control`` also reads the
    float8 control on the same sample, under ``result["control"]``."""
    import jax

    from bench import harness, serve_cell, train_cell, tracing

    mesh = make_mesh(cell, devs)
    tracer = (tracing.Tracer(os.path.join(ROOT, "bench_out", "trace",
                                          cell.name)) if trace else None)
    kind = cell.traffic["kind"]
    if kind == "serve":
        out = serve_cell.run(cell, seed, seconds, mesh, tracer=tracer,
                             wrap_executor=fault)
        tx = out["tx"]
        stats = serve_cell.window_stats(tx, int(cell.traffic["clients"]))
        window = tx.window
        attempted, failed = stats["attempted"], stats["failed"]
        state = (out["executor"].params, out["executor"].pools)
    elif kind == "train":
        out = train_cell.run(cell, seed, seconds, mesh, tracer=tracer,
                             step_fault=fault)
        tx, stats = None, None
        window = (out["window_start"], out["window_end"])
        attempted = len(out["steps"])
        failed = sum(1 for x in out["step_losses"] if not math.isfinite(x))
        state = out.pop("state")
    else:
        raise ValueError(f"unknown traffic kind {kind!r}")
    setup_s = out["window_start"] - t_start
    device = harness.device_info(devs)
    reduced = tracer.reduce() if tracer else None
    view = View(kind=kind, cell=cell, config=cell.config,
                traffic=cell.traffic, seconds=seconds, window=window,
                tx=tx, stats=stats, out=out, trace=reduced, peak=peak,
                chips=len(devs), setup_s=setup_s)
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        v = (setup_s if m["name"] == "setup_s"
             else harness.metric_reader(m["name"])(view))
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    for x in jax.tree.leaves(state):
        x.delete()
    del state
    driver = serve_cell if kind == "serve" else train_cell
    readings = driver.check(cell, seed, out, control=control)
    correct, checks = harness.judge(readings, cell.limits["limits"])
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if control:
        result["control"] = readings
    if reduced is not None:
        result["device"]["busy_s"] = reduced.busy_s()
        result["device"]["window_s"] = reduced.window_s
        result["breakdown"] = reduced.breakdown()
    return result, checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness

    cell = harness.find_cell(args.workload)
    try:
        devs = harness.check_devices(cell.chips)
    except harness.NoChip as e:
        print(f"bench/run.py: {e}; no result", file=sys.stderr)
        return 2
    harness.set_compile_cache(ROOT)
    peak = harness.peaks(devs[0].device_kind)
    result, checks = execute(cell, args.seed, args.seconds,
                             bool(args.trace), devs, t_start=T_START,
                             peak=peak)
    harness.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
