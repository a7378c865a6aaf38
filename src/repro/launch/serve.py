"""Serving driver: continuous batching over a paged KV cache, with the
multilevel engine pricing per-request collectives against weight broadcasts.

One device (a TPU chip, or the CPU):
  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \\
      --full-config --prompt-len 512
CPU demo on four virtual devices:
  XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
  PYTHONPATH=src python -m repro.launch.serve --arch gpt-100m --mesh 1x2x2
"""
from __future__ import annotations

import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.launch.mesh import make_test_mesh, mesh_communicator
from repro.models import transformer as T
from repro.obs import Tracer, get_logger, set_json
from repro.serving import (JaxExecutor, Scheduler, SLO, make_requests,
                           poisson_arrivals, default_compute_model)

log = get_logger("serve")


def _weight_bytes(params) -> float:
    return float(sum(np.prod(l.shape) * l.dtype.itemsize
                     for l in jax.tree.leaves(params)))


def _engine_demo(wcomm, wbytes: float, cfg, prompt_len: int, model: int,
                 replicas: list, n_requests: int) -> None:
    """Price 1 weight bcast + N request gathers under fifo vs priority."""
    from repro.core.engine import Engine
    act_itemsize = jnp.dtype(cfg.dtype).itemsize
    req_bytes = float(prompt_len * cfg.d_model * act_itemsize)
    lat = {}
    for policy in ("fifo", "priority"):
        eng = Engine(wcomm, policy=policy, age_rate=wbytes)
        eng.issue("bcast", wbytes, root=0)
        issue_t = eng.now
        reqs = [eng.issue("allgather", req_bytes / model,
                          members=replicas[r % len(replicas)], priority=1.0)
                for r in range(n_requests)]
        eng.wait_all()
        mean_lat = (sum(h.finished - issue_t for h in reqs)
                    / max(len(reqs), 1))
        lat[policy] = (eng.now, mean_lat)
    serial = wcomm.bcast(wbytes, root=0).time + sum(
        Engine(wcomm).issue("allgather", req_bytes / model,
                            members=replicas[r % len(replicas)]).wait().time
        for r in range(n_requests))
    log.info(f"engine batch (1 weight bcast + {n_requests} request "
             f"gathers): makespan {lat['priority'][0]*1e3:.2f} ms vs "
             f"{serial*1e3:.2f} ms serialized; mean request latency "
             f"{lat['priority'][1]*1e3:.3f} ms (priority) vs "
             f"{lat['fifo'][1]*1e3:.3f} ms (fifo)",
             event="engine_demo",
             makespan_ms=lat["priority"][0] * 1e3,
             serial_ms=serial * 1e3,
             mean_latency_priority_ms=lat["priority"][1] * 1e3,
             mean_latency_fifo_ms=lat["fifo"][1] * 1e3)


def serve(arch: str, n_requests: int, prompt_len: int, gen_len: int,
          mesh_spec: str = "1x1x1", smoke: bool = True, *,
          policy: str = "priority", block_size: int = 8,
          rate: float | None = None, trace: str | None = None,
          monitor: bool = False, metrics_out: str | None = None) -> dict:
    """Run ``n_requests`` through the continuous-batching scheduler on a
    host-device demo mesh (paged KV cache, real greedy decoding).

    ``rate``: open-loop Poisson arrival rate (req/s of *simulation* time);
    default: all requests arrive at t=0 (closed batch).  ``trace`` writes
    a Chrome trace (request lifecycles, engine spans, link occupancy).
    ``monitor`` attaches a :class:`~repro.obs.HealthMonitor` to the engine
    (drift detection + auto-refit, periodic health snapshots in the log);
    ``metrics_out`` writes the run's Prometheus text exposition — a
    scrape-file path that needs no tracer at all.  The result carries the
    ``executor`` so callers can inspect its compiled programs and state."""
    cfg = get_config(arch, smoke=smoke)
    pods, data, model = (int(x) for x in mesh_spec.split("x"))
    mesh = make_test_mesh(pods, data, model)
    tracer = Tracer() if trace else None
    s_max = prompt_len + gen_len
    s_max += (-s_max) % block_size

    params_probe = jax.eval_shape(
        lambda: T.init_model(jax.random.PRNGKey(0), cfg))
    wbytes = _weight_bytes(params_probe)

    # Weight-distribution plan through the single collectives entry point:
    # the multilevel tree broadcast of updated params crosses each slow link
    # exactly once (paper §3.2); on a one-host demo we surface the plan and
    # its postal-model estimate rather than shipping real bytes.
    wcomm = mesh_communicator(mesh, backend="sim", policy="paper")
    if tracer is not None:
        wcomm.tracer = tracer
    bcast_est = wcomm.bcast(wbytes, root=0).time
    crossings = wcomm.slow_crossings('bcast', nbytes=wbytes)
    log.info(f"{wcomm.describe()}; weight bcast "
             f"({wbytes/1e6:.1f} MB): est {bcast_est*1e3:.2f} ms, "
             f"{crossings} slow-link crossing(s)",
             event="setup", weight_mb=wbytes / 1e6,
             bcast_est_ms=bcast_est * 1e3, slow_crossings=crossings)

    replicas = [tuple(range(g * model, (g + 1) * model))
                for g in range(pods * data)]
    _engine_demo(wcomm, wbytes, cfg, prompt_len, model, replicas, n_requests)

    # Continuous batching: requests join/leave the running batch per step;
    # KV lives in on-demand blocks; each step's decode gathers are priced
    # against the periodic weight broadcast by the priority engine.
    from repro.core.engine import Engine
    max_slots = min(n_requests, 8)
    n_blocks = 1 + max_slots * (s_max // block_size)
    act_itemsize = jnp.dtype(cfg.dtype).itemsize
    # one registry spans executor + engine + scheduler + monitor when
    # scraping: the exposition file must read as ONE process, not four
    from repro.obs import MetricsRegistry
    registry = MetricsRegistry() if metrics_out or monitor else None
    ex = JaxExecutor(cfg, mesh, n_blocks=n_blocks, block_size=block_size,
                     max_slots=max_slots, max_blocks=s_max // block_size,
                     metrics=registry)
    eng = Engine(wcomm, policy="fifo" if policy == "fifo" else "priority",
                 age_rate=wbytes, metrics=registry)
    mon = None
    if monitor:
        from repro.obs import HealthMonitor
        mon = HealthMonitor(engine=eng, metrics=registry,
                            log_every=4)
    sch = Scheduler(
        ex, n_blocks=n_blocks, block_size=block_size, max_slots=max_slots,
        s_max=s_max, policy=policy, prefill_token_budget=4 * prompt_len,
        compute_model=default_compute_model(cfg.active_param_count(),
                                            model_size=model),
        engine=eng, replicas=replicas,
        weight_bytes=wbytes,
        gather_bytes=float(cfg.d_model * act_itemsize) / model,
        bcast_every=16, metrics=registry)

    if rate is None:
        arrivals = [0.0] * n_requests
    else:
        arrivals = poisson_arrivals(rate, n_requests / rate, seed=0)[:n_requests]
        arrivals += [n_requests / rate] * (n_requests - len(arrivals))
    reqs = make_requests(arrivals, vocab=cfg.vocab, prompt_len=prompt_len,
                         gen_len=gen_len, slo=SLO(), seed=0)

    t0 = time.monotonic()
    with jax.set_mesh(mesh):
        report = sch.run(reqs)
    dt = time.monotonic() - t0
    gen = np.stack([np.asarray(r.tokens, np.int32)
                    for r in sorted(reqs, key=lambda r: r.rid)])
    s = report.summary()
    log.info(f"{s['n_done']}/{s['n_requests']} done "
             f"({s['n_shed']} shed) in {report.steps} steps / "
             f"{report.now*1e3:.1f} ms simulated; TTFT p50 "
             f"{s['ttft_p50_s']*1e3:.2f} ms p99 {s['ttft_p99_s']*1e3:.2f} ms; "
             f"per-token p50 {s['tpot_p50_s']*1e3:.3f} ms; "
             f"max concurrent {report.max_concurrent}",
             event="report", n_done=s["n_done"], n_shed=s["n_shed"],
             steps=report.steps, simulated_ms=report.now * 1e3,
             ttft_p50_ms=s["ttft_p50_s"] * 1e3,
             ttft_p99_ms=s["ttft_p99_s"] * 1e3,
             tpot_p50_ms=s["tpot_p50_s"] * 1e3,
             max_concurrent=report.max_concurrent)
    if mon is not None:
        snap = mon.snapshot()
        log.info(f"health: {snap['refits']} refit(s), "
                 f"{len(snap['stragglers'])} straggler(s), worst drift "
                 f"{snap['worst_drift']:.3f} over {snap['checks']} checks",
                 event="health", **{k: snap[k] for k in
                                    ("refits", "worst_drift", "checks",
                                     "stragglers", "links")})
    if metrics_out:
        with open(metrics_out, "w") as f:
            f.write(registry.to_prometheus())
        log.info(f"metrics: {len(registry.names())} series -> {metrics_out}",
                 event="metrics", path=metrics_out,
                 series=len(registry.names()))
    if tracer is not None:
        tracer.save(trace)
        log.info(f"trace: {tracer.n_events()} events -> {trace}",
                 event="trace", path=trace, events=tracer.n_events())
    out = {"generated": gen, "seconds": dt,
           "tokens_per_s": n_requests * gen_len / dt,
           "report": s, "executor": ex}
    if mon is not None:
        out["health"] = mon.snapshot()
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt-100m")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--mesh", default="1x1x1")
    ap.add_argument("--full-config", action="store_true",
                    help="use the full (non-smoke) architecture config")
    ap.add_argument("--policy", default="priority",
                    choices=("fifo", "priority", "slo"))
    ap.add_argument("--rate", type=float, default=None,
                    help="open-loop arrival rate (req/s); default: closed batch")
    ap.add_argument("--log-json", action="store_true",
                    help="emit one JSON object per log line instead of the "
                         "human format")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome trace of the serving run "
                         "(open in chrome://tracing or Perfetto)")
    ap.add_argument("--monitor", action="store_true",
                    help="attach a HealthMonitor to the engine: drift "
                         "detection, straggler scoring, auto-refit, "
                         "periodic health snapshots in the log")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the run's metrics as Prometheus text "
                         "exposition (no tracer needed)")
    args = ap.parse_args()
    set_json(args.log_json)
    out = serve(args.arch, args.requests, args.prompt_len, args.gen_len,
                args.mesh, smoke=not args.full_config,
                policy=args.policy, rate=args.rate,
                trace=args.trace, monitor=args.monitor,
                metrics_out=args.metrics_out)
    log.info(f"generated {out['generated'].shape} tokens in "
             f"{out['seconds']:.2f}s ({out['tokens_per_s']:.1f} tok/s)",
             event="done", shape=list(out["generated"].shape),
             seconds=out["seconds"], tokens_per_s=out["tokens_per_s"])
    log.info(f"first request: {out['generated'][0][:16]}",
             event="sample", tokens=out["generated"][0][:16].tolist())


if __name__ == "__main__":
    main()
