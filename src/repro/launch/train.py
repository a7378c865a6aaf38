"""Training driver: checkpoint/restart, elastic recovery, straggler
mitigation — the control plane the dry-run's data plane plugs into.

Usage (one device, a TPU chip or the CPU):
  PYTHONPATH=src python -m repro.launch.train --arch gpt-100m --steps 5 \\
      --full-config --seq 2048 --batch 8
CPU demo on four virtual devices (also the e2e example driver):
  XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
  PYTHONPATH=src python -m repro.launch.train --arch gpt-100m --steps 200 \\
      --mesh 1x2x2 --seq 128 --batch 8 --comm multilevel

On a real fleet the same driver runs under ``jax.distributed.initialize``
with the production mesh from launch/mesh.py; nothing in the loop is
CPU-specific.
"""
from __future__ import annotations

import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.checkpoint.manager import CheckpointManager
from repro.configs import get_config
from repro.configs.shapes import ShapeSpec
from repro.data.pipeline import DataPipeline
from repro.launch import step as STEP
from repro.launch.mesh import (make_test_mesh, make_production_mesh,
                               mesh_communicator)
from repro.models import transformer as T
from repro.obs import Tracer, get_logger, set_json
from repro.optim.adamw import OptConfig, init_opt_state
from repro.runtime.fault_tolerance import (FailureInjector, StragglerMonitor,
                                           plan_recovery, pod_member_ranks)

log = get_logger("train")


def build_mesh(spec: str):
    if spec == "production":
        return make_production_mesh(multi_pod=True)
    pods, data, model = (int(x) for x in spec.split("x"))
    return make_test_mesh(pods, data, model)


def _fit_ef(opt_tree: dict, lost_pods, new_pods: int) -> dict:
    """Fit the EF residual's leading pod dim after an elastic mesh change:
    surviving pods keep their own rows (their residuals are still the
    rounding error of the shard they exchange); any other mismatch resets
    to zeros (EF re-warms in one step)."""
    if "ef" not in opt_tree:
        return opt_tree
    lost = set(lost_pods)

    def fit(e):
        if e.shape[0] == new_pods:
            return e
        keep = [p for p in range(e.shape[0]) if p not in lost]
        if len(keep) == new_pods:
            return np.asarray(e)[keep]
        return np.zeros((new_pods,) + e.shape[1:], e.dtype)

    return dict(opt_tree, ef=jax.tree.map(fit, opt_tree["ef"]))


def _fit_batch(arr: np.ndarray, dp: int) -> np.ndarray:
    """Fit a host batch to a (possibly shrunk) dp degree: drop the tail
    rows that no longer tile (the lost pod's share — the straggler-drop
    semantics, the mean renormalises), or wrap-pad tiny batches up."""
    b = arr.shape[0]
    n = (b // dp) * dp
    if n == b:
        return arr
    if n == 0:
        reps = -(-dp // b)
        return np.concatenate([arr] * reps, axis=0)[:dp]
    return arr[:n]


def train(arch: str, steps: int, mesh_spec: str, seq: int, batch: int,
          comm: str, zero1: bool, ckpt_dir: str, ckpt_every: int,
          fail_at: dict[int, list[int]] | None = None,
          smoke: bool = True, log_every: int = 10,
          bucket_mb: float = 0.0, trace: str | None = None) -> dict:
    """Returns summary metrics; restarts from the latest checkpoint if one
    exists (crash-consistent resume).

    ``bucket_mb`` > 0 switches the gradient sync to size-targeted buckets
    (reverse-layer order, one fused collective per bucket — overlappable
    with backward); forces the dense optimizer state since ZeRO-1 scatters
    per leaf.  ``trace`` writes a Chrome trace of the simulated planning
    plane (per-link occupancy, planner decisions) to that path.  The
    summary's ``params`` are the final device-resident parameters."""
    cfg = get_config(arch, smoke=smoke)
    shape = ShapeSpec("custom", "train", seq, batch)
    mesh = build_mesh(mesh_spec)
    bucket_bytes = bucket_mb * 2 ** 20 if bucket_mb > 0 else None
    tracer = Tracer() if trace else None
    if bucket_bytes and zero1 and comm != "flat":
        log.info("bucketed sync: forcing zero1=False (ZeRO-1 "
                 "scatters per leaf)", event="config")
        zero1 = False
    opt_cfg = OptConfig(comm_mode=comm, zero1=zero1, lr=1e-3,
                        warmup_steps=20, total_steps=steps,
                        bucket_bytes=bucket_bytes)
    injector = FailureInjector(fail_at or {})
    straggler = StragglerMonitor()
    ckpt = CheckpointManager(ckpt_dir, keep=2)
    pipe = DataPipeline(cfg, shape)
    losses: list[float] = []
    recoveries = 0
    repairs = 0

    # the planning/estimation plane outlives mesh rebuilds: on an in-place
    # recovery the SAME communicator is repaired (members shrink, cached
    # plans splice out the dead ranks) instead of being re-created
    from repro.core import Communicator
    from repro.launch.mesh import dp_topology
    sim = Communicator(dp_topology(mesh), policy="paper", backend="sim",
                       tracer=tracer)

    def setup(mesh):
        # the single topology-aware entry point: gradient sync decomposes
        # over the communicator's (slow, fast) mesh axes
        mcomm = mesh_communicator(mesh, backend="jax")
        # estimate over the dp ranks only, with each model slice's share of
        # the gradient (the sync moves 1/model_size of the bytes per slice)
        lbytes = STEP.layer_grad_bytes(cfg, mesh.shape.get("model", 1))
        slice_bytes = sum(lbytes)
        est_s = sim.allreduce(slice_bytes).time
        crossings = sim.slow_crossings('allreduce', nbytes=slice_bytes)
        log.info(f"{mcomm.describe()}; grad sync mode '{comm}': "
                 f"est {est_s*1e3:.1f} ms/step, "
                 f"{crossings} slow-link crossing(s)",
                 event="setup", mode=comm, est_ms=est_s * 1e3,
                 slow_crossings=crossings)
        if bucket_bytes:
            # overlapped-sync estimate through the async engine, at the
            # communication-bound threshold (backward compute ~ sync time,
            # spread over layers by gradient size)
            from repro.core.engine import overlapped_step_times
            t_comm = sim.allreduce(slice_bytes).time
            est = overlapped_step_times(
                sim, lbytes,
                [t_comm * b / slice_bytes for b in lbytes],
                bucket_bytes=bucket_bytes)
            log.info(f"bucketed sync ({bucket_mb:g} MiB x "
                     f"{est['n_buckets']} buckets): overlapped est "
                     f"{est['overlapped_s']*1e3:.1f} ms/step vs serial "
                     f"{est['serial_s']*1e3:.1f} ms "
                     f"({est['speedup']:.2f}x, balanced-compute model)",
                     event="bucketed_estimate",
                     n_buckets=est["n_buckets"],
                     overlapped_ms=est["overlapped_s"] * 1e3,
                     serial_ms=est["serial_s"] * 1e3,
                     speedup=est["speedup"])
        fn = jax.jit(STEP.make_train_fn(cfg, opt_cfg, mesh, comm=mcomm),
                     donate_argnums=(0, 1))
        p_sh, o_sh, b_sh = STEP.train_in_shardings(cfg, opt_cfg, mesh)
        return fn, p_sh, o_sh, b_sh

    fn, p_sh, o_sh, b_sh = setup(mesh)
    params_host = jax.tree.map(np.asarray,
                               T.init_model(jax.random.PRNGKey(0), cfg))
    opt_host = jax.tree.map(np.asarray, init_opt_state(
        params_host, opt_cfg, n_slow=mesh.shape.get("pod", 1)))

    start = 0
    latest = ckpt.latest_step()
    if latest is not None:
        state = ckpt.restore(latest, {"params": params_host, "opt": opt_host})
        params_host, opt_host = state["params"], state["opt"]
        start = latest + 1
        log.info(f"resumed from checkpoint step {latest}",
                 event="resume", step=latest)

    params = jax.device_put(params_host, p_sh)
    opt = jax.device_put(opt_host, o_sh)

    step_i = start
    accum = 1
    orig_dp = mesh.shape.get("pod", 1) * mesh.shape.get("data", 1)
    while step_i < steps:
        t0 = time.monotonic()
        # ---- failure injection / elastic recovery --------------------- #
        failed = injector.failed_pods_at(step_i)
        if failed:
            plan = plan_recovery(tuple(mesh.shape.values()),
                                 tuple(mesh.shape.keys()), failed)
            # current-mesh dp ranks of the lost pods, translated to the
            # ORIGINAL rank ids the planning communicator still speaks
            # (its members list is the order-preserved survivor list)
            dead = [sim.members[r] for r in
                    pod_member_ranks(plan.old_shape, plan.axis_names,
                                     list(plan.lost_pods))
                    if r < len(sim.members)]
            in_place = plan.changed and sim.has_quorum(dead)
            log.info(f"step {step_i}: pods {failed} failed -> "
                     f"mesh {plan.old_shape} -> {plan.new_shape}, "
                     f"accum x{plan.accum_factor} "
                     f"({'in-place repair' if in_place else 'restart'})",
                     event="failure", step=step_i, failed=list(failed),
                     accum=plan.accum_factor, in_place=in_place)
            if plan.changed and plan.new_shape[0] >= 1:
                mesh = build_mesh("x".join(map(str, plan.new_shape))
                                  if len(plan.new_shape) == 3 else mesh_spec)
                if in_place:
                    rep = sim.repair(failed=dead)
                    repairs += 1
                    log.info(f"repair: {rep.repaired} plan(s) spliced "
                             f"in place, {rep.evicted} evicted, {rep.kept} "
                             f"kept; {len(rep.members)} dp rank(s) remain",
                             event="repair", step=step_i,
                             repaired=rep.repaired, evicted=rep.evicted,
                             kept=rep.kept, survivors=len(rep.members))
                else:
                    # full restart: the old membership (and its rank
                    # translation) is void — re-plan on the new mesh
                    sim = Communicator(dp_topology(mesh), policy="paper",
                                       backend="sim")
                fn, p_sh, o_sh, b_sh = setup(mesh)
                accum = plan.accum_factor
            # quorum held: carry the LIVE state onto the shrunk mesh — no
            # checkpoint rewind, no step replay.  Below quorum: restore
            # from the last durable checkpoint (live-carry only as the
            # no-checkpoint-yet fallback).
            carry_live = in_place
            n_pods = mesh.shape.get("pod", 1)
            if not in_place:
                recoveries += 1
                latest = ckpt.latest_step()
                if latest is not None:
                    ckpt.wait()
                    state = ckpt.restore(
                        latest, {"params": params_host, "opt": opt_host})
                    params = jax.device_put(state["params"], p_sh)
                    opt = jax.device_put(
                        _fit_ef(state["opt"], plan.lost_pods, n_pods), o_sh)
                    step_i = latest + 1
                    continue
                carry_live = plan.changed
            if carry_live:
                params = jax.device_put(jax.tree.map(np.asarray, params), p_sh)
                opt = jax.device_put(
                    _fit_ef(jax.tree.map(np.asarray, opt),
                            plan.lost_pods, n_pods), o_sh)

        # ---- the actual step (with grad accumulation on shrunk mesh) -- #
        loss_acc = 0.0
        dp_deg = mesh.shape.get("pod", 1) * mesh.shape.get("data", 1)
        for micro in range(accum):
            hb = pipe.host_batch(step_i * accum + micro)
            # batch fitting is ELASTIC-only: a shrunk dp degree may stop
            # tiling the configured batch; a healthy run keeps the loud
            # device_put error on a misconfigured batch
            fit = ((lambda v: _fit_batch(np.asarray(v), dp_deg))
                   if dp_deg != orig_dp else np.asarray)
            gb = {k: jax.device_put(fit(v), b_sh) for k, v in hb.items()}
            params, opt, loss = fn(params, opt, gb)
            loss_acc += float(loss)
        losses.append(loss_acc / accum)

        dt = time.monotonic() - t0
        if straggler.observe(step_i, dt):
            log.info(f"step {step_i}: straggler ({dt:.2f}s vs median "
                     f"{straggler.median:.2f}s) — bounded-staleness drop "
                     f"logged", event="straggler", step=step_i, dt_s=dt,
                     median_s=straggler.median)
        if ckpt_every and step_i % ckpt_every == 0 and step_i > start:
            params_host = jax.tree.map(np.asarray, params)
            opt_host = jax.tree.map(np.asarray, opt)
            ckpt.save(step_i, {"params": params_host, "opt": opt_host})
        if step_i % log_every == 0:
            log.info(f"step {step_i:5d} loss {losses[-1]:.4f} "
                     f"({dt*1e3:.0f} ms)", event="step", step=step_i,
                     loss=losses[-1], dt_ms=dt * 1e3)
        step_i += 1

    ckpt.wait()
    if tracer is not None:
        tracer.save(trace)
        log.info(f"trace: {tracer.n_events()} events -> {trace}",
                 event="trace", path=trace, events=tracer.n_events())
    return {"losses": losses, "recoveries": recoveries,
            "repairs": repairs,
            "stragglers": len(straggler.dropped_steps),
            "final_loss": losses[-1] if losses else None,
            "params": params}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt-100m")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--mesh", default="1x1x1")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--comm", default="multilevel",
                    choices=["flat", "multilevel", "multilevel_compress"])
    ap.add_argument("--no-zero1", action="store_true")
    ap.add_argument("--full-config", action="store_true",
                    help="use the full (non-smoke) architecture config")
    ap.add_argument("--ckpt-dir", default="checkpoints")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--bucket-mb", type=float, default=0.0,
                    help="size-targeted gradient buckets (MiB); 0 = one "
                         "monolithic sync")
    ap.add_argument("--log-json", action="store_true",
                    help="emit one JSON object per log line instead of the "
                         "human format")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome trace of the planning plane "
                         "(open in chrome://tracing or Perfetto)")
    args = ap.parse_args()
    set_json(args.log_json)
    out = train(args.arch, args.steps, args.mesh, args.seq, args.batch,
                args.comm, not args.no_zero1, args.ckpt_dir, args.ckpt_every,
                smoke=not args.full_config, bucket_mb=args.bucket_mb,
                trace=args.trace)
    log.info(f"done: final_loss={out['final_loss']:.4f} "
             f"recoveries={out['recoveries']} repairs={out['repairs']} "
             f"stragglers={out['stragglers']}",
             event="done", final_loss=out["final_loss"],
             recoveries=out["recoveries"], repairs=out["repairs"],
             stragglers=out["stragglers"])


if __name__ == "__main__":
    main()
