"""A training cell: the jitted step ``launch/train.py`` runs
(``launch/step.make_train_fn`` under ``jax.jit`` with the parameters and
optimizer state donated), on parameters and optimizer state made on the
device with the shardings of ``train_in_shardings``.

Set-up builds that one compiled step and its state, and drives it from the
seed through the job's first three steps with the window's own feed; the
window then goes on with the same object.  Every step reads its loss on
the host, as ``train()`` does.  The readings for ``correct`` come from
those three steps: each step's loss, the first gradient as the optimizer
got it (its first moment over ``1 - beta1``), and the change of the master
weights over the three.  The first gradient is also kept whole, on the
host, for the norm of its difference from the reference's.
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np

from bench import harness, loadgen

CHECK_STEPS = 3
# leaves whose reference gradient lies under this share of the median
# leaf's are nought to rounding: Adam moves them by round-off alone
STILL_LEAF = 1e-3


def opt_config(job: dict):
    from repro.optim.adamw import OptConfig
    o = job["optimizer"]
    return OptConfig(lr=float(o["lr"]), betas=tuple(o["betas"]),
                     eps=float(o["eps"]),
                     weight_decay=float(o["weight_decay"]),
                     clip_norm=float(o["clip_norm"]),
                     warmup_steps=int(o["warmup_steps"]),
                     total_steps=int(o["total_steps"]),
                     zero1=bool(job["zero1"]), comm_mode=job["comm_mode"])


def build(cell, seed: int, mesh):
    """(step, params, opt, feed, rows): the program's jitted step with
    its state, and the feed of global batches."""
    import jax
    from repro.launch import step as STEP
    from repro.launch.mesh import mesh_communicator
    from repro.models import transformer as T
    from repro.optim.adamw import init_opt_state

    from bench import weights

    c, job = cell.config, cell.traffic
    cfg = harness.model_config(c)
    oc = opt_config(job)
    fn = jax.jit(STEP.make_train_fn(cfg, oc, mesh,
                                    comm=mesh_communicator(mesh,
                                                           backend="jax")),
                 donate_argnums=(0, 1))
    p_sh, o_sh, b_sh = STEP.train_in_shardings(cfg, oc, mesh)
    params = weights.make_params(c, seed, out_shardings=p_sh)
    want = jax.tree.structure(jax.eval_shape(
        lambda: T.init_model(jax.random.PRNGKey(0), cfg)))
    if jax.tree.structure(params) != want:
        raise RuntimeError("the weights' tree differs from the program's "
                           f"init_model: {want}")
    n_slow = mesh.shape.get("pod", 1)
    opt = jax.jit(lambda p: init_opt_state(p, oc, n_slow=n_slow),
                  out_shardings=o_sh)(params)
    dp = mesh.shape.get("pod", 1) * mesh.shape.get("data", 1)
    rows = int(job["batch_per_chip"]) * dp

    def feed(step: int) -> dict:
        t = loadgen.train_rows(job, cfg.vocab, seed, step, rows)
        return {"tokens": jax.device_put(t[:, :-1], b_sh),
                "labels": jax.device_put(t[:, 1:], b_sh)}

    return fn, params, opt, feed, rows


def _norms(tree) -> dict:
    import jax
    import jax.numpy as jnp
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    vals = jax.jit(lambda xs: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in xs])([x for _, x in flat])
    return {jax.tree_util.keystr(p): float(v)
            for (p, _), v in zip(flat, vals)}


def _change_norms(c: dict, seed: int, master, shardings) -> dict:
    """Leaf norms of master - the seed's weights (as float32)."""
    import jax
    import jax.numpy as jnp
    from bench import weights
    w0 = weights.make_params(c, seed, out_shardings=shardings)
    diff = jax.jit(lambda a, b: jax.tree.map(
        lambda x, y: x - y.astype(jnp.float32), a, b))(master, w0)
    del w0
    return _norms(diff)


def run(cell, seed: int, seconds: float, mesh, *, tracer=None,
        step_fault=None) -> dict:
    """Set-up (three checked steps), then steps until ``seconds`` have
    passed.  ``step_fault`` lets a test break the step underneath."""
    import jax
    from repro.launch import step as STEP

    fn, params, opt, feed, rows = build(cell, seed, mesh)
    if step_fault is not None:
        fn = step_fault(fn)
    b1 = float(cell.traffic["optimizer"]["betas"][0])
    losses, grad = [], None
    with jax.set_mesh(mesh):
        for i in range(CHECK_STEPS):
            params, opt, loss = fn(params, opt, feed(i))
            losses.append(float(loss))
            if i == 0:
                grad = {k: v / (1.0 - b1) for k, v in _norms(opt["m"]).items()}
                grad_tree = _by_path(jax.device_get(jax.jit(
                    lambda m: jax.tree.map(lambda x: x / (1.0 - b1), m))(
                        opt["m"])))
        p_sh = STEP.train_in_shardings(harness.model_config(cell.config),
                                       opt_config(cell.traffic), mesh)[0]
        change = _change_norms(cell.config, seed, opt["master"], p_sh)
        if tracer is not None:
            tracer.start()
        t0 = time.perf_counter()
        step_times, step_losses, i = [], [], CHECK_STEPS
        annotate = tracer.annotate if tracer else None
        try:
            while True:
                a = time.perf_counter()
                if a >= t0 + seconds:
                    break
                if annotate:
                    with annotate("bench.feed"):
                        batch = feed(i)
                    with annotate("bench.step"):
                        params, opt, loss = fn(params, opt, batch)
                        loss = float(loss)
                else:
                    params, opt, loss = fn(params, opt, feed(i))
                    loss = float(loss)
                step_losses.append(loss)
                step_times.append((a, time.perf_counter()))
                i += 1
        finally:
            if tracer is not None and tracer.active:
                tracer.stop()
    t1 = step_times[-1][1] if step_times else time.perf_counter()
    return {"window_start": t0, "window_end": t1, "steps": step_times,
            "step_losses": step_losses, "rows": rows,
            "tokens_per_step": rows * int(cell.traffic["seq_len"]),
            "loss": losses, "grad": grad, "change": change,
            "grad_tree": grad_tree,
            "devices": list(mesh.devices.flat), "state": (params, opt)}


def _by_path(tree) -> dict:
    import jax
    return {jax.tree_util.keystr(p): np.asarray(x, np.float32)
            for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def diff_by_leaf(got: dict, want: dict) -> dict:
    """Per leaf: norm(program - reference) over the larger of that leaf's
    reference norm and the median leaf's."""
    norms = {k: float(np.linalg.norm(v)) for k, v in want.items()}
    med = float(np.median(list(norms.values())))
    return {k: float(np.linalg.norm(got[k] - want[k])) / max(norms[k], med)
            for k in want}


def gap_by_leaf(got: dict, want: dict, keep=None) -> float:
    """Worst leaf of |norm(program) - norm(reference)| over the larger of
    that leaf's reference norm and the median leaf's."""
    keys = [k for k in want if keep is None or k in keep]
    med = float(np.median([want[k] for k in keys]))
    return max(abs(got[k] - want[k]) / max(want[k], med) for k in keys)


def readings(ref: dict, out: dict) -> dict:
    g_med = float(np.median(list(ref["grad"].values())))
    moving = {k for k, v in ref["grad"].items() if v >= STILL_LEAF * g_med}
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(out["loss"],
                                                       ref["loss"]))
    diff = diff_by_leaf(out["grad_tree"], ref["grad_tree"])
    print("grad_diff by leaf " + json.dumps(diff), file=sys.stderr)
    return {"loss_gap": float(loss_gap),
            "grad_norm_gap": float(gap_by_leaf(out["grad"], ref["grad"])),
            "grad_diff": max(diff.values()),
            "grad_diff_median": float(np.median(list(diff.values()))),
            "update_norm_gap": float(gap_by_leaf(out["change"],
                                                 ref["change"], moving))}


def check(cell, seed: int, out: dict, control: bool = False) -> dict:
    """Readings for ``correct``, from the plain reference's three steps on
    the same batches.  ``control`` adds the float8 reference's readings
    against the float32 one, under ``control_<name>``."""
    ref_mod = harness.reference_module(cell.config)
    batches = [loadgen.train_rows(cell.traffic, cell.config["vocab_size"],
                                  seed, i, out["rows"])
               for i in range(CHECK_STEPS)]
    ref = ref_mod.train_steps(cell.config, cell.traffic, seed, batches,
                              devices=out["devices"])
    got = readings(ref, out)
    if control:
        q = ref_mod.train_steps(cell.config, cell.traffic, seed, batches,
                                quant=True, devices=out["devices"])
        got |= {"control_" + k: v for k, v in readings(ref, q).items()}
    return got
