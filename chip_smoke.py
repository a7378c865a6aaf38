"""Chip smoke test: the serving and training entry points on a TPU.

  python chip_smoke.py             one chip: TinyLlama-1.1B (published
                                   widths, random weights) served through
                                   launch/serve.py, and gpt-100m trained
                                   through launch/train.py
  python chip_smoke.py --chips 4   one 2x2 host: the collective plane
                                   (Communicator jax and ppermute backends
                                   against lax.psum and a plain broadcast),
                                   and multilevel-vs-flat training on 1x2x2

Every phase runs in this one process: a chip belongs to one process.  A
failed check or an exception exits non-zero; a host without a TPU exits 1
before any phase runs.  The last stdout line is the JSON result.  Wall
times come from the host clock around work that ends in
``block_until_ready``; they are one smoke run, not a benchmark.

The compile cache lives where ``JAX_COMPILATION_CACHE_DIR`` says, and in
``<repo>/.jax_cache`` when it is unset.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(REPO, ".jax_cache"))
sys.path.insert(0, os.path.join(REPO, "src"))

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

OUT = os.path.join(REPO, "chiprun_out", "chip_smoke")

# Max |kernel - reference| of the last-position prefill logits, as a share
# of max |reference|.  Both programs run the same bf16 model; they differ
# only in the attention lowering (Pallas kernel with f32 matmuls against the
# jnp flash with XLA's default-precision f32 dots), whose bf16-rounded
# outputs then diverge through 22 residual layers.  On a v5e the kernel
# lands near 0.8%, and the two broken-attention controls of check_prefill
# near 25% and 32%: the tolerance sits about a factor of 5 from each.
PREFILL_REL_TOL = 0.05
# Loss agreement of multilevel vs flat gradient sync, as in
# tests/test_collectives.py::test_zero1_multilevel_trains_identically_to_flat.
LOSS_RTOL = LOSS_ATOL = 5e-3
# f32 all-reduce of four N(0, 1) rows in a different summation order.
ALLREDUCE_ATOL = 1e-4


class SmokeFailure(AssertionError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(**kv) -> None:
    print("chip_smoke " + json.dumps(kv, sort_keys=True), flush=True)


def fresh_dir(name: str) -> str:
    path = os.path.join(OUT, name)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path


def peak_bytes() -> int | None:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def timed(fn, *args, repeats: int = 1):
    """Mean wall seconds of ``fn(*args)`` ended with block_until_ready,
    after one untimed call."""
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / repeats, out


# ---------------------------------------------------------------------- #
# One chip
# ---------------------------------------------------------------------- #

def phase_serve(arch: str = "tinyllama-1.1b", n_requests: int = 4,
                prompt_len: int = 512, gen_len: int = 16,
                smoke: bool = False) -> None:
    from repro.configs import get_config
    from repro.launch.serve import serve
    from repro.serving import SLO, make_requests

    cfg = get_config(arch, smoke=smoke)
    t0 = time.perf_counter()
    out = serve(arch, n_requests, prompt_len, gen_len, mesh_spec="1x1x1",
                smoke=smoke)
    ex = out["executor"]
    jax.block_until_ready(ex.pools)
    serve_s = time.perf_counter() - t0
    gen = out["generated"]
    check(out["report"]["n_done"] == n_requests,
          f"{out['report']['n_done']}/{n_requests} requests finished")
    check(gen.shape == (n_requests, gen_len), f"generated {gen.shape}")
    check(bool(((gen >= 0) & (gen < cfg.vocab)).all()),
          "generated token outside the vocabulary")

    # the prompts as serve() drew them
    reqs = make_requests([0.0] * n_requests, vocab=cfg.vocab,
                         prompt_len=prompt_len, gen_len=gen_len, slo=SLO(),
                         seed=0)
    bs = ex.block_size
    S_p = -(-prompt_len // bs) * bs
    toks = np.zeros((1, S_p), np.int32)
    toks[0, :prompt_len] = reqs[0].prompt
    toks, last = jnp.asarray(toks), jnp.asarray([prompt_len - 1])

    with jax.set_mesh(ex.mesh):      # the context serve() ran the programs in
        compile_s, kernel_logits = check_prefill(ex, cfg, S_p, toks, last)
        check(int(np.argmax(kernel_logits)) == int(gen[0, 0]),
              "prefill argmax is not the first served token")
        prefill_s, _ = timed(ex.prefill_fn(S_p), ex.params, toks, last,
                             repeats=3)
        # serve() released every slot; prefill them again into blocks of
        # their own, one spare block each, so the timed decode step reads
        # the KV state of every request rather than the null block
        slots = list(range(n_requests))
        for s, r in enumerate(reqs):
            base = 1 + s * ex.max_blocks
            ex.prefill(s, list(range(base, base + S_p // bs)), r.prompt)
            ex.extend(s, base + S_p // bs)
        first = list(gen[:, 0])

        def decode():
            ex.decode(slots, first, [prompt_len] * n_requests)
            return ex.pools

        decode_s, _ = timed(decode, repeats=5)
    say(phase="serve", arch=cfg.name, requests=n_requests,
        prompt_len=prompt_len, gen_len=gen_len,
        serve_wall_s_incl_compile=serve_s, prefill_compile_s=compile_s,
        prefill_s=prefill_s, decode_step_s=decode_s,
        decode_pos=prompt_len, peak_bytes_in_use=peak_bytes())


def check_prefill(ex, cfg, S_p: int, toks, last):
    """Compile the served prefill, require the Pallas kernel in its HLO,
    and compare its logits with the same prefill lowered through the jnp
    flash attention.  Two broken attentions, lowered the same way, show
    that the tolerance separates a wrong kernel from bf16 drift.  Returns
    (compile seconds, last-position logits)."""
    from unittest import mock

    from repro.kernels import backend
    from repro.models import layers
    from repro.models import transformer as T

    jnp_attention = layers.chunked_attention
    controls = {
        # a wrong GQA mapping: every query group reads its neighbour's kv
        "kv_heads_rolled": lambda q, k, v, **kw: jnp_attention(
            q, jnp.roll(k, 1, axis=2), jnp.roll(v, 1, axis=2), **kw),
        "attention_zeroed": lambda q, k, v, **kw: jnp.zeros_like(q),
    }

    def compile_jnp(attention=jnp_attention):
        with mock.patch.object(backend, "on_tpu", lambda: False), \
                mock.patch.object(layers, "chunked_attention", attention):
            return jax.jit(lambda p, t, lp: T.prefill(
                p, cfg, {"tokens": t}, S_p, last_pos=lp,
                full_local_cache=True)[0]).lower(ex.params, toks,
                                                 last).compile()

    jax.config.update("jax_enable_compilation_cache", False)  # a cold compile
    try:
        t0 = time.perf_counter()
        kernel = ex.prefill_fn(S_p).lower(ex.params, toks, last).compile()
        compile_s = time.perf_counter() - t0
        ref = compile_jnp()
        broken = {name: compile_jnp(f) for name, f in controls.items()}
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    check("tpu_custom_call" in kernel.as_text(),
          "served prefill has no Pallas kernel (tpu_custom_call)")
    for name, prog in [("reference", ref), *broken.items()]:
        check("tpu_custom_call" not in prog.as_text(),
              f"{name} prefill still runs a Pallas kernel")

    def last_logits(prog):
        out = prog(ex.params, toks, last)
        out = out[0] if isinstance(out, tuple) else out
        return np.asarray(out, np.float32)[0, -1]

    got, want = last_logits(kernel), last_logits(ref)
    scale = float(np.abs(want).max())

    def rel_err(x):
        return float(np.abs(x - want).max()) / scale

    err = rel_err(got)
    control_err = {name: rel_err(last_logits(prog))
                   for name, prog in broken.items()}
    say(phase="serve_prefill_check", max_abs_ref=scale, rel_err=err,
        rel_tol=PREFILL_REL_TOL, control_rel_err=control_err,
        argmax_equal=bool(got.argmax() == want.argmax()))
    check(np.isfinite(got).all(), "non-finite prefill logits")
    check(err <= PREFILL_REL_TOL,
          f"prefill logits differ from the jnp reference: relative error "
          f"{err} > {PREFILL_REL_TOL}")
    check(got.argmax() == want.argmax(),
          "prefill argmax differs from the jnp reference")
    for name, e in control_err.items():
        check(e > PREFILL_REL_TOL,
              f"control {name} passes the tolerance: {e} <= "
              f"{PREFILL_REL_TOL}; the check cannot see a broken attention")
    return compile_s, got


def phase_train(arch: str = "gpt-100m", steps: int = 5, seq: int = 2048,
                batch: int = 8, smoke: bool = False) -> None:
    from repro.launch.train import train

    t0 = time.perf_counter()
    res = train(arch, steps, "1x1x1", seq, batch, comm="multilevel",
                zero1=True, ckpt_dir=fresh_dir("ckpt_train"), ckpt_every=0,
                smoke=smoke, log_every=1)
    jax.block_until_ready(res["params"])
    wall = time.perf_counter() - t0
    losses = res["losses"]
    check(len(losses) == steps, f"{len(losses)} of {steps} steps ran")
    check(bool(np.isfinite(losses).all()), f"non-finite loss: {losses}")
    say(phase="train", arch=arch, steps=steps, seq=seq, batch=batch,
        losses=losses, train_wall_s_incl_compile=wall,
        peak_bytes_in_use=peak_bytes())


# ---------------------------------------------------------------------- #
# Four chips
# ---------------------------------------------------------------------- #

def check_spread(tree, n: int, what: str) -> None:
    for leaf in jax.tree.leaves(tree):
        check(len(leaf.sharding.device_set) == n,
              f"{what}: array on {len(leaf.sharding.device_set)} of {n} "
              f"devices")


def phase_collectives(sizes_mib=(1, 64), root: int = 3) -> None:
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_test_mesh, mesh_communicator

    mesh = make_test_mesh(pods=2, data=2, model=1)
    dp = ("pod", "data")
    flat = dp + ("model",)
    n_dev = mesh.devices.size

    def run(f, x):
        out = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P(dp),
                                    out_specs=P(dp), check_vma=False))(x)
        check_spread(out, n_dev, "collective output")
        return out

    def max_err(a, b):
        return float(jnp.max(jnp.abs(a - b)))

    jx = mesh_communicator(mesh, backend="jax")
    cases = [("jax", "allreduce_tree multilevel", None, "allreduce")]
    cases += [("ppermute", f"{op} {alg or 'tree'}", alg, op)
              for alg, op in ((None, "bcast"), ("sag", "bcast"),
                              (None, "allreduce"), ("rsag", "allreduce"))]
    for mib in sizes_mib:
        n = mib * 2 ** 20 // 4                       # f32 elements per rank
        x = jax.device_put(
            jax.random.normal(jax.random.PRNGKey(mib), (n_dev, n)),
            NamedSharding(mesh, P(dp)))
        want = {"allreduce": run(lambda v: lax.psum(v, dp), x),
                "bcast": run(lambda v: lax.psum(
                    jnp.where(lax.axis_index(dp) == root, v, 0.0), dp), x)}
        for backend, name, alg, op in cases:
            if backend == "jax":
                got = run(lambda v: jx.allreduce_tree(v, mode="multilevel"),
                          x)
            else:
                comm = mesh_communicator(mesh, backend="ppermute",
                                         axis=flat, algorithm=alg)
                fn = ((lambda v: comm.bcast(v, root=root)) if op == "bcast"
                      else comm.allreduce)
                got = run(fn, x)
            err = max_err(got, want[op])
            say(phase="collectives", mib=mib, backend=backend, case=name,
                reference="lax.psum" if op == "allreduce"
                else "psum broadcast", max_abs_err=err)
            # a broadcast moves bytes and must be exact
            check(err <= (ALLREDUCE_ATOL if op == "allreduce" else 0.0),
                  f"{backend} {name} at {mib} MiB: max abs err {err}")


def phase_train_multilevel(arch: str = "gpt-100m", steps: int = 3,
                           seq: int = 2048, batch: int = 8,
                           smoke: bool = False) -> None:
    from repro.launch.train import train

    losses = {}
    for comm in ("flat", "multilevel"):
        res = train(arch, steps, "1x2x2", seq, batch, comm=comm, zero1=True,
                    ckpt_dir=fresh_dir(f"ckpt_{comm}"), ckpt_every=0,
                    smoke=smoke, log_every=1)
        check_spread(res["params"], 4, f"{comm} params")
        losses[comm] = res["losses"]
        check(bool(np.isfinite(res["losses"]).all()),
              f"{comm}: non-finite loss {res['losses']}")
    diff = np.abs(np.asarray(losses["flat"]) - np.asarray(losses["multilevel"]))
    say(phase="train_multilevel_vs_flat", arch=arch, mesh="1x2x2",
        losses=losses, max_abs_loss_diff=float(diff.max()))
    np.testing.assert_allclose(losses["flat"], losses["multilevel"],
                               rtol=LOSS_RTOL, atol=LOSS_ATOL)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: serving + training on one chip; 4: the "
                         "collective plane and multilevel training on a "
                         "2x2 host")
    args = ap.parse_args()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (found {dev.platform}); nothing run",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              f"device(s)", file=sys.stderr)
        return 1
    cache = os.environ["JAX_COMPILATION_CACHE_DIR"]
    say(device_kind=dev.device_kind, devices=len(devices),
        jax=jax.__version__, compile_cache=cache,
        cache_entries_at_start=len(os.listdir(cache))
        if os.path.isdir(cache) else 0)
    if args.chips == 1:
        phase_serve()
        phase_train()
    else:
        phase_collectives()
        phase_train_multilevel()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
