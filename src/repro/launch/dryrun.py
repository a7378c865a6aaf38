import os
os.environ["XLA_FLAGS"] = (os.environ.get("_DRYRUN_EXTRA_XLA", "") +
                           " --xla_force_host_platform_device_count=512").strip()
# Persistent compilation cache makes re-sweeps (perf iterations) cheap: where
# JAX_COMPILATION_CACHE_DIR says, else one fixed directory in the checkout.
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "..", ".jax_cache")))
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "1")

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this proves (a) the sharding config is coherent on the
production mesh (16x16 single-pod AND 2x16x16 multi-pod), (b) it fits
memory (memory_analysis), and (c) extracts the roofline terms
(cost_analysis + HLO collective census).

Results accumulate in benchmarks/results/dryrun.json (incremental; safe to
re-run cell by cell).

Usage:
  python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--mesh both] [--comm multilevel]
"""
import argparse
import json
import sys
import time
import traceback

import numpy as np
import jax

from repro.configs import get_config, list_archs, SHAPES
from repro.configs.shapes import input_specs, cache_specs, applicable
from repro.core.costmodel import TPU_V5E, roofline_terms
from repro.launch import hlo_census
from repro.launch.mesh import make_production_mesh
from repro.launch import step as STEP
from repro.optim.adamw import OptConfig

RESULTS = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "benchmarks", "results", "dryrun.json")


def _load() -> dict:
    try:
        with open(RESULTS) as f:
            return json.load(f)
    except Exception:
        return {}


def _save(res: dict) -> None:
    os.makedirs(os.path.dirname(RESULTS), exist_ok=True)
    tmp = RESULTS + ".tmp"
    with open(tmp, "w") as f:
        json.dump(res, f, indent=1, sort_keys=True)
    os.replace(tmp, RESULTS)


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               comm_mode: str = "multilevel", zero1: bool = True,
               parallel_block: bool = False):
    """Lower+compile one cell; return the roofline record."""
    import dataclasses
    cfg = get_config(arch)
    if parallel_block:
        cfg = dataclasses.replace(cfg, parallel_block=True)
    shape = SHAPES[shape_name]
    ok, why = applicable(cfg, shape)
    if not ok:
        return {"skipped": why}
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    chips_per_pod = chips // mesh.shape.get("pod", 1)
    t0 = time.time()

    from repro.optim import adamw
    from repro.models.sharding import param_shardings, batch_pspec
    from jax.sharding import NamedSharding, PartitionSpec as P

    if shape.kind == "train":
        opt_cfg = OptConfig(comm_mode=comm_mode, zero1=zero1)
        raw = STEP.make_train_fn(cfg, opt_cfg, mesh)
        p_sh, o_sh, b_sh = STEP.train_in_shardings(cfg, opt_cfg, mesh)
        aparams = STEP.abstract_params(cfg)
        aopt = jax.eval_shape(
            lambda p: adamw.init_opt_state(
                p, opt_cfg, n_slow=mesh.shape.get("pod", 1)), aparams)
        batch = input_specs(cfg, shape)
        fn = jax.jit(raw, donate_argnums=(0, 1),
                     in_shardings=(p_sh, o_sh,
                                   jax.tree.map(lambda _: b_sh, batch)))
        with jax.set_mesh(mesh):
            lowered = fn.lower(aparams, aopt, batch)
            compiled = lowered.compile()
    elif shape.kind == "prefill":
        raw = STEP.make_prefill_fn(cfg, mesh, s_max=shape.seq_len)
        aparams = STEP.abstract_params(cfg)
        p_sh = param_shardings(aparams, mesh)
        b_sh = NamedSharding(mesh, batch_pspec(mesh))
        batch = input_specs(cfg, shape)
        fn = jax.jit(raw,
                     in_shardings=(p_sh, jax.tree.map(lambda _: b_sh, batch)))
        with jax.set_mesh(mesh):
            lowered = fn.lower(aparams, batch)
            compiled = lowered.compile()
    else:  # decode
        raw = STEP.make_decode_fn(cfg, mesh)
        aparams = STEP.abstract_params(cfg)
        p_sh = param_shardings(aparams, mesh)
        acache = cache_specs(cfg, SHAPES[shape_name])
        c_sh = STEP.cache_shardings(cfg, mesh, acache)
        inp = input_specs(cfg, shape)
        tok_sh = NamedSharding(mesh, P("data" if shape.global_batch
                                       % mesh.shape["data"] == 0 else None))
        fn = jax.jit(raw, donate_argnums=(1,),
                     in_shardings=(p_sh, c_sh, tok_sh,
                                   NamedSharding(mesh, P())))
        with jax.set_mesh(mesh):
            lowered = fn.lower(aparams, acache, inp["tokens"],
                               inp["pos"])
            compiled = lowered.compile()

    compile_s = time.time() - t0
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    cens = hlo_census.census(compiled.as_text(), chips_per_pod)

    flops = float(cost.get("flops", 0.0))
    bytes_acc = float(cost.get("bytes accessed", 0.0))
    terms = roofline_terms(
        hlo_flops=flops, hlo_bytes=bytes_acc,
        ici_bytes=cens["ici_bytes"],     # census bytes are per-chip already
        dcn_bytes=cens["dcn_bytes"],
        chips=chips, hw=TPU_V5E)
    # model flops: 6*N*D for train, 2*N*D for inference (per token)
    cfg_full = cfg
    n_active = cfg_full.active_param_count()
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    mult = 6 if shape.kind == "train" else 2
    model_flops = mult * n_active * tokens
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "comm_mode": comm_mode, "zero1": zero1,
        "compile_s": round(compile_s, 1),
        "bytes_per_device": int(mem.output_size_in_bytes
                                + mem.temp_size_in_bytes
                                + mem.argument_size_in_bytes
                                - mem.alias_size_in_bytes)
        if hasattr(mem, "temp_size_in_bytes") else str(mem),
        "hlo_gflops": flops / 1e9,
        "hlo_gbytes": bytes_acc / 1e9,
        "ici_mb_per_chip": cens["ici_bytes"] / 1e6,
        "dcn_mb_per_chip": cens["dcn_bytes"] / 1e6,
        "collective_counts": cens["counts"],
        "model_gflops": model_flops / 1e9,
        "useful_flops_frac": model_flops / flops if flops else None,
        **{k: v for k, v in terms.items()},
    }
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--comm", default="multilevel",
                    choices=["flat", "multilevel", "multilevel_compress"])
    ap.add_argument("--no-zero1", action="store_true")
    ap.add_argument("--parallel-block", action="store_true",
                    help="PaLM-style parallel residual (perf variant)")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--tag", default=None, help="results key suffix")
    args = ap.parse_args()

    archs = list_archs()[:10] if args.all or not args.arch else [args.arch]
    shapes = list(SHAPES) if args.all or not args.shape else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    res = _load()
    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                key = f"{arch}|{shape}|{'2x16x16' if mp else '16x16'}|{args.comm}" \
                      + (f"|{args.tag}" if args.tag else "")
                if key in res and "error" not in res[key]:
                    print(f"SKIP (cached) {key}")
                    continue
                print(f"RUN {key} ...", flush=True)
                try:
                    rec = lower_cell(arch, shape, mp, args.comm,
                                     zero1=not args.no_zero1,
                                     parallel_block=args.parallel_block)
                    rec["tag"] = args.tag
                    res[key] = rec
                    msg = rec.get("skipped") or (
                        f"ok compile={rec['compile_s']}s "
                        f"bound={rec.get('bound')} step={rec.get('step_s'):.4f}s")
                    print(f"  -> {msg}", flush=True)
                except Exception as e:
                    failures += 1
                    res[key] = {"error": f"{type(e).__name__}: {e}",
                                "trace": traceback.format_exc()[-2000:]}
                    print(f"  -> FAIL {type(e).__name__}: {e}", flush=True)
                _save(res)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
