"""Step builders: jitted train / prefill / decode steps for any (arch, mesh).

train_step: partial-manual shard_map — MANUAL over the data-parallel axes
(`pod`, `data`) so the paper's multilevel gradient collective is explicit in
the lowered HLO, AUTO (GSPMD) over `model` so tensor-parallel sharding is
propagated by XLA.

serve steps: pure GSPMD jit with sharding constraints (no dp gradient sync
to decompose); decode KV caches shard batch over `data` and the cache
sequence dim over `model` (flash-decode style).
"""
from __future__ import annotations

import functools
from typing import Any

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models import transformer as T
from repro.models import sharding as SH
from repro.models.config import ModelConfig
from repro.optim import adamw
from repro.configs.shapes import ShapeSpec, AUDIO_SRC_FRACTION

__all__ = ["model_dims_of", "make_train_step", "make_prefill_step",
           "make_decode_step", "make_paged_decode_step", "train_in_shardings",
           "cache_shardings", "paged_pool_shardings", "abstract_params",
           "layer_grad_bytes"]


def abstract_params(cfg: ModelConfig):
    return jax.eval_shape(lambda: T.init_model(jax.random.PRNGKey(0), cfg))


def layer_grad_bytes(cfg: ModelConfig, model_size: int = 1) -> list[float]:
    """Per-layer gradient wire bytes (f32 sync) in FORWARD order.

    Backward produces gradients for these entries last-to-first, which is
    exactly the issue order of the engine's bucketed gradient sync — feed
    this list to :func:`repro.core.engine.overlapped_step_times` (the
    train driver's overlap estimate and ``benchmarks/bench_engine.py`` do).
    Entry 0 aggregates the non-layer leaves (embedding/head/norms): their
    gradients arrive at the very end of backward.  ``model_size`` divides
    out the tensor-parallel shard — the sync moves 1/model_size of the
    bytes per model slice.
    """
    aparams = abstract_params(cfg)
    runs = aparams.get("runs", [])
    run_bytes = 0.0
    layers: list[float] = []
    for (kind, n), run in zip(cfg.runs(), runs):
        rb = 4.0 * sum(l.size for l in jax.tree.leaves(run))
        run_bytes += rb
        layers.extend([rb / n] * n)
    total = 4.0 * sum(l.size for l in jax.tree.leaves(aparams))
    return [(total - run_bytes) / model_size] + [b / model_size
                                                for b in layers]


def model_dims_of(params: Any, model_size: int) -> Any:
    """Tree of ints: which dim of each leaf is model-sharded (-1 if none)."""
    specs = SH.param_pspecs(params, model_size)

    def dim(spec):
        for i, s in enumerate(spec):
            if s == "model":
                return i
        return -1

    return jax.tree.map(dim, specs, is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------- #
# Train
# ---------------------------------------------------------------------- #

def make_train_fn(cfg: ModelConfig, opt_cfg: adamw.OptConfig, mesh,
                  comm=None):
    """The raw (un-jitted) shard_map'd train step.

    Structure: OUTER shard_map manual over the dp axes (pod, data) with the
    model axis auto (GSPMD propagates tensor-parallel shardings through the
    fwd/bwd); an INNER shard_map makes `model` manual too for the gradient
    sync + optimizer, because a manual-axis collective on an auto-sharded
    operand makes the partitioner all-gather the auto axis first (measured:
    +52 GB/chip ICI on qwen3 train before this nesting).

    ``comm``: the mesh's :class:`repro.core.Communicator` (jax backend); the
    gradient sync decomposes over its (slow_axis, fast_axes).  Built from the
    mesh when omitted."""
    from repro.launch.mesh import mesh_communicator

    if comm is None:
        comm = mesh_communicator(mesh, backend="jax")
    dp = SH.dp_axes(mesh)                       # ("pod","data") or ("data",)
    slow = comm.slow_axis
    data_size = mesh.shape["data"]
    model_size = mesh.shape.get("model", 1)
    dp_degree = int(np.prod([mesh.shape[a] for a in dp]))

    aparams = abstract_params(cfg)
    mdims = model_dims_of(aparams, model_size)
    opt_specs = adamw.opt_manual_specs(aparams, opt_cfg, data_size, mdims,
                                       slow_axis=slow)
    pspecs = SH.param_pspecs(aparams, model_size)  # model-axis specs
    opt_inner = {"m": pspecs, "v": pspecs, "master": pspecs, "step": P()}
    if opt_cfg.error_feedback:
        # ef leaves carry a leading slow-axis dim ahead of the param dims
        opt_inner["ef"] = jax.tree.map(lambda s: P(None, *s), pspecs,
                                       is_leaf=lambda x: isinstance(x, P))
    model_axis = "model" if model_size > 1 else None

    def update(p_, g_, o_):
        return adamw.apply_updates(
            p_, g_, o_, opt_cfg, slow, data_size, dp_degree, mdims,
            model_axis=model_axis)

    if model_axis:
        # nested shard_map: mesh inferred from the enclosing manual context
        update = jax.shard_map(update,
                           in_specs=(pspecs, pspecs, opt_inner),
                           out_specs=(pspecs, opt_inner),
                           axis_names={"model"}, check_vma=False)

    def step(params, opt, batch):
        loss_val, grads = jax.value_and_grad(
            lambda p: T.loss_fn(p, cfg, batch))(params)
        new_params, new_opt = update(params, grads, opt)
        return new_params, new_opt, lax.pmean(loss_val, dp)

    batch_spec = P(dp)
    return jax.shard_map(
        step,
        mesh=mesh,
        in_specs=(P(), opt_specs, batch_spec),
        out_specs=(P(), opt_specs, P()),
        axis_names=set(dp),
        check_vma=False,
    )


def make_train_step(cfg: ModelConfig, opt_cfg: adamw.OptConfig, mesh):
    return jax.jit(make_train_fn(cfg, opt_cfg, mesh), donate_argnums=(0, 1))


def train_in_shardings(cfg: ModelConfig, opt_cfg: adamw.OptConfig, mesh):
    """jit-level in_shardings for (params, opt, batch) — used by the dry-run
    to .lower() from ShapeDtypeStructs with pinned layouts."""
    aparams = abstract_params(cfg)
    model_size = mesh.shape.get("model", 1)
    pspecs = SH.param_pspecs(aparams, model_size)
    mdims = model_dims_of(aparams, model_size)
    param_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                            is_leaf=lambda x: isinstance(x, P))

    axes = adamw.scatter_axes(aparams, mesh.shape["data"], mdims)

    def combined(spec, ax, leaf):
        dims = list(spec) + [None] * (leaf.ndim - len(spec))
        if ax is not None and dims[ax] is None:
            dims[ax] = "data"
        return NamedSharding(mesh, P(*dims))

    scattered = jax.tree.map(combined, pspecs, axes, aparams,
                             is_leaf=lambda x: isinstance(x, P))
    ms = scattered if opt_cfg.sharded_state else param_sh
    opt_sh = {"m": ms, "v": ms, "master": ms,
              "step": NamedSharding(mesh, P())}
    if opt_cfg.error_feedback:
        # per-(pod, data)-shard residual even in dense mode: leading dim
        # over the slow axis, scatter dim over 'data'.  The shapes are the
        # leaf shapes regardless of opt_cfg.quant_kernel — the fused Pallas
        # quantiser pads its own input to QTILE internally, so the fused-EF
        # buffer needs no extra sharded storage here.
        slow = "pod" if "pod" in mesh.shape else None

        def ef_sharding(spec, ax, leaf):
            dims = list(spec) + [None] * (leaf.ndim - len(spec))
            if ax is not None and dims[ax] is None:
                dims[ax] = "data"
            return NamedSharding(mesh, P(slow, *dims))

        opt_sh["ef"] = jax.tree.map(ef_sharding, pspecs, axes, aparams,
                                    is_leaf=lambda x: isinstance(x, P))
    batch_sh = NamedSharding(mesh, SH.batch_pspec(mesh))
    return param_sh, opt_sh, batch_sh


# ---------------------------------------------------------------------- #
# Serve
# ---------------------------------------------------------------------- #

def _maybe(axis: str, size: int, div: int):
    return axis if size % div == 0 and div > 1 else None


def cache_shardings(cfg: ModelConfig, mesh, cache_abstract) -> Any:
    """Batch over `data`, cache sequence dim over `model` (flash-decode),
    recurrent channel dims over `model`."""
    dsz = mesh.shape.get("data", 1)
    msz = mesh.shape.get("model", 1)

    def spec_for(path, leaf):
        name = ""
        for e in reversed(path):
            if isinstance(e, jax.tree_util.DictKey):
                name = str(e.key)
                break
        shp = leaf.shape  # (run, B, ...)
        b_ax = _maybe("data", shp[1], dsz)
        if name in ("k", "v", "xk", "xv"):
            s_ax = _maybe("model", shp[2], msz)
            return NamedSharding(mesh, P(None, b_ax, s_ax, None, None))
        if name == "h":
            return NamedSharding(mesh, P(None, b_ax, _maybe("model", shp[2], msz)))
        if name == "conv":
            return NamedSharding(mesh, P(None, b_ax, None, _maybe("model", shp[3], msz)))
        if name == "S":
            return NamedSharding(mesh, P(None, b_ax, _maybe("model", shp[2], msz), None, None))
        if name in ("x_tm", "x_cm"):
            return NamedSharding(mesh, P(None, b_ax, _maybe("model", shp[2], msz)))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map_with_path(spec_for, cache_abstract)


def make_prefill_fn(cfg: ModelConfig, mesh, s_max: int):
    def run(params, inputs):
        return T.prefill(params, cfg, inputs, s_max)
    return run


def make_prefill_step(cfg: ModelConfig, mesh, s_max: int):
    return jax.jit(make_prefill_fn(cfg, mesh, s_max))


def make_decode_fn(cfg: ModelConfig, mesh):
    def run(params, cache, tokens, pos):
        return T.decode_step(params, cfg, cache, tokens, pos)
    return run


def make_decode_step(cfg: ModelConfig, mesh):
    return jax.jit(make_decode_fn(cfg, mesh), donate_argnums=(1,))


def paged_pool_shardings(cfg: ModelConfig, mesh, pools_abstract) -> Any:
    """Paged pools have no batch dim — any request's blocks live anywhere in
    the shared pool — so the only safe static partition is over the KV-head
    dim (model axis), mirroring tensor-parallel attention.  Latent (mla)
    pools have no head axis and are replicated."""
    msz = mesh.shape.get("model", 1)

    def spec_for(leaf):
        if leaf.ndim != 5:
            return NamedSharding(mesh, P())
        h_ax = _maybe("model", leaf.shape[3], msz)
        return NamedSharding(mesh, P(None, None, None, h_ax, None))

    return jax.tree.map(spec_for, pools_abstract)


def make_paged_decode_fn(cfg: ModelConfig, mesh):
    def run(params, pools, block_tables, tokens, pos):
        return T.decode_step_paged(params, cfg, pools, block_tables,
                                   tokens, pos)
    return run


def make_paged_decode_step(cfg: ModelConfig, mesh):
    """Jitted paged decode step; the pool buffers are donated so the
    fixed-size cache is updated in place across steps."""
    return jax.jit(make_paged_decode_fn(cfg, mesh), donate_argnums=(1,))
