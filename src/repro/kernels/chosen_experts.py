"""Pallas TPU kernel: a MoE block's few (token, choice) pairs, each read
straight from its held expert in the run's stacked weights.

For one decoded token a MoE layer needs the top_k experts it chose, of
which a device holding a share of the experts has only some.  Slicing the
layer's whole expert set out of the stacked run and multiplying all of it
reads every held expert; this kernel reads only the chosen ones.

Grid: one step per choice.  The scalar-prefetched layer index and each
step's local expert id drive the weights' ``BlockSpec`` index maps, so a
step DMAs that expert's ``(D, F)`` / ``(F, D)`` blocks of the stacked
``(layers, held, ...)`` arrays and nothing else.  Choices arrive sorted by
local expert, those of experts not held last: a step whose block index is
the previous step's fetches nothing new, so a token reads each held expert
it chose once, and the choices not held (mapped to the last held block)
read nothing.  Their output rows are zero.

Numerics follow ``models.layers._moe_block``'s ragged dots: operands in the
weights' dtype, float32 accumulation, ``h``, ``g``, the gated activation
and the output rounded to the operands' dtype.

Validated on CPU via interpret mode against the ragged path (see
tests/test_models.py); ``interpret=None`` auto-detects the backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

__all__ = ["chosen_expert_matmul"]


def _kernel(layer_ref, expert_ref, block_ref, x_ref, w_in_ref, w_gate_ref,
            w_out_ref, o_ref, *, held: int):
    del layer_ref, block_ref  # read by the index maps
    held_here = expert_ref[pl.program_id(0)] < held

    @pl.when(held_here)
    def _():
        dt = o_ref.dtype
        x = x_ref[0]                                          # (1, D)
        h = jnp.dot(x, w_in_ref[0, 0],
                    preferred_element_type=jnp.float32).astype(dt)
        g = jnp.dot(x, w_gate_ref[0, 0],
                    preferred_element_type=jnp.float32).astype(dt)
        a = (jax.nn.silu(g.astype(jnp.float32))
             * h.astype(jnp.float32)).astype(dt)
        o_ref[0] = jnp.dot(a, w_out_ref[0, 0],
                           preferred_element_type=jnp.float32).astype(dt)

    @pl.when(jnp.logical_not(held_here))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


def chosen_expert_matmul(x, w_in, w_gate, w_out, layer, expert, *,
                         interpret=None):
    """``silu(x W_gate) * (x W_in) @ W_out`` for each choice, from its
    expert's weights in the stacked run.

    x: (C, D), one row a choice; w_in, w_gate: (L, E, D, F) and w_out:
    (L, E, F, D), a run's stacked weights of its E held experts; layer:
    int32 scalar, the layer of the run; expert: (C,) int32 local expert ids
    in ascending order, E for a choice of an expert not held (so those come
    last).  Returns (C, D) in x's dtype, zero rows for choices not held."""
    C, D = x.shape
    _, E, _, F = w_in.shape
    layer = jnp.reshape(layer, (1,)).astype(jnp.int32)
    expert = expert.astype(jnp.int32)
    # a choice not held reads the last held choice's blocks again: no DMA
    last = jnp.max(jnp.where(expert < E, expert, 0))
    block = jnp.minimum(expert, last)

    def wmap(c, layer, expert, block):
        return layer[0], block[c], 0, 0

    def rowmap(c, layer, expert, block):
        return c, 0, 0

    w_bytes = 3 * D * F * w_in.dtype.itemsize
    out = pl.pallas_call(
        functools.partial(_kernel, held=E),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(C,),
            in_specs=[
                pl.BlockSpec((1, 1, D), rowmap),
                pl.BlockSpec((1, 1, D, F), wmap),
                pl.BlockSpec((1, 1, D, F), wmap),
                pl.BlockSpec((1, 1, F, D), wmap),
            ],
            out_specs=pl.BlockSpec((1, 1, D), rowmap),
        ),
        out_shape=jax.ShapeDtypeStruct((C, 1, D), x.dtype),
        # the three weight blocks, double-buffered, do not fit the default
        # scoped VMEM at real widths (34.6 MB at 2048 x 1408 in bfloat16)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=2 * w_bytes + (8 << 20)),
        interpret=resolve_interpret(interpret),
        name="chosen_expert_matmul",
    )(layer, expert, block, x.reshape(C, 1, D), w_in, w_gate, w_out)
    return out.reshape(C, D)
