"""Pallas TPU kernels: blockwise symmetric int8 quantise / dequantise, and
the FUSED quantise + error-feedback residual update.

Used by the slow-link (DCN) gradient compressor — the perf-critical inner
loop of the paper-inspired topology-aware compression: gradients cross the
pod boundary as int8 + per-block f32 scales (~0.26x of f32 wire bytes).

``quantize_ef_int8`` computes ``q``, ``scales`` AND the new EF residual
``(x+ef) - dequant(q)`` in one VMEM pass: the two-pass formulation (add,
quantise, dequantise, subtract as separate HBM-resident ops) moves ~34
bytes/element where the fused kernel moves ~13 (see BENCH_kernels.json).

VMEM tiling: TILE quant blocks of QBLOCK elements each per grid step.  The
scales leave each step as one (1, TILE) lane row of a (steps, 1, TILE)
array: Mosaic refuses a 1-D (TILE,) block unless TILE matches the 1024-
element tiling of 1-D f32, and that would force callers to pad every
buffer to 256 Ki elements.  The constants live in
``repro.core.compression`` (single source of truth shared with the jnp
reference path); callers pad with ``compression.pad_to_block(x, QTILE)``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.compression import BLOCK as QBLOCK, TILE, QTILE
from repro.kernels.backend import resolve_interpret


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)            # (TILE, QBLOCK)
    amax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = jnp.maximum(amax / 127.0, 1e-12)      # (TILE, 1)
    q = jnp.clip(jnp.round(x / scale), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[0] = scale.reshape(1, TILE)


def _dequant_kernel(q_ref, s_ref, x_ref):
    q = q_ref[...].astype(jnp.float32)
    x_ref[...] = q * s_ref[0].reshape(TILE, 1)


def _quant_ef_kernel(x_ref, e_ref, q_ref, s_ref, r_ref):
    # one pass: corrected buffer, quantise, and the fresh rounding residual
    x = x_ref[...].astype(jnp.float32) + e_ref[...].astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = jnp.maximum(amax / 127.0, 1e-12)
    q = jnp.clip(jnp.round(x / scale), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[0] = scale.reshape(1, TILE)
    # q is already the exact f32 value of the int8 payload, so this residual
    # is bit-identical to the two-pass dequantise-and-subtract — PROVIDED the
    # product is rounded before the subtract.  Compilers contract x - q*scale
    # into an FMA (one rounding, ulp-off from the two-pass reference;
    # optimization_barrier does NOT stop the CPU emitter); the minimum with
    # F32_MAX is a value-identity the contraction cannot look through.
    deq = jnp.minimum(q * scale, jnp.float32(3.4028235e38))
    r_ref[...] = x - deq


# one (1, TILE) row of scales per grid step
_SCALE_SPEC = pl.BlockSpec((1, 1, TILE), lambda i: (i, 0, 0))


def _scale_shape(nblk: int) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct((nblk // TILE, 1, TILE), jnp.float32)


def _check_1d(x: jax.Array, name: str) -> None:
    if x.ndim != 1 or x.size % QTILE != 0:
        raise ValueError(f"{name} needs a 1-D buffer divisible by "
                         f"QTILE={QTILE} (see compression.pad_to_block), "
                         f"got shape {x.shape}")


def quantize_int8(x: jax.Array, *, interpret: bool | None = None):
    """x: 1-D f32, length divisible by QTILE (callers pad).
    Returns (q int8 [N], scales f32 [N/QBLOCK])."""
    _check_1d(x, "quantize_int8")
    nblk = x.size // QBLOCK
    xb = x.reshape(nblk, QBLOCK)
    grid = (nblk // TILE,)
    q, s = pl.pallas_call(
        _quant_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((TILE, QBLOCK), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((TILE, QBLOCK), lambda i: (i, 0)),
                   _SCALE_SPEC],
        out_shape=[jax.ShapeDtypeStruct((nblk, QBLOCK), jnp.int8),
                   _scale_shape(nblk)],
        interpret=resolve_interpret(interpret),
    )(xb)
    return q.reshape(-1), s.reshape(-1)


def dequantize_int8(q: jax.Array, scales: jax.Array, *,
                    interpret: bool | None = None) -> jax.Array:
    _check_1d(q, "dequantize_int8")
    nblk = q.size // QBLOCK
    qb = q.reshape(nblk, QBLOCK)
    grid = (nblk // TILE,)
    x = pl.pallas_call(
        _dequant_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((TILE, QBLOCK), lambda i: (i, 0)),
                  _SCALE_SPEC],
        out_specs=pl.BlockSpec((TILE, QBLOCK), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nblk, QBLOCK), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(qb, scales.reshape(nblk // TILE, 1, TILE))
    return x.reshape(-1)


def quantize_ef_int8(x: jax.Array, ef: jax.Array, *,
                     interpret: bool | None = None):
    """Fused EF quantiser: quantise ``x + ef`` and emit the new residual in
    the same VMEM pass.

    x, ef: 1-D f32 of equal length divisible by QTILE (callers pad).
    Returns (q int8 [N], scales f32 [N/QBLOCK], new_ef f32 [N]) with
    ``new_ef = (x+ef) - q*scale`` — bit-identical to the two-pass
    quantise/dequantise/subtract, minus two HBM round-trips.
    """
    _check_1d(x, "quantize_ef_int8")
    if ef.shape != x.shape:
        raise ValueError(f"quantize_ef_int8 needs matching shapes, got "
                         f"x={x.shape} ef={ef.shape}")
    nblk = x.size // QBLOCK
    grid = (nblk // TILE,)
    q, s, r = pl.pallas_call(
        _quant_ef_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((TILE, QBLOCK), lambda i: (i, 0)),
                  pl.BlockSpec((TILE, QBLOCK), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((TILE, QBLOCK), lambda i: (i, 0)),
                   _SCALE_SPEC,
                   pl.BlockSpec((TILE, QBLOCK), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((nblk, QBLOCK), jnp.int8),
                   _scale_shape(nblk),
                   jax.ShapeDtypeStruct((nblk, QBLOCK), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(x.reshape(nblk, QBLOCK), ef.reshape(nblk, QBLOCK))
    return q.reshape(-1), s.reshape(-1), r.reshape(-1)
