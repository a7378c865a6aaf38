"""Pallas TPU flash-attention kernels (forward AND backward).

TPU-native adaptation of the flash algorithm: BlockSpec-tiled VMEM staging,
MXU-aligned (multiple-of-128) q/k blocks, grid (batch*kv_heads, q_blocks,
kv_blocks) with the innermost dimension "arbitrary" so accumulators live in
VMEM scratch across its steps.

GQA layout (shared by forward and backward): q is (B*Hkv, G*bq, hd) blocks
against k/v (B*Hkv, bk, hd) — the query-group dim rides inside the q block
so one k/v VMEM stage serves all G query heads of its group (cuts k/v HBM
traffic by G).  Per-row statistics (lse, delta) travel as one (1, G*bq) row
per (b, q block): Mosaic cannot split a 1-D lane vector into a (G, bq) tile,
so the kernels never reshape them beyond that row.

Backward = blockwise recompute (no S x S buffer):
  delta_i = rowsum(do_i * o_i)                       (precomputed, tiny)
  p_ij    = exp(s_ij - lse_i)     where s = qk^T * scale, masked
  dv_j   += p^T do ;  ds = p * (dp - delta) * scale  with dp = do v^T
  dq_i   += ds k   ;  dk_j += ds^T q
split over two kernels so each accumulator matches its grid order: dq
iterates kv innermost (grid b, i, j), dk/dv iterate q innermost (grid
b, j, i).  ``flash_attention`` wires both into a jax.custom_vjp, which
``models.layers.chunked_attention`` dispatches to on TPU — the jnp
custom-VJP there remains the CPU lowering and the numerical oracle.

Validated on CPU via interpret mode against ``ref.mha_reference`` and the
jnp VJP (see tests/test_kernels.py); ``interpret=None`` auto-detects the
backend (``repro.kernels.backend``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
# rows (G*block_q) of one q block: the (rows, block_k) f32 score and
# probability tiles must fit v5e's 16 MiB scoped VMEM — 4096 rows overflow
MAX_BLOCK_ROWS = 2048
NEG_INF = -1e30


def _block_mask(s_shape, qi, ki, *, causal, window, block_q, block_k,
                q_offset):
    """Boolean keep-mask for one (q block, k block) tile of scores.

    Row r of the flattened (G*bq, bk) tile is query ``qi*bq + r % bq`` of
    group ``r // bq``; ``q_offset`` shifts query positions (decode /
    continuation chunks)."""
    r = jax.lax.broadcasted_iota(jnp.int32, s_shape, 0)
    c = jax.lax.broadcasted_iota(jnp.int32, s_shape, 1)
    q_pos = q_offset + qi * block_q + jax.lax.rem(r, block_q)
    k_pos = ki * block_k + c
    mask = jnp.ones(s_shape, dtype=jnp.bool_)
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= q_pos - k_pos < window
    return mask


# ---------------------------------------------------------------------- #
# Forward
# ---------------------------------------------------------------------- #

def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
                  *, scale: float, causal: bool, window: int | None,
                  block_q: int, block_k: int, n_k: int, q_offset: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    G_, bq, hd = q_ref.shape[1:]
    q = q_ref[0].astype(jnp.float32).reshape(G_ * bq, hd)   # (G*bq, hd)
    k = k_ref[0].astype(jnp.float32)                        # (bk, hd)
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    mask = _block_mask(s.shape, qi, ki, causal=causal, window=window,
                       block_q=block_q, block_k=block_k, q_offset=q_offset)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == n_k - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o = acc_ref[...] / l[:, None]
        o_ref[0] = o.reshape(G_, bq, hd).astype(o_ref.dtype)
        lse_ref[0] = (m_ref[...] + jnp.log(l)).reshape(1, G_ * bq)


def _fold_gqa(q, k, v):
    """(B,S,H,hd) tensors -> grouped (B*Hkv, G, Sq, hd) / (B*Hkv, Sk, hd)."""
    B, Sq, H, hd = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    qg = (q.reshape(B, Sq, Hkv, G, hd).transpose(0, 2, 3, 1, 4)
          .reshape(B * Hkv, G, Sq, hd))
    kg = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk, hd)
    vg = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk, hd)
    return qg, kg, vg


def _rows_to_blocks(x, n_q, block_q):
    """(N, G, Sq) per-row stats -> (N*n_q, 1, G*block_q): one kernel row per
    (n, q block), holding all G groups of that block."""
    N, G, _ = x.shape
    return (x.reshape(N, G, n_q, block_q).transpose(0, 2, 1, 3)
            .reshape(N * n_q, 1, G * block_q))


def _blocks_to_rows(x, N, G, n_q, block_q):
    """Inverse of :func:`_rows_to_blocks`."""
    return (x.reshape(N, n_q, G, block_q).transpose(0, 2, 1, 3)
            .reshape(N, G, n_q * block_q))


def _check_blocks(Sq, Sk, block_q, block_k, G):
    block_q, block_k = min(block_q, Sq), min(block_k, Sk)
    # halving keeps a divisor of Sq a divisor
    while G * block_q > MAX_BLOCK_ROWS and block_q % 256 == 0:
        block_q //= 2
    if Sq % block_q != 0 or Sk % block_k != 0:
        raise ValueError(f"flash attention blocks must tile the "
                         f"sequence: Sq={Sq} Sk={Sk} "
                         f"block_q={block_q} block_k={block_k}")
    return block_q, block_k


def flash_attention_fwd(
    q: jax.Array,          # (B, Sq, H, hd)
    k: jax.Array,          # (B, Sk, Hkv, hd)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    q_offset: int = 0,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Returns (o: (B,Sq,H,hd), lse: (B,Hkv,G,Sq) f32) — the lse layout of
    ``models.layers._flash_fwd_impl``, consumed by the backward kernels."""
    B, Sq, H, hd = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    block_q, block_k = _check_blocks(Sq, Sk, block_q, block_k, G)
    n_q, n_k = Sq // block_q, Sk // block_k
    scale = 1.0 / math.sqrt(hd)
    qg, kg, vg = _fold_gqa(q, k, v)

    grid = (B * Hkv, n_q, n_k)
    out, lse = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          window=window, block_q=block_q, block_k=block_k,
                          n_k=n_k, q_offset=q_offset),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, G, block_q, hd), lambda b, i, j: (b, 0, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, G, block_q, hd), lambda b, i, j: (b, 0, i, 0)),
            pl.BlockSpec((1, 1, G * block_q),
                         lambda b, i, j: (b * n_q + i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * Hkv, G, Sq, hd), q.dtype),
            jax.ShapeDtypeStruct((B * Hkv * n_q, 1, G * block_q),
                                 jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((G * block_q,), jnp.float32),   # running max m
            pltpu.VMEM((G * block_q,), jnp.float32),   # running sum l
            pltpu.VMEM((G * block_q, hd), jnp.float32),  # accumulator
        ],
        interpret=resolve_interpret(interpret),
    )(qg, kg, vg)
    # (B*Hkv, G, Sq, hd) -> (B, Sq, H, hd)
    out = out.reshape(B, Hkv, G, Sq, hd).transpose(0, 3, 1, 2, 4)
    lse = _blocks_to_rows(lse, B * Hkv, G, n_q, block_q)
    return out.reshape(B, Sq, H, hd), lse.reshape(B, Hkv, G, Sq)


# ---------------------------------------------------------------------- #
# Backward
# ---------------------------------------------------------------------- #

def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                         dq_ref, acc_ref, *, scale: float, causal: bool,
                         window: int | None, block_q: int, block_k: int,
                         n_k: int, q_offset: int):
    """dq: grid (B*Hkv, n_q, n_k) — kv innermost, dq accumulator in VMEM."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    G_, bq, hd = q_ref.shape[1:]
    q = q_ref[0].astype(jnp.float32).reshape(G_ * bq, hd)
    do = do_ref[0].astype(jnp.float32).reshape(G_ * bq, hd)
    lse = lse_ref[0].reshape(G_ * bq)
    delta = dl_ref[0].reshape(G_ * bq)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    mask = _block_mask(s.shape, qi, ki, causal=causal, window=window,
                       block_q=block_q, block_k=block_k, q_offset=q_offset)
    # explicit mask (not NEG_INF arithmetic): a fully-masked row has
    # lse ~ NEG_INF and exp(s - lse) would blow up to 1, not 0
    p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta[:, None]) * scale
    acc_ref[...] = acc_ref[...] + jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _finalize():
        dq_ref[0] = acc_ref[...].reshape(G_, bq, hd)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc, *, scale: float,
                          causal: bool, window: int | None, block_q: int,
                          block_k: int, n_q: int, q_offset: int):
    """dk/dv: grid (B*Hkv, n_k, n_q) — q innermost, dk/dv scratch in VMEM."""
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    G_, bq, hd = q_ref.shape[1:]
    q = q_ref[0].astype(jnp.float32).reshape(G_ * bq, hd)
    do = do_ref[0].astype(jnp.float32).reshape(G_ * bq, hd)
    lse = lse_ref[0].reshape(G_ * bq)
    delta = dl_ref[0].reshape(G_ * bq)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    mask = _block_mask(s.shape, qi, ki, causal=causal, window=window,
                       block_q=block_q, block_k=block_k, q_offset=q_offset)
    p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)
    # dv += p^T do  — contract the G*bq query dim
    dv_acc[...] = dv_acc[...] + jax.lax.dot_general(
        p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta[:, None]) * scale
    dk_acc[...] = dk_acc[...] + jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(qi == n_q - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...]
        dv_ref[0] = dv_acc[...]


def flash_attention_bwd(
    q: jax.Array, k: jax.Array, v: jax.Array,
    o: jax.Array, lse: jax.Array, do: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    q_offset: int = 0,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Blockwise-recompute backward.  ``lse``: (B,Hkv,G,Sq) f32 from
    :func:`flash_attention_fwd`.  Returns (dq, dk, dv) in the input dtypes."""
    B, Sq, H, hd = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    block_q, block_k = _check_blocks(Sq, Sk, block_q, block_k, G)
    n_q, n_k = Sq // block_q, Sk // block_k
    scale = 1.0 / math.sqrt(hd)
    interpret = resolve_interpret(interpret)

    qg, kg, vg = _fold_gqa(q, k, v)
    dog, _, _ = _fold_gqa(do, k, v)
    og, _, _ = _fold_gqa(o, k, v)
    lseg = _rows_to_blocks(lse.reshape(B * Hkv, G, Sq), n_q, block_q)
    # delta_i = rowsum(do_i * o_i): O(S*hd), cheap enough to precompute
    delta = _rows_to_blocks(
        jnp.einsum("bgsd,bgsd->bgs", dog.astype(jnp.float32),
                   og.astype(jnp.float32)), n_q, block_q)

    kw = dict(scale=scale, causal=causal, window=window, block_q=block_q,
              block_k=block_k, q_offset=q_offset)
    q_spec = pl.BlockSpec((1, G, block_q, hd), lambda b, i, j: (b, 0, i, 0))
    kv_spec = pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0))
    row_spec = pl.BlockSpec((1, 1, G * block_q),
                            lambda b, i, j: (b * n_q + i, 0, 0))

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, n_k=n_k, **kw),
        grid=(B * Hkv, n_q, n_k),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=pl.BlockSpec((1, G, block_q, hd),
                               lambda b, i, j: (b, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hkv, G, Sq, hd), jnp.float32),
        scratch_shapes=[pltpu.VMEM((G * block_q, hd), jnp.float32)],
        interpret=interpret,
    )(qg, kg, vg, dog, lseg, delta)

    # dkv grid swaps the loop order: index maps see (b, j, i)
    q_spec_t = pl.BlockSpec((1, G, block_q, hd), lambda b, j, i: (b, 0, i, 0))
    kv_spec_t = pl.BlockSpec((1, block_k, hd), lambda b, j, i: (b, j, 0))
    row_spec_t = pl.BlockSpec((1, 1, G * block_q),
                              lambda b, j, i: (b * n_q + i, 0, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, n_q=n_q, **kw),
        grid=(B * Hkv, n_k, n_q),
        in_specs=[q_spec_t, kv_spec_t, kv_spec_t, q_spec_t, row_spec_t,
                  row_spec_t],
        out_specs=[pl.BlockSpec((1, block_k, hd), lambda b, j, i: (b, j, 0)),
                   pl.BlockSpec((1, block_k, hd), lambda b, j, i: (b, j, 0))],
        out_shape=[jax.ShapeDtypeStruct((B * Hkv, Sk, hd), jnp.float32),
                   jax.ShapeDtypeStruct((B * Hkv, Sk, hd), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_k, hd), jnp.float32),
                        pltpu.VMEM((block_k, hd), jnp.float32)],
        interpret=interpret,
    )(qg, kg, vg, dog, lseg, delta)

    dq = (dq.reshape(B, Hkv, G, Sq, hd).transpose(0, 3, 1, 2, 4)
          .reshape(B, Sq, H, hd).astype(q.dtype))
    dk = (dk.reshape(B, Hkv, Sk, hd).transpose(0, 2, 1, 3)
          .astype(k.dtype))
    dv = (dv.reshape(B, Hkv, Sk, hd).transpose(0, 2, 1, 3)
          .astype(v.dtype))
    return dq, dk, dv


# ---------------------------------------------------------------------- #
# Differentiable entry point
# ---------------------------------------------------------------------- #

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention(q, k, v, causal, window, block_q, block_k, q_offset,
                    interpret):
    """Differentiable flash attention: Pallas forward AND backward.
    Positional statics (custom_vjp nondiff args); use the keyword wrapper
    ``repro.kernels.ops.flash_attention`` from user code."""
    o, _ = flash_attention_fwd(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_k=block_k,
                               q_offset=q_offset, interpret=interpret)
    return o


def _fa_vjp_fwd(q, k, v, causal, window, block_q, block_k, q_offset,
                interpret):
    o, lse = flash_attention_fwd(q, k, v, causal=causal, window=window,
                                 block_q=block_q, block_k=block_k,
                                 q_offset=q_offset, interpret=interpret)
    return o, (q, k, v, o, lse)


def _fa_vjp_bwd(causal, window, block_q, block_k, q_offset, interpret,
                res, do):
    q, k, v, o, lse = res
    return flash_attention_bwd(q, k, v, o, lse, do, causal=causal,
                               window=window, block_q=block_q,
                               block_k=block_k, q_offset=q_offset,
                               interpret=interpret)


flash_attention.defvjp(_fa_vjp_fwd, _fa_vjp_bwd)
