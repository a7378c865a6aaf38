"""Pallas TPU kernel: RWKV-6 chunked WKV recurrence, fused over the sequence.

The linear-attention state update S_t = diag(w_t) S_{t-1} + k_t v_t^T with
per-step output o_t = r_t S_{t-1} + (r_t . (u*k_t)) v_t is the compute
hot-spot of the rwkv6-1.6b architecture.  The chunked form (intra-chunk
factored decays + inter-chunk state) is exactly `models.layers._wkv_chunk_
scan`.

ONE kernel invocation per (batch*head): the full (S, hd) sequence is staged
per grid step and a ``lax.fori_loop`` INSIDE the kernel walks the chunks
with the (hd, hd) state carried as the loop value — no per-chunk grid
relaunch, no state round-trip through HBM between chunks (the pre-fusion
version ran one grid step per chunk with the state parked in VMEM scratch
across steps; this version also removes the per-chunk block re-staging).

Validated against ``models.layers._wkv_chunk_scan`` in
tests/test_kernels.py; ``interpret=None`` auto-detects the backend.

Interpret mode only: the TPU compiler refuses this kernel (``jnp.cumsum``
has no Pallas TPU lowering), and no model calls it — rwkv6 runs
``_wkv_chunk_scan``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import resolve_interpret

CHUNK = 16


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, *,
                chunk: int, n_chunks: int):
    hd = r_ref.shape[-1]
    u = u_ref[0].astype(jnp.float32)                # (1, hd) bonus

    def chunk_step(ci, S):
        sl = pl.ds(ci * chunk, chunk)
        r = r_ref[0, sl, :].astype(jnp.float32)     # (C, hd)
        k = k_ref[0, sl, :].astype(jnp.float32)
        v = v_ref[0, sl, :].astype(jnp.float32)
        w = w_ref[0, sl, :].astype(jnp.float32)     # decays in (0,1)

        logw = jnp.log(jnp.maximum(w, 1e-8))
        e = jnp.exp(jnp.cumsum(logw, axis=0))       # e_t = prod_{j<=t} w_j
        e_excl = e / jnp.maximum(w, 1e-8)           # prod_{j<t}
        # inter-chunk: o_t += (r_t * e_excl_t) @ S_prev
        o = jax.lax.dot_general(r * e_excl, S, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        # intra-chunk: scores_{t,j} = (r_t*e_excl_t) . (k_j/e_j), j < t
        kk = k / jnp.maximum(e, 1e-30)
        sc = jax.lax.dot_general(r * e_excl, kk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
        sc = jnp.where(row > col, sc, 0.0)
        o = o + jax.lax.dot_general(sc, v, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
        # diagonal bonus
        bonus = jnp.sum(r * (u * k), axis=1, keepdims=True)
        o = o + bonus * v
        o_ref[0, sl, :] = o.astype(o_ref.dtype)
        # state to next chunk: S = diag(e_C) S + sum_j diag(e_C/e_j) k_j v_j^T
        eC = e[-1:]                                 # (1, hd)
        return eC.T * S + jax.lax.dot_general(
            kk * eC, v, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    jax.lax.fori_loop(0, n_chunks, chunk_step,
                      jnp.zeros((hd, hd), jnp.float32))


def wkv_chunked(r, k, v, w, u, *, chunk: int = CHUNK,
                interpret: bool | None = None):
    """r,k,v,w: (B,S,H,hd); u: (H,hd).  Returns o: (B,S,H,hd) f32.

    S must divide by ``chunk`` (callers pad, as models.layers does)."""
    B, S, H, hd = r.shape
    if S % chunk != 0:
        raise ValueError(f"wkv_chunked needs S % chunk == 0, got "
                         f"S={S} chunk={chunk}")
    n = S // chunk

    def fold(x):  # (B,S,H,hd) -> (B*H, S, hd)
        return x.transpose(0, 2, 1, 3).reshape(B * H, S, hd)

    rf, kf, vf, wf = fold(r), fold(k), fold(v), fold(w)
    uf = jnp.broadcast_to(u[None], (B, H, hd)).reshape(B * H, 1, hd)

    seq_spec = pl.BlockSpec((1, S, hd), lambda b: (b, 0, 0))
    out = pl.pallas_call(
        functools.partial(_wkv_kernel, chunk=chunk, n_chunks=n),
        grid=(B * H,),
        in_specs=[seq_spec, seq_spec, seq_spec, seq_spec,
                  pl.BlockSpec((1, 1, hd), lambda b: (b, 0, 0))],
        out_specs=pl.BlockSpec((1, S, hd), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, hd), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(rf, kf, vf, wf, uf)
    return out.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
