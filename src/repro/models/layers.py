"""Neural building blocks, pure JAX.

Every block has ``init_<block>(key, cfg) -> params`` and
``<block>_fwd(params, x, ...) -> y`` plus, where serving needs it, a
``<block>_decode`` single-token step against a cache/state.

Attention uses an online-softmax double-chunked formulation (flash-style) so
that the lowered HLO never materialises an S x S score matrix — this is what
keeps the 32k-prefill dry-run memory term sane; the Pallas kernel in
``repro.kernels.flash_attention`` is the TPU-target version of the same
algorithm and is validated against ``naive_attention`` below.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .config import ModelConfig

# ---------------------------------------------------------------------- #
# Small pieces
# ---------------------------------------------------------------------- #

def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    """RMSNorm with f32 statistics but NO f32 materialisation of x.

    The obvious ``x.astype(f32)`` implementation makes XLA hoist an f32 copy
    of the entire saved-activation stack out of the backward scan (observed:
    +11.8 GB/device on tinyllama train_4k).  Computing the sum-of-squares via
    a dot with f32 accumulation keeps every x-sized tensor in bf16."""
    ss = jnp.einsum("...d,...d->...", x, x,
                    preferred_element_type=jnp.float32)
    inv = jax.lax.rsqrt(ss / x.shape[-1] + eps)
    scale = inv[..., None].astype(x.dtype) * (1.0 + w).astype(x.dtype)
    return x * scale


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, hd); positions: (..., S) int32."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (...,S,half)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def rope_inv_freq(dim: int, theta: float, yarn=None) -> np.ndarray:
    """The ``dim // 2`` rotation frequencies of a rotary embedding; with
    ``yarn`` (a :class:`~repro.models.config.YarnCfg`) YaRN's blend of
    interpolated and extrapolated frequencies, as in DeepSeek-V2's
    ``DeepseekV2YarnRotaryEmbedding`` and ``transformers``'
    ``_compute_yarn_parameters``."""
    pos_freqs = theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    extra = 1.0 / pos_freqs
    if yarn is None:
        return extra.astype(np.float32)
    inter = 1.0 / (yarn.factor * pos_freqs)

    def correction_dim(rotations):
        return (dim * math.log(yarn.original_max_position
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(correction_dim(yarn.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0.0, 1.0)
    extra_share = 1.0 - ramp
    return (inter * (1.0 - extra_share) + extra * extra_share).astype(
        np.float32)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature factor: 0.1 m ln(factor) + 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_pairs(x: jax.Array, positions: jax.Array,
               inv_freq: np.ndarray) -> jax.Array:
    """Rotary embedding of interleaved pairs (x[2i], x[2i+1]) by angle
    ``position * inv_freq[i]``, as DeepSeek-V2 rotates (``view_as_complex``
    on (..., d/2, 2)).  x: (..., S, H, d); positions: (..., S)."""
    ang = positions[..., None].astype(jnp.float32) * jnp.asarray(inv_freq)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    xr = x.reshape(*x.shape[:-1], -1, 2)
    x1, x2 = xr[..., 0], xr[..., 1]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _uniform(key, shape, scale, dtype=jnp.float32):
    return jax.random.uniform(key, shape, dtype, -scale, scale)


def dense_init(key, d_in: int, d_out: int, dtype=jnp.bfloat16):
    return _uniform(key, (d_in, d_out), 1.0 / math.sqrt(d_in)).astype(dtype)


# ---------------------------------------------------------------------- #
# Attention
# ---------------------------------------------------------------------- #

def init_attention(key, cfg: ModelConfig, cross: bool = False) -> dict:
    ks = jax.random.split(key, 6)
    D, Q, KV, hd = cfg.d_model, cfg.q_dim, cfg.kv_dim, cfg.head_dim
    p = {
        "wq": dense_init(ks[0], D, Q),
        "wk": dense_init(ks[1], D, KV),
        "wv": dense_init(ks[2], D, KV),
        "wo": dense_init(ks[3], Q, D),
    }
    if cfg.qk_norm and not cross:
        p["q_norm"] = jnp.zeros((hd,), jnp.float32)
        p["k_norm"] = jnp.zeros((hd,), jnp.float32)
    return p


def naive_attention(q, k, v, *, causal: bool, window: int | None,
                    q_pos, k_pos) -> jax.Array:
    """Reference O(S^2)-memory attention.  q:(B,Sq,H,hd) k/v:(B,Sk,Hkv,hd)."""
    B, Sq, H, hd = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    qg = q.reshape(B, Sq, Hkv, G, hd)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(hd)
    mask = jnp.ones((Sq, k.shape[1]), bool)
    if causal:
        mask &= q_pos[:, None] >= k_pos[None, :]
    if window is not None:
        mask &= q_pos[:, None] - k_pos[None, :] < window
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)  # fully-masked rows
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return o.reshape(B, Sq, H, hd).astype(q.dtype)


def _block_mask(q_pos, k_pos, causal, window):
    mask = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        mask &= q_pos[:, None] >= k_pos[None, :]
    if window is not None:
        mask &= q_pos[:, None] - k_pos[None, :] < window
    return mask


def _flash_fwd_impl(q, k, v, causal, window, cq, ck, q_offset):
    """Online-softmax forward.  Returns (o, lse) with
    o: (B,Sq,H,hd); lse: (B,Hkv,G,Sq) f32."""
    B, Sq, H, hd = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    nq, nk = Sq // cq, Sk // ck
    qc = q.reshape(B, nq, cq, Hkv, G, hd).transpose(1, 0, 2, 3, 4, 5)
    kc = k.reshape(B, nk, ck, Hkv, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nk, ck, Hkv, hd).transpose(1, 0, 2, 3, 4)
    scale = 1.0 / math.sqrt(hd)

    def q_step(_, qi_i):
        qi, i = qi_i
        q_pos = q_offset + i * cq + jnp.arange(cq)

        def kv_step(carry, kj_vj_j):
            kj, vj, j = kj_vj_j

            def compute(carry):
                m, l, acc = carry
                k_pos = j * ck + jnp.arange(ck)
                s = jnp.einsum("bqhgd,bkhd->bhgqk", qi.astype(jnp.float32),
                               kj.astype(jnp.float32)) * scale
                s = jnp.where(_block_mask(q_pos, k_pos, causal, window),
                              s, -1e30)
                m_new = jnp.maximum(m, s.max(-1))
                alpha = jnp.exp(m - m_new)
                p = jnp.exp(s - m_new[..., None])
                l = l * alpha + p.sum(-1)
                acc = acc * alpha[..., None] + jnp.einsum(
                    "bhgqk,bkhd->bhgqd", p, vj.astype(jnp.float32))
                return m_new, l, acc

            # Block skipping: off-band blocks (above the causal diagonal /
            # outside the sliding window) are genuine HLO conditionals —
            # halves attention FLOPs at 4k causal, 1/32 at 32k window-1k.
            needed = jnp.bool_(True)
            if causal:
                needed &= j * ck <= i * cq + cq - 1 + q_offset
            if window is not None:
                needed &= (q_offset + i * cq) - (j * ck + ck - 1) < window
            return lax.cond(needed, compute, lambda c: c, carry), None

        m0 = jnp.full((B, Hkv, G, cq), -1e30, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, cq), jnp.float32)
        a0 = jnp.zeros((B, Hkv, G, cq, hd), jnp.float32)
        (m, l, acc), _ = lax.scan(kv_step, (m0, l0, a0),
                                  (kc, vc, jnp.arange(nk)))
        l = jnp.maximum(l, 1e-30)
        o = acc / l[..., None]
        lse = m + jnp.log(l)
        return None, (o.transpose(0, 3, 1, 2, 4), lse)

    _, (oc, lsec) = lax.scan(q_step, None, (qc, jnp.arange(nq)))
    o = oc.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, H, hd).astype(q.dtype)
    lse = lsec.transpose(1, 2, 3, 0, 4).reshape(B, Hkv, G, Sq)
    return o, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, window, cq, ck, q_offset):
    o, _ = _flash_fwd_impl(q, k, v, causal, window, cq, ck, q_offset)
    return o


def _flash_vjp_fwd(q, k, v, causal, window, cq, ck, q_offset):
    o, lse = _flash_fwd_impl(q, k, v, causal, window, cq, ck, q_offset)
    return o, (q, k, v, o, lse)


def _flash_vjp_bwd(causal, window, cq, ck, q_offset, res, do):
    """Flash backward: recompute scores blockwise; memory O(block^2), not
    O(S^2) — this is what keeps the train-shape remat footprint sane."""
    q, k, v, o, lse = res
    B, Sq, H, hd = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    nq, nk = Sq // cq, Sk // ck
    scale = 1.0 / math.sqrt(hd)
    f32 = jnp.float32
    qg = q.reshape(B, Sq, Hkv, G, hd)
    dog = do.reshape(B, Sq, Hkv, G, hd)
    og = o.reshape(B, Sq, Hkv, G, hd)
    # D_i = rowsum(do * o): (B,Hkv,G,Sq)
    Dd = jnp.einsum("bqhgd,bqhgd->bhgq", dog.astype(f32), og.astype(f32))
    qc = qg.reshape(B, nq, cq, Hkv, G, hd).transpose(1, 0, 3, 4, 2, 5)   # (nq,B,h,g,cq,hd)
    doc = dog.reshape(B, nq, cq, Hkv, G, hd).transpose(1, 0, 3, 4, 2, 5)
    lsec = lse.reshape(B, Hkv, G, nq, cq).transpose(3, 0, 1, 2, 4)       # (nq,B,h,g,cq)
    Dc = Dd.reshape(B, Hkv, G, nq, cq).transpose(3, 0, 1, 2, 4)
    kc = k.reshape(B, nk, ck, Hkv, hd).transpose(1, 0, 3, 2, 4)          # (nk,B,h,ck,hd)
    vc = v.reshape(B, nk, ck, Hkv, hd).transpose(1, 0, 3, 2, 4)

    def kv_step(dq, blk):
        kj, vj, j = blk
        k_pos = j * ck + jnp.arange(ck)

        def q_step(carry, qblk):
            qi, doi, lsei, Di, i = qblk

            def compute(carry):
                dkj, dvj = carry
                q_pos = q_offset + i * cq + jnp.arange(cq)
                s = jnp.einsum("bhgqd,bhkd->bhgqk", qi.astype(f32),
                               kj.astype(f32)) * scale
                s = jnp.where(_block_mask(q_pos, k_pos, causal, window),
                              s, -1e30)
                p = jnp.exp(s - lsei[..., None])             # (B,h,g,cq,ck)
                dvj = dvj + jnp.einsum("bhgqk,bhgqd->bhkd", p,
                                       doi.astype(f32))
                dp = jnp.einsum("bhgqd,bhkd->bhgqk", doi.astype(f32),
                                vj.astype(f32))
                ds = p * (dp - Di[..., None]) * scale
                dkj = dkj + jnp.einsum("bhgqk,bhgqd->bhkd", ds,
                                       qi.astype(f32))
                dqi = jnp.einsum("bhgqk,bhkd->bhgqd", ds, kj.astype(f32))
                return (dkj, dvj), dqi

            needed = jnp.bool_(True)
            if causal:
                needed &= j * ck <= i * cq + cq - 1 + q_offset
            if window is not None:
                needed &= (q_offset + i * cq) - (j * ck + ck - 1) < window
            zero_dq = jnp.zeros((B, Hkv, G, cq, hd), f32)
            return lax.cond(needed, compute,
                            lambda c: (c, zero_dq), carry)

        z = jnp.zeros((B, Hkv, ck, hd), f32)
        (dkj, dvj), dqc = lax.scan(
            q_step, (z, z), (qc, doc, lsec, Dc, jnp.arange(nq)))
        return dq + dqc, (dkj, dvj)

    dq0 = jnp.zeros((nq, B, Hkv, G, cq, hd), f32)
    dq, (dk, dv) = lax.scan(kv_step, dq0,
                            (kc, vc, jnp.arange(nk)))
    dq = dq.transpose(1, 0, 4, 2, 3, 5).reshape(B, Sq, H, hd)
    dk = dk.transpose(1, 0, 3, 2, 4).reshape(B, Sk, Hkv, hd)
    dv = dv.transpose(1, 0, 3, 2, 4).reshape(B, Sk, Hkv, hd)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _pallas_flash(q, k, v, causal, window, chunk_q, chunk_k, q_offset):
    """The Pallas flash kernel, inside a shard_map when the caller left any
    mesh axis ``Auto``: Mosaic kernels cannot be partitioned by GSPMD.  The
    shard_map names every axis, the already-manual ones too — a nested
    shard_map that names only the auto axes still lowers as partially
    automatic.  Heads split over ``model`` and batch over auto dp axes where
    they divide; anything else is replicated.

    Forward and backward kernels each run in their own shard_map under one
    custom VJP, so autodiff never transposes the shard_map: with
    ``check_vma=False`` that transpose psums the input cotangents over every
    axis the specs leave out, already-manual dp axes included, which would
    mix the gradients of different batch shards."""
    from repro.kernels import flash_attention as fa

    am = jax.sharding.get_abstract_mesh()
    auto = {n for n, t in zip(am.axis_names, am.axis_types)
            if t != jax.sharding.AxisType.Manual}
    if not auto:
        return fa.flash_attention(q, k, v, causal, window, chunk_q, chunk_k,
                                  q_offset, None)
    B, H, Hkv = q.shape[0], q.shape[2], k.shape[2]
    dp = tuple(a for a in ("pod", "data") if a in auto)
    dp_size = math.prod(am.shape[a] for a in dp)
    b_ax = dp if dp and B % dp_size == 0 else None
    h_ax = ("model" if "model" in auto and H % am.shape["model"] == 0
            and Hkv % am.shape["model"] == 0 else None)
    spec = jax.sharding.PartitionSpec(b_ax, None, h_ax, None)
    lse_spec = jax.sharding.PartitionSpec(b_ax, h_ax, None, None)
    kw = dict(causal=causal, window=window, block_q=chunk_q, block_k=chunk_k,
              q_offset=q_offset)

    def smap(f, in_specs, out_specs):
        return jax.shard_map(f, in_specs=in_specs, out_specs=out_specs,
                             axis_names=set(am.axis_names), check_vma=False)

    fwd = smap(functools.partial(fa.flash_attention_fwd, **kw), (spec,) * 3,
               (spec, lse_spec))
    bwd = smap(functools.partial(fa.flash_attention_bwd, **kw),
               (spec,) * 4 + (lse_spec, spec), (spec,) * 3)

    @jax.custom_vjp
    def attend(q, k, v):
        return fwd(q, k, v)[0]

    def attend_fwd(q, k, v):
        o, lse = fwd(q, k, v)
        return o, (q, k, v, o, lse)

    attend.defvjp(attend_fwd, lambda res, do: bwd(*res, do))
    return attend(q, k, v)


def chunked_attention(q, k, v, *, causal: bool = True, window: int | None = None,
                      chunk_q: int = 512, chunk_k: int = 512,
                      q_offset: int = 0, impl: str | None = None) -> jax.Array:
    """Flash attention (online softmax fwd, blockwise-recompute custom VJP),
    GQA-aware, never materialising an S x S buffer in fwd OR bwd.  Falls back
    to the naive oracle for ragged (test-sized) shapes.

    ``impl``: None -> auto ("pallas" on TPU, "jnp" elsewhere).  "pallas"
    dispatches to ``repro.kernels.flash_attention`` — fwd AND bwd are Pallas
    kernels behind a ``jax.custom_vjp``, so training steps no longer fall
    back to this module's jnp VJP on TPU.  "jnp" keeps the pure-jnp lowering
    below, which doubles as the kernels' oracle and the CPU dry-run path."""
    Sq, Sk = q.shape[1], k.shape[1]
    if Sq % chunk_q or Sk % chunk_k:
        q_pos = q_offset + jnp.arange(Sq)
        return naive_attention(q, k, v, causal=causal, window=window,
                               q_pos=q_pos, k_pos=jnp.arange(Sk))
    if impl is None:
        from repro.kernels.backend import on_tpu  # lazy: models stay light
        impl = "pallas" if on_tpu() else "jnp"
    if impl == "pallas":
        return _pallas_flash(q, k, v, causal, window, chunk_q, chunk_k,
                             q_offset)
    if impl != "jnp":
        raise ValueError(f"chunked_attention impl must be None, 'pallas' or "
                         f"'jnp', got {impl!r}")
    return _flash(q, k, v, causal, window, chunk_q, chunk_k, q_offset)


def attention_fwd(p, cfg: ModelConfig, x, *, causal=True, window=None,
                  kv_src=None, positions=None) -> jax.Array:
    """Full attention sublayer (projections + rope + attention + out proj).

    kv_src: source sequence for cross-attention (keys/values from encoder).
    """
    B, S, D = x.shape
    hd = cfg.head_dim
    src = x if kv_src is None else kv_src
    q = (x @ p["wq"]).reshape(B, S, cfg.n_heads, hd)
    k = (src @ p["wk"]).reshape(B, src.shape[1], cfg.n_kv_heads, hd)
    v = (src @ p["wv"]).reshape(B, src.shape[1], cfg.n_kv_heads, hd)
    if "q_norm" in p:
        q, k = rmsnorm(q, p["q_norm"]), rmsnorm(k, p["k_norm"])
    if positions is None:
        positions = jnp.arange(S)
    if kv_src is None:  # rope only for self-attention
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, jnp.arange(src.shape[1]), cfg.rope_theta)
    o = chunked_attention(q, k, v, causal=causal and kv_src is None,
                          window=window)
    return o.reshape(B, S, cfg.q_dim) @ p["wo"]


# -------------------------- decode (KV cache) ------------------------- #

@dataclasses.dataclass
class KVCache:
    """Per-run stacked cache.  k/v: (run, B, S_cache, Hkv, hd).  For sliding
    window layers S_cache == window and writes wrap modulo the window."""
    k: jax.Array
    v: jax.Array
    windowed: bool

    @staticmethod
    def init(run_len, B, s_max, cfg: ModelConfig, windowed: bool):
        s_cache = min(cfg.window, s_max) if windowed else s_max
        shape = (run_len, B, s_cache, cfg.n_kv_heads, cfg.head_dim)
        z = jnp.zeros(shape, jnp.bfloat16)
        return KVCache(z, z, windowed)


jax.tree_util.register_dataclass(
    KVCache, data_fields=["k", "v"], meta_fields=["windowed"])


def _cache_attend_sp(q, k_new, v_new, cache_k, cache_v, pos, windowed,
                     axis="model"):
    """Flash-decode partial attention INSIDE shard_map manual over ``axis``.

    The cache sequence dim is sharded over the model axis; each rank scores
    its slice, then a pmax/psum log-sum-exp combine merges the partials —
    three tiny (B,H)-sized collectives instead of GSPMD re-gathering the
    cache/score tensors every layer (measured 37.5 GB/chip/step on qwen3
    decode_32k multi-pod with the naive lowering).

    q: (B,Hkv,G,hd) replicated over model; k/v_new: (B,1,Hkv,hd);
    cache_k/v: (B,S_loc,Hkv,hd) = this rank's sequence slice."""
    nsh = int(lax.psum(1, axis))
    r = lax.axis_index(axis)
    B, S_loc, Hkv, hd = cache_k.shape
    S_tot = S_loc * nsh
    slot_g = jnp.where(windowed, pos % S_tot, jnp.minimum(pos, S_tot - 1))
    local = slot_g - r * S_loc
    in_range = (local >= 0) & (local < S_loc)
    lc = jnp.clip(local, 0, S_loc - 1)
    ck = jnp.where(in_range,
                   lax.dynamic_update_slice(
                       cache_k, k_new.astype(cache_k.dtype), (0, lc, 0, 0)),
                   cache_k)
    cv = jnp.where(in_range,
                   lax.dynamic_update_slice(
                       cache_v, v_new.astype(cache_v.dtype), (0, lc, 0, 0)),
                   cache_v)
    idx = r * S_loc + jnp.arange(S_loc)        # absolute cache indices
    if windowed:
        abs_pos = pos - ((pos - idx) % S_tot)
        valid = (abs_pos >= 0) & (abs_pos >= pos - S_tot + 1) & (abs_pos <= pos)
    else:
        valid = idx <= pos
    sc = jnp.einsum("bhgd,bkhd->bhgk", q.astype(jnp.float32),
                    ck.astype(jnp.float32)) / math.sqrt(hd)
    sc = jnp.where(valid[None, None, None, :], sc, -1e30)
    m = lax.pmax(sc.max(-1), axis)             # (B,Hkv,G)
    pr = jnp.exp(sc - m[..., None])
    l = lax.psum(pr.sum(-1), axis)
    o = lax.psum(jnp.einsum("bhgk,bkhd->bhgd", pr, cv.astype(jnp.float32)),
                 axis)
    return o / jnp.maximum(l, 1e-30)[..., None], ck, cv


def _sp_decode_ctx(s_cache: int, batch: int):
    """(use_sp, auto_dp) when a model axis exists and divides the cache."""
    am = jax.sharding.get_abstract_mesh()
    if "model" not in am.axis_names:
        return False, ()
    msize = am.shape["model"]
    if msize <= 1 or s_cache % msize:
        return False, ()
    auto_dp = tuple(n for n, t in zip(am.axis_names, am.axis_types)
                    if n in ("pod", "data") and "Auto" in str(t))
    dp_deg = 1
    for a in auto_dp:
        dp_deg *= am.shape[a]
    if batch % dp_deg:
        auto_dp = ()
    return True, auto_dp


def attention_decode(p, cfg: ModelConfig, x, cache_k, cache_v, pos, *,
                     windowed: bool):
    """One-token decode.  x: (B,1,D); cache_k/v: (B,S_cache,Hkv,hd);
    pos: scalar int32 — number of tokens already in the cache.

    With a model axis present, the cache attention runs as an explicit
    flash-decode shard_map (sequence-sharded cache + LSE combine)."""
    import jax.sharding as jsh

    B = x.shape[0]
    hd = cfg.head_dim
    q = (x @ p["wq"]).reshape(B, 1, cfg.n_heads, hd)
    k = (x @ p["wk"]).reshape(B, 1, cfg.n_kv_heads, hd)
    v = (x @ p["wv"]).reshape(B, 1, cfg.n_kv_heads, hd)
    if "q_norm" in p:
        q, k = rmsnorm(q, p["q_norm"]), rmsnorm(k, p["k_norm"])
    q = rope(q, pos[None], cfg.rope_theta)
    k = rope(k, pos[None], cfg.rope_theta)

    use_sp, auto_dp = _sp_decode_ctx(cache_k.shape[1], B)
    if use_sp:
        G = cfg.n_heads // cfg.n_kv_heads
        qg = q.reshape(B, cfg.n_kv_heads, G, hd)
        P = jsh.PartitionSpec
        bdp = auto_dp if auto_dp else None
        rep4 = P(bdp, None, None, None)
        cache_spec = P(bdp, "model", None, None)
        sp = jax.shard_map(
            lambda qq, kn, vn, ckk, cvv, pp: _cache_attend_sp(
                qq, kn, vn, ckk, cvv, pp, windowed),
            in_specs=(rep4, rep4, rep4, cache_spec, cache_spec, P()),
            out_specs=(rep4, cache_spec, cache_spec),
            axis_names={"model", *(auto_dp or ())}, check_vma=False)
        o, cache_k, cache_v = sp(qg, k, v, cache_k, cache_v, pos)
        o = o.reshape(B, 1, cfg.q_dim).astype(x.dtype)
        return o @ p["wo"], cache_k, cache_v

    s_cache = cache_k.shape[1]
    slot = jnp.where(windowed, pos % s_cache, jnp.minimum(pos, s_cache - 1))
    cache_k = lax.dynamic_update_slice(cache_k, k.astype(cache_k.dtype),
                                       (0, slot, 0, 0))
    cache_v = lax.dynamic_update_slice(cache_v, v.astype(cache_v.dtype),
                                       (0, slot, 0, 0))
    # positions of cache entries for masking
    idx = jnp.arange(s_cache)
    if windowed:
        # entry i holds absolute position: the latest p' <= pos with p'%W == i
        abs_pos = pos - ((pos - idx) % s_cache)
        valid = (abs_pos >= 0) & (abs_pos >= pos - s_cache + 1) & (abs_pos <= pos)
    else:
        abs_pos = idx
        valid = idx <= pos
    G = cfg.n_heads // cfg.n_kv_heads
    qg = q.reshape(B, cfg.n_kv_heads, G, hd)
    sc = jnp.einsum("bhgd,bkhd->bhgk", qg.astype(jnp.float32),
                    cache_k.astype(jnp.float32)) / math.sqrt(hd)
    sc = jnp.where(valid[None, None, None, :], sc, -1e30)
    pr = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", pr, cache_v.astype(jnp.float32))
    o = o.reshape(B, 1, cfg.q_dim).astype(x.dtype)
    return o @ p["wo"], cache_k, cache_v


def paged_attention_decode(p, cfg: ModelConfig, x, pool_k, pool_v, layer,
                           block_tables, pos, *, window: int | None = None):
    """One-token decode against a paged KV pool (vLLM-style block table).

    x: (B,1,D); pool_k/v: (n_layers, n_blocks, block_size, Hkv, hd) — a
    run's stacked pools, one shared physical pool per layer; layer: int32
    scalar, the layer this call reads and writes; block_tables: (B,
    max_blocks) int32 mapping each slot's logical block i to a physical
    block (0 = the reserved null block, never owned by a live request, so
    idle slots write there harmlessly);
    pos: (B,) int32 per-slot token count — unlike the dense path the write
    pointer is per request, which is what lets continuous batching mix
    requests at different depths in one step.

    The new rows are scattered at ``(layer, block, offset)`` and the keys
    and values gathered at ``(layer, block_tables)`` straight from the
    stacked pools, so no op holds a whole layer's pool: with the pools
    donated, a step writes one row per layer in place.

    The gather `pool[table]` reconstructs each slot's cache in logical token
    order, so with max_blocks*block_size == s_max the score/softmax math is
    term-for-term identical to :func:`attention_decode`'s dense full-
    attention path — bit-identical logits (asserted in tests).  Windowed
    layers store the full sequence and mask `pos - idx >= window` instead of
    wrapping; numerics match the wrapped dense path exactly when no wrap has
    occurred (window >= s_max) and to float tolerance otherwise (the softmax
    sums the same terms in a different order).
    """
    B = x.shape[0]
    hd = cfg.head_dim
    q = (x @ p["wq"]).reshape(B, 1, cfg.n_heads, hd)
    k = (x @ p["wk"]).reshape(B, 1, cfg.n_kv_heads, hd)
    v = (x @ p["wv"]).reshape(B, 1, cfg.n_kv_heads, hd)
    if "q_norm" in p:
        q, k = rmsnorm(q, p["q_norm"]), rmsnorm(k, p["k_norm"])
    q = rope(q, pos[:, None], cfg.rope_theta)
    k = rope(k, pos[:, None], cfg.rope_theta)

    BS = pool_k.shape[2]
    bidx = block_tables[jnp.arange(B), pos // BS]       # (B,) physical block
    off = pos % BS
    pool_k = pool_k.at[layer, bidx, off].set(k[:, 0].astype(pool_k.dtype))
    pool_v = pool_v.at[layer, bidx, off].set(v[:, 0].astype(pool_v.dtype))

    MB = block_tables.shape[1]
    S = MB * BS
    gk = pool_k[layer, block_tables].reshape(B, S, *pool_k.shape[3:])
    gv = pool_v[layer, block_tables].reshape(B, S, *pool_v.shape[3:])
    idx = jnp.arange(S)
    valid = idx[None, :] <= pos[:, None]
    if window is not None:
        valid &= pos[:, None] - idx[None, :] < window
    G = cfg.n_heads // cfg.n_kv_heads
    qg = q.reshape(B, cfg.n_kv_heads, G, hd)
    sc = jnp.einsum("bhgd,bkhd->bhgk", qg.astype(jnp.float32),
                    gk.astype(jnp.float32)) / math.sqrt(hd)
    sc = jnp.where(valid[:, None, None, :], sc, -1e30)
    pr = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", pr, gv.astype(jnp.float32))
    o = o.reshape(B, 1, cfg.q_dim).astype(x.dtype)
    return o @ p["wo"], pool_k, pool_v


def attention_prefill(p, cfg: ModelConfig, x, *, window=None,
                      keep_full: bool = False):
    """Like attention_fwd (self, causal) but also returns the KV cache slice.

    For windowed layers the cache keeps the last ``window`` keys; prefill
    length must be a multiple of the window so modular slots line up with
    ``attention_decode``'s write pointer.  ``keep_full`` returns the whole
    sequence instead (the paged pool stores windowed layers unwrapped and
    masks at read time), which also lifts the S %% window constraint.
    """
    B, S, D = x.shape
    hd = cfg.head_dim
    q = (x @ p["wq"]).reshape(B, S, cfg.n_heads, hd)
    k = (x @ p["wk"]).reshape(B, S, cfg.n_kv_heads, hd)
    v = (x @ p["wv"]).reshape(B, S, cfg.n_kv_heads, hd)
    if "q_norm" in p:
        q, k = rmsnorm(q, p["q_norm"]), rmsnorm(k, p["k_norm"])
    pos = jnp.arange(S)
    q = rope(q, pos, cfg.rope_theta)
    k = rope(k, pos, cfg.rope_theta)
    o = chunked_attention(q, k, v, causal=True, window=window)
    y = o.reshape(B, S, cfg.q_dim) @ p["wo"]
    if window is not None and S >= window and not keep_full:
        if S % window != 0:
            raise ValueError(
                f"windowed prefill needs S % window == 0, got "
                f"S={S} window={window}")
        ck, cv = k[:, S - window:], v[:, S - window:]
    else:
        ck, cv = k, v
    return y, ck.astype(jnp.bfloat16), cv.astype(jnp.bfloat16)


def cross_kv(p, cfg: ModelConfig, enc_out):
    """Precompute cross-attention K/V from encoder output."""
    B, Sk, _ = enc_out.shape
    k = (enc_out @ p["wk"]).reshape(B, Sk, cfg.n_kv_heads, cfg.head_dim)
    v = (enc_out @ p["wv"]).reshape(B, Sk, cfg.n_kv_heads, cfg.head_dim)
    return k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)


def cross_attention_decode(p, cfg: ModelConfig, x, ck, cv):
    """One-token cross-attention against precomputed encoder K/V."""
    B = x.shape[0]
    hd = cfg.head_dim
    q = (x @ p["wq"]).reshape(B, 1, cfg.n_heads, hd)
    G = cfg.n_heads // cfg.n_kv_heads
    qg = q.reshape(B, cfg.n_kv_heads, G, hd)
    sc = jnp.einsum("bhgd,bkhd->bhgk", qg.astype(jnp.float32),
                    ck.astype(jnp.float32)) / math.sqrt(hd)
    pr = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", pr, cv.astype(jnp.float32))
    return o.reshape(B, 1, cfg.q_dim).astype(x.dtype) @ p["wo"]


# ---------------------------------------------------------------------- #
# Multi-head latent attention (DeepSeek-V2)
# ---------------------------------------------------------------------- #
#
# Per token, x W_kv_a gives a latent c~ (kv_lora_rank) and one rope key
# k_pe (qk_rope_dim) that every head shares; c = RMSNorm(c~).  Each head's
# key is [c W_UK_h, k_pe] and its value c W_UV_h, where W_kv_b = [W_UK_h,
# W_UV_h]_h.  The cache holds only c and k_pe (576 values a token a layer
# at DeepSeek-V2's widths): no head axis.  Prefill decompresses k and v and
# runs flash attention at the query-key width; decode never does: W_UK is
# folded into the query and W_UV is applied to the attention-weighted
# latent ("absorbed").
#
# The paged pools keep c as (layer, block, token, kv_lora_rank) and k_pe
# as (layer, block, token * qk_rope_dim), a block's rope keys side by side
# in one row.  A (…, 16, 576) pool of whole rows is not kept: its minor
# dim is no multiple of the TPU's 128 lanes, so the TPU's default layout
# transposes it (block ids minor-most) and every decode step would copy
# the whole pool into the layout its gather needs and back.

def init_mla(key, cfg: ModelConfig) -> dict:
    ks = jax.random.split(key, 4)
    D, H, a = cfg.d_model, cfg.n_heads, cfg.mla
    return {
        "wq": dense_init(ks[0], D, H * a.qk_dim),
        "wkv_a": dense_init(ks[1], D, a.latent_dim),
        "kv_norm": jnp.zeros((a.kv_lora_rank,), jnp.float32),
        "wkv_b": dense_init(ks[2], a.kv_lora_rank,
                            H * (a.qk_nope_dim + a.v_head_dim)),
        "wo": dense_init(ks[3], H * a.v_head_dim, D),
    }


def mla_rope_freq(cfg: ModelConfig) -> np.ndarray:
    return rope_inv_freq(cfg.mla.qk_rope_dim, cfg.rope_theta, cfg.yarn)


def mla_mscale_sq(cfg: ModelConfig) -> float:
    """The factor on the softmax scale qk_dim^-0.5: YaRN's
    mscale(factor, mscale_all_dim) squared, as DeepSeek-V2's source and vLLM
    apply it (``transformers``' port leaves it out).  The rotation itself
    is scaled by mscale(factor, mscale) / mscale(factor, mscale_all_dim),
    1 for DeepSeek-V2, so it is not applied."""
    y = cfg.yarn
    if y is None or not y.mscale_all_dim:
        return 1.0
    return yarn_mscale(y.factor, y.mscale_all_dim) ** 2


def mla_softmax_scale(cfg: ModelConfig) -> float:
    return cfg.mla.qk_dim ** -0.5 * mla_mscale_sq(cfg)


def mla_latent(p, cfg: ModelConfig, x, positions):
    """(q_nope, q_pe, c, k_pe) of tokens x: (B,S,D) at positions (S,) or
    (B,S).  q_nope (B,S,H,qk_nope_dim) and the rotated q_pe
    (B,S,H,qk_rope_dim) in float32; the normalised latent c
    (B,S,kv_lora_rank) and the rotated rope key k_pe (B,S,qk_rope_dim) in
    x's dtype: what the cache holds."""
    B, S, _ = x.shape
    a, H = cfg.mla, cfg.n_heads
    freq = mla_rope_freq(cfg)
    q = jnp.matmul(x, p["wq"], preferred_element_type=jnp.float32)
    q = q.reshape(B, S, H, a.qk_dim)
    q_pe = rope_pairs(q[..., a.qk_nope_dim:], positions, freq)
    kv_a = x @ p["wkv_a"]
    c = rmsnorm(kv_a[..., :a.kv_lora_rank], p["kv_norm"])
    k_pe = rope_pairs(kv_a[..., None, a.kv_lora_rank:], positions, freq)
    return q[..., :a.qk_nope_dim], q_pe, c, k_pe[..., 0, :]


def mla_prefill(p, cfg: ModelConfig, x):
    """Causal MLA over x: (B,S,D), decompressed.  Returns (y, c, k_pe),
    the cache's latents (B,S,kv_lora_rank) and rope keys (B,S,qk_rope_dim)
    in ``cfg.dtype``.

    Keys are qk_dim wide and values v_head_dim; the flash kernel takes one
    head width, so v is padded with zeros to qk_dim (as ``transformers``
    does for FlashAttention-2) and the pad is cut off its output.  The
    kernel scales scores by qk_dim^-0.5; YaRN's mscale^2 rides on q."""
    B, S, _ = x.shape
    a, H = cfg.mla, cfg.n_heads
    q_nope, q_pe, c, k_pe = mla_latent(p, cfg, x, jnp.arange(S))
    kv = (c @ p["wkv_b"]).reshape(B, S, H, a.qk_nope_dim + a.v_head_dim)
    k = jnp.concatenate([kv[..., :a.qk_nope_dim], jnp.broadcast_to(
        k_pe[:, :, None], (B, S, H, a.qk_rope_dim))], axis=-1)
    q = (jnp.concatenate([q_nope, q_pe], axis=-1)
         * mla_mscale_sq(cfg)).astype(x.dtype)
    v = jnp.pad(kv[..., a.qk_nope_dim:],
                [(0, 0)] * 3 + [(0, a.qk_dim - a.v_head_dim)])
    o = chunked_attention(q, k, v, causal=True)[..., :a.v_head_dim]
    y = o.reshape(B, S, H * a.v_head_dim) @ p["wo"]
    dt = jnp.dtype(cfg.dtype)
    return y, c.astype(dt), k_pe.astype(dt)


def mla_fwd(p, cfg: ModelConfig, x) -> jax.Array:
    return mla_prefill(p, cfg, x)[0]


def mla_absorbed(p, cfg: ModelConfig, q_nope, q_pe, c, pe, valid):
    """One query per slot against its cached tokens, absorbed.

    q_nope (B,H,qk_nope_dim), q_pe (B,H,qk_rope_dim) float32; the cache in
    M blocks of T tokens: latents c (B,M,T,kv_lora_rank), rope keys pe
    (B,M,T*qk_rope_dim) (a block's keys side by side), valid (B,M,T).
    score_{h,t} = (q_nope_h W_UK_h^T) . c_t + q_pe_h . k_pe_t;
    o_h = (sum_t p_{h,t} c_t) W_UV_h.  Returns (B,H,v_head_dim) float32.
    Nothing holds a per-head key or value of the context."""
    a, H = cfg.mla, cfg.n_heads
    B, M, T, r = c.shape
    wkv_b = p["wkv_b"].astype(jnp.float32).reshape(
        r, H, a.qk_nope_dim + a.v_head_dim)
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope, wkv_b[..., :a.qk_nope_dim])
    ctx = c.astype(jnp.float32)
    s = jnp.einsum("bhr,bmtr->bhmt", q_lat, ctx)
    # a block's rope keys lie side by side in one row: score that row
    # against the query laid out block-diagonally, (T*dr, T)
    q_blk = jnp.einsum("bhd,tu->bhtdu", q_pe, jnp.eye(T, dtype=jnp.float32))
    s = s + jnp.einsum("bmk,bhku->bhmu", pe.astype(jnp.float32),
                       q_blk.reshape(B, H, T * a.qk_rope_dim, T))
    s = jnp.where(valid[:, None], s * mla_softmax_scale(cfg), -1e30)
    pr = jax.nn.softmax(s, axis=(-2, -1))
    o_lat = jnp.einsum("bhmt,bmtr->bhr", pr, ctx)
    return jnp.einsum("bhr,rhv->bhv", o_lat, wkv_b[..., a.qk_nope_dim:])


def _mla_out(p, cfg: ModelConfig, o, x):
    B = x.shape[0]
    return (o.reshape(B, 1, cfg.n_heads * cfg.mla.v_head_dim).astype(x.dtype)
            @ p["wo"])


def mla_decode(p, cfg: ModelConfig, x, cache_c, cache_pe, pos):
    """One-token MLA decode against a dense cache: latents (B,S_c,
    kv_lora_rank) and rope keys (B,S_c,qk_rope_dim); pos: scalar int32,
    the tokens already cached."""
    B = x.shape[0]
    q_nope, q_pe, c, k_pe = mla_latent(p, cfg, x, pos[None])
    S_c = cache_c.shape[1]
    slot = jnp.minimum(pos, S_c - 1)
    cache_c = lax.dynamic_update_slice(cache_c, c.astype(cache_c.dtype),
                                       (0, slot, 0))
    cache_pe = lax.dynamic_update_slice(cache_pe, k_pe.astype(cache_pe.dtype),
                                        (0, slot, 0))
    valid = jnp.broadcast_to((jnp.arange(S_c) <= pos)[None, :, None],
                             (B, S_c, 1))
    o = mla_absorbed(p, cfg, q_nope[:, 0], q_pe[:, 0], cache_c[:, :, None],
                     cache_pe, valid)
    return _mla_out(p, cfg, o, x), cache_c, cache_pe


def paged_mla_decode(p, cfg: ModelConfig, x, pool_c, pool_pe, layer,
                     block_tables, pos):
    """One-token MLA decode against a run's paged pools: latents (n_layers,
    n_blocks, block_size, kv_lora_rank) and rope keys (n_layers, n_blocks,
    block_size * qk_rope_dim).  Writes each slot's new token at ``(layer,
    block, offset)`` and reads the blocks of its table, as
    :func:`paged_attention_decode` does for k and v."""
    B = x.shape[0]
    dr = cfg.mla.qk_rope_dim
    q_nope, q_pe, c, k_pe = mla_latent(p, cfg, x, pos[:, None])
    BS = pool_c.shape[2]
    bidx = block_tables[jnp.arange(B), pos // BS]
    off = pos % BS
    pool_c = pool_c.at[layer, bidx, off].set(c[:, 0].astype(pool_c.dtype))
    lanes = (off * dr)[:, None] + jnp.arange(dr)
    pool_pe = pool_pe.at[layer, bidx[:, None], lanes].set(
        k_pe[:, 0].astype(pool_pe.dtype))
    MB = block_tables.shape[1]
    idx = jnp.arange(MB)[:, None] * BS + jnp.arange(BS)
    valid = idx[None] <= pos[:, None, None]
    # gathered once: the scores and the weighted sum both read the latents
    c_rows, pe_rows = lax.optimization_barrier(
        (pool_c[layer, block_tables], pool_pe[layer, block_tables]))
    o = mla_absorbed(p, cfg, q_nope[:, 0], q_pe[:, 0], c_rows, pe_rows,
                     valid)
    return _mla_out(p, cfg, o, x), pool_c, pool_pe


# ---------------------------------------------------------------------- #
# MLP / MoE
# ---------------------------------------------------------------------- #

def init_mlp(key, cfg: ModelConfig, d_ff: int | None = None) -> dict:
    ks = jax.random.split(key, 3)
    D, F = cfg.d_model, d_ff or cfg.d_ff
    p = {"wi": dense_init(ks[0], D, F), "wo": dense_init(ks[1], F, D)}
    if cfg.activation in ("swiglu", "geglu"):
        p["wg"] = dense_init(ks[2], D, F)
    return p


def mlp_fwd(p, cfg: ModelConfig, x) -> jax.Array:
    h = x @ p["wi"]
    if cfg.activation == "swiglu":
        h = jax.nn.silu(x @ p["wg"]) * h
    elif cfg.activation == "geglu":
        h = jax.nn.gelu(x @ p["wg"]) * h
    else:
        h = jax.nn.gelu(h)
    return h @ p["wo"]


def init_moe(key, cfg: ModelConfig) -> dict:
    m = cfg.moe
    ks = jax.random.split(key, 5)
    D, Fe, E = cfg.d_model, m.d_ff_expert, m.n_experts
    scale = 1.0 / math.sqrt(D)
    Eh = m.held
    p = {
        "router": dense_init(ks[0], D, E).astype(jnp.float32),
        "w_in": _uniform(ks[1], (Eh, D, Fe), scale).astype(jnp.bfloat16),
        "w_gate": _uniform(ks[2], (Eh, D, Fe), scale).astype(jnp.bfloat16),
        "w_out": _uniform(ks[3], (Eh, Fe, D), 1.0 / math.sqrt(Fe)).astype(jnp.bfloat16),
    }
    if m.shared_expert:
        p["shared"] = init_mlp(ks[4], cfg, cfg.d_ff_shared)
    return p


MOE_CHUNK = 8192  # token-block size for the scanned dispatch


def _route(p, m, xt):
    """(gates, expert ids), each (T, top_k): greedy top-k of the softmax
    over all ``n_experts``, renormalised or not."""
    logits = xt.astype(jnp.float32) @ p["router"]
    gates, eidx = lax.top_k(jax.nn.softmax(logits, axis=-1), m.top_k)
    if m.norm_topk_prob:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, eidx


EXPERT_KEYS = ("w_in", "w_gate", "w_out")


def _local_experts(m, eidx):
    """(flat local expert ids (T*k,), mask of held choices or None).

    Where this device holds a share of the experts, ``[first_held,
    first_held + held)``, a choice of another expert gets id ``held``, so
    that it sorts after the held ones."""
    flat_e = eidx.reshape(-1)
    if m.held == m.n_experts:
        return flat_e, None
    local = flat_e - m.first_held
    mine = (local >= 0) & (local < m.held)
    return jnp.where(mine, local, m.held), mine


def _combine(gates, yo):
    """Each token's (T*k, D) choice outputs, token-major, weighted by its
    gates: (T, D)."""
    T, k = gates.shape
    yo = yo.reshape(T, k, yo.shape[-1])
    return jnp.einsum("tk,tkd->td", gates.astype(yo.dtype), yo)


def _moe_block(p, m, xt):
    """Route + dispatch + expert compute for one block of tokens (T, D).

    Dropless: the dispatch buffer has a row for every (token, choice).
    Choices of experts not held here sort after the held groups, which
    ``ragged_dot`` leaves out, and are masked off."""
    gates, eidx = _route(p, m, xt)                 # (T,k)
    flat_e, mine = _local_experts(m, eidx)         # (T*k,)
    order = jnp.argsort(flat_e)                    # stable sort by expert
    tok_for = order // m.top_k                     # token index per slot
    xs = xt[tok_for]                               # (T*k, D) sorted by expert
    group_sizes = jnp.bincount(flat_e, length=m.held)
    h = lax.ragged_dot(xs, p["w_in"], group_sizes)
    g = lax.ragged_dot(xs, p["w_gate"], group_sizes)
    h = jax.nn.silu(g) * h
    yo = lax.ragged_dot(h, p["w_out"], group_sizes)  # (T*k, D)
    yo = yo[jnp.argsort(order)]
    if mine is not None:
        yo = jnp.where(mine[:, None], yo, 0)
    return _combine(gates, yo)


def _moe_chosen(p, m, xt, layer):
    """``_moe_block`` for a few tokens, each (token, choice) computed from
    its held expert's weights in the stacked run (``p``'s w_* are (layers,
    held, ...)), read by index at ``layer``: no layer's expert set is
    sliced out, and no expert not chosen is read."""
    from repro.kernels.chosen_experts import chosen_expert_matmul

    gates, eidx = _route(p, m, xt)
    flat_e, _ = _local_experts(m, eidx)
    order = jnp.argsort(flat_e)
    yo = _replicated(chosen_expert_matmul)(
        xt[order // m.top_k], *(p[k] for k in EXPERT_KEYS), layer,
        flat_e[order])
    return _combine(gates, yo[jnp.argsort(order)])


def _replicated(kernel):
    """``kernel`` inside a shard_map naming every mesh axis, all operands
    replicated, where the caller left any axis ``Auto``: GSPMD cannot
    partition a Mosaic kernel."""
    am = jax.sharding.get_abstract_mesh()
    if all(t == jax.sharding.AxisType.Manual for t in am.axis_types):
        return kernel
    return jax.shard_map(kernel, in_specs=jax.sharding.PartitionSpec(),
                         out_specs=jax.sharding.PartitionSpec(),
                         axis_names=set(am.axis_names), check_vma=False)


def _moe_block_ep(p, m, xt, axis: str):
    """Expert-parallel MoE block INSIDE a shard_map manual over ``axis``.

    Each rank owns E_local = E/|axis| experts (w_* enter as local slices).
    Tokens are replicated across the model axis (as GSPMD already keeps the
    residual stream), so dispatch is a LOCAL capacity-bounded gather — no
    all-to-all, and crucially no per-block all-gather of expert weights
    (GSPMD cannot partition ragged_dot and was gathering all experts every
    chunk: measured 9.3 TB/chip on llama4 prefill_32k).  Combine = one psum.
    """
    T, D = xt.shape
    nshards = int(lax.psum(1, axis))
    rank = lax.axis_index(axis)
    E_local = p["w_in"].shape[0]          # local expert slice
    e0 = rank * E_local

    gates, eidx = _route(p, m, xt)                  # router is replicated

    flat_e = eidx.reshape(-1)                       # (T*k,) global expert ids
    local = flat_e - e0
    mine = (local >= 0) & (local < E_local)
    # capacity per rank: fair share + slack for imbalance
    C = int(T * m.top_k * m.capacity_factor) // nshards
    C = max(C - C % 8, 8)
    # sort my slots first (by local expert id), overflow + others last
    key = jnp.where(mine, local, E_local)
    order = jnp.argsort(key)[:C]                    # static-size selection
    sel_local = key[order]                          # E_local == padding
    valid = sel_local < E_local
    tok_for = order // m.top_k
    xs = jnp.where(valid[:, None], xt[tok_for], 0.0)
    group_sizes = jnp.bincount(jnp.where(valid, sel_local, E_local),
                               length=E_local + 1)[:E_local]
    h = lax.ragged_dot(xs, p["w_in"], group_sizes)
    g = lax.ragged_dot(xs, p["w_gate"], group_sizes)
    h = jax.nn.silu(g) * h
    yo = lax.ragged_dot(h, p["w_out"], group_sizes)  # (C, D)
    w = jnp.where(valid, gates.reshape(-1)[order], 0.0)
    out = jnp.zeros((T, D), jnp.float32).at[tok_for].add(
        yo.astype(jnp.float32) * w[:, None])
    return lax.psum(out, axis).astype(xt.dtype)


def expert_parallel(m, mesh) -> bool:
    """Whether ``moe_fwd`` splits the experts over ``mesh``'s ``model``
    axis (an abstract or concrete mesh, or None): the axis exists, is
    wider than 1 and divides the experts, and all of them are held."""
    if mesh is None or "model" not in mesh.axis_names:
        return False
    msize = mesh.shape["model"]
    return msize > 1 and m.n_experts % msize == 0 and m.held == m.n_experts


def reads_chosen_experts(m, T: int, mesh) -> bool:
    """Whether ``moe_fwd`` over T tokens, handed a run's stacked experts
    and a layer index, computes each (token, choice) from its held
    expert's stacked weights (``_moe_chosen``): while the choices are no
    more than the experts held, that reads fewer bytes than the layer's
    whole held set.  Expert parallelism keeps its own path."""
    return T * m.top_k <= m.held and not expert_parallel(m, mesh)


def moe_fwd(p, cfg: ModelConfig, x, chunk: int = MOE_CHUNK,
            layer=None) -> jax.Array:
    """Top-k MoE via sort + lax.ragged_dot (MegaBlocks-style).

    Three data paths:
      * explicit expert parallelism (shard_map manual over `model`) when a
        model axis exists and divides n_experts — local capacity-bounded
        dispatch, one combine psum;
      * with ``layer`` given (``p``'s w_* then stacked over a run's layers)
        and few tokens (``reads_chosen_experts``), each choice read from
        its held expert in the stacked run by the ``chosen_expert_matmul``
        kernel;
      * single-device ragged path otherwise (tests, no-TP meshes), on
        ``layer``'s slice of stacked weights.
    Long sequences are scanned in token blocks with remat: dispatch buffers
    live only per block (8x working-set cut at olmoe prefill_32k)."""
    import jax.sharding as jsh

    m = cfg.moe
    B, S, D = x.shape
    T = B * S
    xt = x.reshape(T, D)

    block = None
    am = jsh.get_abstract_mesh()
    if layer is not None:
        if reads_chosen_experts(m, T, am):
            block = lambda xb: _moe_chosen(p, m, xb, layer)
        else:
            p = dict(p, **{k: p[k][layer] for k in EXPERT_KEYS})
    if block is None and expert_parallel(m, am):
        # dp axes still in AUTO state (e.g. the GSPMD serving path) must
        # become manual alongside `model`, with tokens sharded over them
        # — otherwise the P() token spec would force an all-gather of
        # the whole global batch onto every device.
        auto_dp = tuple(
            n for n, t in zip(am.axis_names, am.axis_types)
            if n in ("pod", "data") and "Auto" in str(t))
        manual = {"model", *auto_dp}
        tok_spec = (jsh.PartitionSpec(auto_dp, None) if auto_dp
                    else jsh.PartitionSpec())
        especs = {
            "router": jsh.PartitionSpec(),
            "w_in": jsh.PartitionSpec("model", None, None),
            "w_gate": jsh.PartitionSpec("model", None, None),
            "w_out": jsh.PartitionSpec("model", None, None),
        }
        if m.shared_expert:
            especs["shared"] = jax.tree.map(
                lambda _: jsh.PartitionSpec(), p["shared"])
        ep = jax.shard_map(
            lambda pp, xb: _moe_block_ep(pp, m, xb, "model"),
            in_specs=(especs, tok_spec),
            out_specs=tok_spec,
            axis_names=manual, check_vma=False)
        block = lambda xb: ep(p, xb)
    if block is None:
        block = lambda xb: _moe_block(p, m, xb)

    if T <= chunk or T % chunk:
        y = block(xt).reshape(B, S, D)
    else:
        blocks = xt.reshape(T // chunk, chunk, D)
        body = jax.checkpoint(block, prevent_cse=False)
        y = lax.scan(lambda c, xb: (c, body(xb)), None, blocks)[1]
        y = y.reshape(B, S, D)
    if m.shared_expert:
        y = y + mlp_fwd(p["shared"], cfg, x)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------- #
# RG-LRU (Griffin / recurrentgemma recurrent block)
# ---------------------------------------------------------------------- #

def init_rglru(key, cfg: ModelConfig) -> dict:
    R = cfg.d_rnn or cfg.d_model
    D = cfg.d_model
    ks = jax.random.split(key, 6)
    return {
        "w_x": dense_init(ks[0], D, R),
        "w_gate": dense_init(ks[1], D, R),
        "conv": _uniform(ks[2], (4, R), 0.5).astype(jnp.bfloat16),
        "w_a": dense_init(ks[3], R, R),
        "w_i": dense_init(ks[4], R, R),
        "lam": jnp.linspace(-4.3, -9.0, R).astype(jnp.float32),  # a in (.9,.999)
        "w_out": dense_init(ks[5], R, D),
    }


def _rglru_gates(p, u):
    """u: (..., R) conv output -> (a, gated_input) both f32."""
    uf = u.astype(jnp.float32)
    r = jax.nn.sigmoid(uf @ p["w_a"].astype(jnp.float32))
    i = jax.nn.sigmoid(uf @ p["w_i"].astype(jnp.float32))
    log_a = -8.0 * jax.nn.softplus(p["lam"]) * r  # c=8 per Griffin
    a = jnp.exp(log_a)
    b = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-6)) * (i * uf)
    return a, b


def rglru_fwd(p, cfg: ModelConfig, x, h0=None):
    """x: (B,S,D) -> (B,S,D).  Linear diagonal recurrence via associative
    scan: h_t = a_t h_{t-1} + b_t."""
    B, S, D = x.shape
    u = x @ p["w_x"]
    gate = x @ p["w_gate"]
    # causal depthwise conv, kernel 4
    upad = jnp.pad(u, ((0, 0), (3, 0), (0, 0)))
    u = sum(upad[:, i : i + S] * p["conv"][i] for i in range(4))
    a, b = _rglru_gates(p, u)
    if h0 is not None:
        b = b.at[:, 0].add(a[:, 0] * h0)
    def combine(l, r):
        al, bl = l
        ar, br = r
        return al * ar, ar * bl + br
    _, h = lax.associative_scan(combine, (a, b), axis=1)
    y = (jax.nn.gelu(gate.astype(jnp.float32)) * h).astype(x.dtype)
    return y @ p["w_out"], h[:, -1]


def rglru_prefill(p, cfg: ModelConfig, x):
    """Forward + recurrent/conv state for decode continuation."""
    B, S, D = x.shape
    u_pre = x @ p["w_x"]                # pre-conv inputs
    y, h_last = rglru_fwd(p, cfg, x)
    if S >= 3:
        conv_state = u_pre[:, -3:]
    else:
        conv_state = jnp.pad(u_pre, ((0, 0), (3 - S, 0), (0, 0)))
    return y, h_last.astype(jnp.float32), conv_state


def rglru_decode(p, cfg: ModelConfig, x, h_prev, conv_state):
    """x: (B,1,D); h_prev: (B,R); conv_state: (B,3,R)."""
    u_new = (x @ p["w_x"])[:, 0]                      # (B,R)
    gate = (x @ p["w_gate"])[:, 0]
    window = jnp.concatenate([conv_state, u_new[:, None]], axis=1)  # (B,4,R)
    u = jnp.einsum("bkr,kr->br", window, p["conv"])
    a, b = _rglru_gates(p, u)
    h = a * h_prev + b
    y = (jax.nn.gelu(gate.astype(jnp.float32)) * h).astype(x.dtype)
    return (y @ p["w_out"])[:, None], h, window[:, 1:]


# ---------------------------------------------------------------------- #
# RWKV-6 ("Finch"): linear attention with data-dependent per-channel decay
# ---------------------------------------------------------------------- #

def init_rwkv6(key, cfg: ModelConfig) -> dict:
    D, F = cfg.d_model, cfg.d_ff
    H = D // cfg.rwkv_head_dim
    ks = jax.random.split(key, 9)
    return {
        # time-mix
        "w_r": dense_init(ks[0], D, D),
        "w_k": dense_init(ks[1], D, D),
        "w_v": dense_init(ks[2], D, D),
        "w_g": dense_init(ks[3], D, D),
        "w_w": dense_init(ks[4], D, D),     # decay projection
        "w_o": dense_init(ks[5], D, D),
        "u": _uniform(ks[6], (H, cfg.rwkv_head_dim), 0.5).astype(jnp.float32),
        "mix": _uniform(ks[7], (5, D), 0.5).astype(jnp.float32),  # r,k,v,g,w
        # channel-mix
        "cm_k": dense_init(ks[8], D, F),
        "cm_v": dense_init(jax.random.fold_in(key, 99), F, D),
        "cm_r": dense_init(jax.random.fold_in(key, 98), D, D),
        "cm_mix": _uniform(jax.random.fold_in(key, 97), (2, D), 0.5).astype(jnp.float32),
    }


def _token_shift(x, x_prev_last=None):
    """x_{t-1} with zero (or carried state) at t=0.  x: (B,S,D)."""
    shifted = jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    if x_prev_last is not None:
        shifted = shifted.at[:, 0].set(x_prev_last)
    return shifted


def _wkv_chunk_scan(r, k, v, w, u, chunk: int):
    """Chunked linear recurrence S_t = diag(w_t) S_{t-1} + k_t v_t^T with
    per-step output o_t = r_t S_{t-1} + (r_t . (u*k_t)) v_t.

    r,k,v,w: (B,S,H,hd) — w in (0,1); u: (H,hd).  Returns (o, S_final).
    """
    B, S, H, hd = r.shape
    C = chunk
    if S % C != 0:
        raise ValueError(f"linear-attention chunking needs S % chunk "
                         f"== 0, got S={S} chunk={C}")
    n = S // C
    rs = r.reshape(B, n, C, H, hd).transpose(1, 0, 3, 2, 4)  # (n,B,H,C,hd)
    ks_ = k.reshape(B, n, C, H, hd).transpose(1, 0, 3, 2, 4)
    vs = v.reshape(B, n, C, H, hd).transpose(1, 0, 3, 2, 4)
    ws = w.reshape(B, n, C, H, hd).transpose(1, 0, 3, 2, 4)

    def step(S_prev, x):
        rc, kc, vc, wc = x  # (B,H,C,hd)
        logw = jnp.log(jnp.maximum(wc, 1e-8))
        e = jnp.exp(jnp.cumsum(logw, axis=2))        # e_t = prod_{j<=t} w_j
        e_excl = e / jnp.maximum(wc, 1e-8)           # e_{t-1} relative
        # inter-chunk: o_t += (r_t * e_excl_t) @ S_prev
        o = jnp.einsum("bhtd,bhde->bhte", rc * e_excl, S_prev)
        # intra-chunk: scores_{t,j} = (r_t*e_excl_t) . (k_j/e_j), j < t
        kk = kc / jnp.maximum(e, 1e-30)
        sc = jnp.einsum("bhtd,bhjd->bhtj", rc * e_excl, kk)
        mask = jnp.tril(jnp.ones((C, C), bool), -1)
        sc = jnp.where(mask, sc, 0.0)
        o = o + jnp.einsum("bhtj,bhjd->bhtd", sc, vc)
        # diagonal bonus term
        bonus = jnp.einsum("bhtd,bhtd->bht", rc, u[None, :, None, :] * kc)
        o = o + bonus[..., None] * vc
        # state update
        S_new = e[:, :, -1][..., None] * S_prev + jnp.einsum(
            "bhtd,bhte->bhde", kk * e[:, :, -1][:, :, None], vc)
        return S_new, o

    S0 = jnp.zeros((B, H, hd, hd), jnp.float32)
    S_fin, os_ = lax.scan(step, S0, (rs, ks_, vs, ws))
    o = os_.transpose(1, 0, 3, 2, 4).reshape(B, S, H, hd)
    return o, S_fin


def rwkv6_fwd(p, cfg: ModelConfig, x, chunk: int = 16, return_state: bool = False):
    """RWKV-6 time-mix sublayer (pre-norm handled by caller).  x: (B,S,D)."""
    B, S, D = x.shape
    hd = cfg.rwkv_head_dim
    H = D // hd
    xp = _token_shift(x)
    mix = p["mix"].astype(x.dtype)
    xr, xk, xv, xg, xw = (x + mix[i] * (xp - x) for i in range(5))
    r = (xr @ p["w_r"]).reshape(B, S, H, hd).transpose(0, 1, 2, 3)
    k = (xk @ p["w_k"]).reshape(B, S, H, hd)
    v = (xv @ p["w_v"]).reshape(B, S, H, hd)
    g = xg @ p["w_g"]
    # Decay clamp keeps the factored chunk recurrence in f32 range for
    # chunk<=16 (see _wkv_chunk_scan numerics note in DESIGN.md).
    w = jnp.exp(-jnp.exp(jnp.clip((xw @ p["w_w"]).astype(jnp.float32),
                                  -8, 0.5))).reshape(B, S, H, hd)
    # pad sequence to a chunk multiple (zero k contributes nothing; w=1 keeps
    # the state unchanged so S_fin stays exact)
    pad = (-S) % chunk
    if pad:
        zpad = lambda t, fill=0.0: jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)),
                                           constant_values=fill)
        r, k, v = (zpad(t.astype(jnp.float32)) for t in (r, k, v))
        w = zpad(w, fill=1.0)
    o, S_fin = _wkv_chunk_scan(r.astype(jnp.float32), k.astype(jnp.float32),
                               v.astype(jnp.float32), w, p["u"], chunk)
    if pad:
        o = o[:, :S]
    o = (o.reshape(B, S, D) * jax.nn.silu(g.astype(jnp.float32))).astype(x.dtype)
    y = o @ p["w_o"]
    if return_state:
        return y, {"S": S_fin, "x_tm": x[:, -1], "x_cm": x[:, -1]}
    return y


def rwkv6_channel_mix(p, cfg: ModelConfig, x):
    xp = _token_shift(x)
    mix = p["cm_mix"].astype(x.dtype)
    xk = x + mix[0] * (xp - x)
    xr = x + mix[1] * (xp - x)
    kk = jnp.square(jax.nn.relu(xk @ p["cm_k"]))
    return jax.nn.sigmoid((xr @ p["cm_r"]).astype(jnp.float32)).astype(x.dtype) * (kk @ p["cm_v"])


def rwkv6_channel_mix_decode(p, cfg: ModelConfig, x, x_cm_prev):
    """Single-token channel mix.  x: (B,1,D); x_cm_prev: (B,D)."""
    xt = x[:, 0]
    mix = p["cm_mix"].astype(x.dtype)
    xk = xt + mix[0] * (x_cm_prev - xt)
    xr = xt + mix[1] * (x_cm_prev - xt)
    kk = jnp.square(jax.nn.relu(xk @ p["cm_k"]))
    y = jax.nn.sigmoid((xr @ p["cm_r"]).astype(jnp.float32)).astype(x.dtype) * (kk @ p["cm_v"])
    return y[:, None], xt


def rwkv6_decode(p, cfg: ModelConfig, x, state):
    """Single-token step.  state = {"S": (B,H,hd,hd), "x_tm": (B,D),
    "x_cm": (B,D)}."""
    B, _, D = x.shape
    hd = cfg.rwkv_head_dim
    H = D // hd
    xt = x[:, 0]
    mix = p["mix"].astype(x.dtype)
    xp = state["x_tm"]
    xr, xk, xv, xg, xw = (xt + mix[i] * (xp - xt) for i in range(5))
    r = (xr @ p["w_r"]).reshape(B, H, hd).astype(jnp.float32)
    k = (xk @ p["w_k"]).reshape(B, H, hd).astype(jnp.float32)
    v = (xv @ p["w_v"]).reshape(B, H, hd).astype(jnp.float32)
    g = xg @ p["w_g"]
    w = jnp.exp(-jnp.exp(jnp.clip((xw @ p["w_w"]).astype(jnp.float32), -8, 0.5)))
    w = w.reshape(B, H, hd)
    S = state["S"]
    o = jnp.einsum("bhd,bhde->bhe", r, S) + \
        jnp.einsum("bhd,bhd->bh", r, p["u"][None] * k)[..., None] * v
    S = w[..., None] * S + k[..., None] * v[:, :, None, :]
    o = (o.reshape(B, D) * jax.nn.silu(g.astype(jnp.float32))).astype(x.dtype)
    y = (o @ p["w_o"])[:, None]
    # channel mix on (y + x)? caller handles residuals; here only state keep
    new_state = dict(state, S=S, x_tm=xt)
    return y, new_state
