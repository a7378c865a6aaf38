"""Plain float32 reference of a DeepSeek-V2 stack (multi-head latent
attention, a dense first layer, then top-k MoE layers with shared experts
of which this chip holds a share), written from the configuration file
alone, from the published equations (``modeling_deepseek.py`` of
DeepSeek-V2).  It imports nothing of the program and makes its own
weights again from the seed (``bench/weights.py``), layer by layer.

Attention is the decompressed form over the whole sequence: every head's
key [c W_UK_h, k_pe] and value c W_UV_h are made from the latent c, and
the scores are scaled by qk^-0.5 times YaRN's mscale squared, as the
source does (the ``transformers`` port leaves mscale out).  Rotary
positions rotate interleaved pairs at YaRN's frequencies.  The MoE takes
the softmax over every routed expert, its greedy top k, gates not
renormalised where ``norm_topk_prob`` is false, and adds the parts of the
held experts only; every held expert runs on every token, weighted by its
gate (zero where not chosen).

Every matrix product runs at ``Precision.HIGHEST``.  ``quant="f8"`` rounds
every activation the program holds in bfloat16 to float8 e4m3 with one
scale per row and every weight with one scale per output column (the
control that ``correct`` has to reject); ``quant="bf16"`` rounds them to
bfloat16 instead (for the CPU tests' tolerances).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0
Q_BLOCK = 256       # query rows whose attention scores are made at once
T_BLOCK = 1024      # token rows of one MLP or MoE block
PAD = 1024          # sequences are padded to a multiple of this
ROWS = 64           # served positions are read in blocks of this many


def _round(x, quant, axis):
    if quant == "f8":
        s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                        1e-30) / F8_MAX
        return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    if quant == "bf16":
        return x.astype(jnp.bfloat16).astype(F32)
    return x


def _act(x, quant):
    """An activation as the computation holds it."""
    return _round(x, quant, -1)


def _mm(a, b, quant):
    """a (..., K) @ b (K, N) in float32, b rounded per output column and
    the product per row."""
    b = _round(b.astype(F32), quant, 0)
    return _act(jnp.matmul(a.astype(F32), b, precision=HI), quant)


def rmsnorm(x, w, eps):
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * (1.0 + w.astype(F32))


# ---------------------------------------------------------------------- #
# Rotary positions with YaRN
# ---------------------------------------------------------------------- #

def yarn_get_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def rope_freq(c) -> np.ndarray:
    """The qk_rope_head_dim / 2 inverse frequencies (float64)."""
    dim, base = c["qk_rope_head_dim"], float(c["rope_theta"])
    freq = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    rs = c.get("rope_scaling")
    if not rs:
        return freq
    factor, orig = rs["factor"], rs["original_max_position_embeddings"]

    def dim_of(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(dim_of(rs["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rs["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3),
                   0.0, 1.0)
    keep = 1.0 - ramp        # share of the unscaled (extrapolated) frequency
    return freq / factor * (1.0 - keep) + freq * keep


def softmax_scale(c) -> float:
    scale = (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]) ** -0.5
    rs = c.get("rope_scaling")
    if rs and rs.get("mscale_all_dim"):
        scale *= yarn_get_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return scale


def rope(x, positions, c):
    """Rotate interleaved pairs of x (S, H, d) at ``positions`` (S,)."""
    rs = c.get("rope_scaling") or {}
    m = (yarn_get_mscale(rs["factor"], rs["mscale"])
         / yarn_get_mscale(rs["factor"], rs["mscale_all_dim"])) if rs else 1.0
    ang = positions.astype(F32)[:, None] * jnp.asarray(rope_freq(c), F32)
    cos, sin = (m * jnp.cos(ang))[:, None, :], (m * jnp.sin(ang))[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


# ---------------------------------------------------------------------- #
# One layer
# ---------------------------------------------------------------------- #

def attention(c, w, x, quant):
    """Causal latent attention of x (S, D), decompressed."""
    S = x.shape[0]
    H, r = c["num_attention_heads"], c["kv_lora_rank"]
    dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                  c["v_head_dim"])
    pos = jnp.arange(S)
    q = _mm(x, w["wq"], quant).reshape(S, H, dn + dr)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], pos, c)], -1)
    kv_a = _mm(x, w["wkv_a"], quant)
    lat = _act(rmsnorm(kv_a[:, :r], w["kv_norm"], c["rms_norm_eps"]), quant)
    k_pe = _act(rope(kv_a[:, None, r:], pos, c), quant)
    kv = _mm(lat, w["wkv_b"], quant).reshape(S, H, dn + dv)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_pe, (S, H, dr))], -1)
    v = kv[..., dn:]
    scale = softmax_scale(c)

    def rows(args):
        qb, lo = args
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) * scale
        causal = (lo + jnp.arange(Q_BLOCK))[:, None] >= pos[None, :]
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    o = jax.lax.map(rows, (q.reshape(S // Q_BLOCK, Q_BLOCK, H, dn + dr),
                           jnp.arange(0, S, Q_BLOCK)))
    return _mm(_act(o.reshape(S, H * dv), quant), w["wo"], quant)


def swiglu(x, wi, wg, wo, quant):
    h = _act(jax.nn.silu(_mm(x, wg, quant)) * _mm(x, wi, quant), quant)
    return _mm(h, wo, quant)


def moe(c, w, x, quant):
    """The held experts' part of the top-k MoE, plus the shared experts."""
    E, k = w["router"].shape[1], c["num_experts_per_tok"]
    first = c.get("held_experts_first", 0)
    held = w["w_in"].shape[0]
    probs = jax.nn.softmax(jnp.matmul(x, w["router"].astype(F32),
                                      precision=HI), axis=-1)
    top, idx = jax.lax.top_k(probs, k)
    if c["norm_topk_prob"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    top = top * c["routed_scaling_factor"]
    gate = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], idx].set(top)[:, first:first + held]

    def expert(y, e):
        wi, wg, wo, g = e
        return y + g[:, None] * swiglu(x, wi, wg, wo, quant), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                        (w["w_in"], w["w_gate"], w["w_out"], gate.T))
    if "shared" in w:
        s = w["shared"]
        y = y + swiglu(x, s["wi"], s["wg"], s["wo"], quant)
    return y


def mlp(c, w, x, quant):
    if "router" in w:
        return moe(c, w, x, quant)
    return swiglu(x, w["wi"], w["wg"], w["wo"], quant)


def layer(c, w, h, quant=None):
    """One decoder layer on h (S, D) float32; S a multiple of PAD."""
    eps = c["rms_norm_eps"]
    x = _act(rmsnorm(h, w["norm1"], eps), quant)
    h = _act(h + attention(c, w["attn"], x, quant), quant)
    S, D = h.shape

    def block(hb):
        xb = _act(rmsnorm(hb, w["norm2"], eps), quant)
        return _act(hb + mlp(c, w["mlp"], xb, quant), quant)

    return jax.lax.map(block, h.reshape(S // T_BLOCK, T_BLOCK, D)).reshape(
        S, D)


def embed(c, top, tokens, quant=None):
    return _act(top["embed"].astype(F32)[tokens]
                * math.sqrt(c["hidden_size"]), quant)


def head(c, top):
    return top["embed"].T if c["tie_word_embeddings"] else top["lm_head"]


def logits(c, top, h, quant=None):
    x = _act(rmsnorm(h, top["final_norm"], c["rms_norm_eps"]), quant)
    return _mm(x, head(c, top), quant)


def padded_len(n: int) -> int:
    return -(-n // PAD) * PAD


def forward(c, top, layers, tokens, quant=None):
    """Logits (S, V) of the whole sequence ``tokens`` (S a multiple of
    PAD) under the given weights: the top leaves and one tree per layer."""
    with jax.default_matmul_precision("highest"):
        h = embed(c, top, tokens, quant)
        for w in layers:
            h = layer(c, w, h, quant)
        return logits(c, top, h, quant)


# ---------------------------------------------------------------------- #
# Serving: the gap of each served token below the reference's best
# ---------------------------------------------------------------------- #

@functools.lru_cache(maxsize=None)
def _jit_layer(quant):
    return jax.jit(lambda c, w, h: layer(c, w, h, quant), static_argnums=0)


@functools.partial(jax.jit, static_argnums=(0, 5))
def _gaps(c, top, h, start, toks, n_rows, ctrl_h=None):
    """Gaps at rows [start, start + n_rows) of h: of the served tokens,
    and of the tokens the control's float8 logits put first."""
    D = h.shape[1]
    ref = logits(c, top, jax.lax.dynamic_slice(h, (start, 0), (n_rows, D)))
    best = jnp.max(ref, -1)
    out = best - jnp.take_along_axis(ref, toks[:, None], -1)[:, 0]
    if ctrl_h is None:
        return out, out
    ch = jax.lax.dynamic_slice(ctrl_h, (start, 0), (n_rows, D))
    pick = jnp.argmax(logits(c, top, ch, "f8"), -1)
    return out, best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]


def served_gaps(c: dict, seed: int, items: list, control: bool = False):
    """For each (prompt, served tokens): the gap by which each served
    token's reference logit lies below the reference's best at its
    position.  With ``control`` also the gap of the token the float8
    forward puts first there.  Returns (gaps, control gaps), each one
    float array per item."""
    with jax.default_matmul_precision("highest"):
        return _served_gaps(W.Frozen(c), seed, items, control)


def _served_gaps(c, seed, items, control):
    top = W.make_top(c, seed)
    seqs, hs, cs = [], [], []
    for prompt, served in items:
        full = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
        padded = np.zeros(padded_len(len(full) + ROWS), np.int32)
        padded[:len(full)] = full
        seqs.append((len(prompt), np.asarray(served, np.int32)))
        hs.append(embed(c, top, jnp.asarray(padded)))
        cs.append(embed(c, top, jnp.asarray(padded), "f8") if control
                  else None)
    for i in range(c["num_hidden_layers"]):
        w = W.make_layer(c, seed, i)
        hs = [_jit_layer(None)(c, w, h) for h in hs]
        if control:
            cs = [_jit_layer("f8")(c, w, h) for h in cs]
        del w
    out, ctrl = [], []
    for (P, served), h, ch in zip(seqs, hs, cs):
        g_all, c_all = [], []
        for r0 in range(0, len(served), ROWS):
            toks = np.zeros(ROWS, np.int32)
            part = served[r0:r0 + ROWS]
            toks[:len(part)] = part
            g, cg = _gaps(c, top, h, P - 1 + r0, jnp.asarray(toks), ROWS,
                          ch)
            g_all.append(np.asarray(g)[:len(part)])
            c_all.append(np.asarray(cg)[:len(part)])
        out.append(np.concatenate(g_all))
        ctrl.append(np.concatenate(c_all))
    return out, (ctrl if control else None)
