"""Model assembly: init / train forward / prefill / decode, for every
assigned architecture family (dense, MoE, VLM, hybrid, audio enc-dec, SSM).

The layer stack is executed as *run-grouped scans*: maximal runs of identical
block kinds are stacked (leading run dim) and driven by ``lax.scan`` with
``jax.checkpoint`` on the body — keeps the lowered HLO size O(#runs), not
O(#layers), which is what makes 512-device dry-run compiles tractable, and
gives the standard remat memory/compute trade.
"""
from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from . import layers as L
from .config import ModelConfig

# ---------------------------------------------------------------------- #
# Init
# ---------------------------------------------------------------------- #

def _init_layer(key, cfg: ModelConfig, kind: str, cross: bool, causal: bool,
                moe: bool):
    ks = jax.random.split(key, 4)
    D = cfg.d_model
    p: dict[str, Any] = {"norm1": jnp.zeros((D,), jnp.float32),
                         "norm2": jnp.zeros((D,), jnp.float32)}
    if kind in ("attn", "local"):
        p["attn"] = L.init_attention(ks[0], cfg)
    elif kind == "mla":
        p["attn"] = L.init_mla(ks[0], cfg)
    elif kind == "rglru":
        p["rec"] = L.init_rglru(ks[0], cfg)
    elif kind == "rwkv6":
        p["rwkv"] = L.init_rwkv6(ks[0], cfg)
    else:
        raise ValueError(kind)
    if kind != "rwkv6":
        p["mlp"] = L.init_moe(ks[1], cfg) if moe else L.init_mlp(ks[1], cfg)
    if cross:
        p["norm_x"] = jnp.zeros((D,), jnp.float32)
        p["xattn"] = L.init_attention(ks[2], cfg, cross=True)
    return p


def _init_run(key, cfg: ModelConfig, kind: str, n: int, cross: bool,
              causal: bool, moe: bool):
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: _init_layer(k, cfg, kind, cross, causal,
                                          moe))(keys)


def init_model(key, cfg: ModelConfig) -> dict:
    ks = jax.random.split(key, 8)
    D, V = cfg.d_model, cfg.vocab
    params: dict[str, Any] = {
        "embed": (jax.random.normal(ks[0], (V, D)) / math.sqrt(D)).astype(jnp.bfloat16),
        "final_norm": jnp.zeros((D,), jnp.float32),
    }
    cross = cfg.enc_dec is not None
    params["runs"] = [
        _init_run(jax.random.fold_in(ks[1], i), cfg, kind, n, cross, True,
                  cfg.layer_moe(first))
        for i, ((kind, n), first) in enumerate(zip(cfg.runs(),
                                                   cfg.run_firsts()))
    ]
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(ks[2], D, V)
    if cfg.enc_dec:
        params["enc"] = {
            "runs": [_init_run(jax.random.fold_in(ks[3], i), cfg, "attn",
                               cfg.enc_dec.n_enc_layers, False, False,
                               cfg.moe is not None)
                     for i in range(1)],
            "final_norm": jnp.zeros((D,), jnp.float32),
        }
    return params


# ---------------------------------------------------------------------- #
# Train / full-sequence forward
# ---------------------------------------------------------------------- #

def _mlp(p, cfg: ModelConfig, x):
    """The layer's MLP sublayer: the MoE where its params have a router."""
    return (L.moe_fwd if "router" in p else L.mlp_fwd)(p, cfg, x)


def _layer_fwd(p, cfg: ModelConfig, kind: str, x, enc_out, causal: bool):
    if kind == "mla":
        x = x + L.mla_fwd(p["attn"], cfg, L.rmsnorm(x, p["norm1"]))
    elif kind in ("attn", "local"):
        window = cfg.window if kind == "local" else None
        if cfg.parallel_block and enc_out is None and not cfg.moe:
            # parallel residual: both sublayer outputs are partial-sums over
            # the model axis; adding BEFORE the (GSPMD) psum merges two
            # all-reduces into one per direction.
            xn = L.rmsnorm(x, p["norm1"])
            return (x + L.attention_fwd(p["attn"], cfg, xn, causal=causal,
                                        window=window)
                    + L.mlp_fwd(p["mlp"], cfg, L.rmsnorm(x, p["norm2"])))
        x = x + L.attention_fwd(p["attn"], cfg, L.rmsnorm(x, p["norm1"]),
                                causal=causal, window=window)
    elif kind == "rglru":
        y, _ = L.rglru_fwd(p["rec"], cfg, L.rmsnorm(x, p["norm1"]))
        x = x + y
    elif kind == "rwkv6":
        x = x + L.rwkv6_fwd(p["rwkv"], cfg, L.rmsnorm(x, p["norm1"]))
        return x + L.rwkv6_channel_mix(p["rwkv"], cfg, L.rmsnorm(x, p["norm2"]))
    if enc_out is not None:
        x = x + L.attention_fwd(p["xattn"], cfg, L.rmsnorm(x, p["norm_x"]),
                                kv_src=enc_out)
    return x + _mlp(p["mlp"], cfg, L.rmsnorm(x, p["norm2"]))


def _run_fwd(stacked, cfg: ModelConfig, kind: str, x, enc_out, causal: bool):
    body = jax.checkpoint(
        lambda x, p: _layer_fwd(p, cfg, kind, x, enc_out, causal),
        prevent_cse=False)

    def step(x, p):
        return body(x, p), None

    x, _ = lax.scan(step, x, stacked)
    return x


def embed_inputs(params, cfg: ModelConfig, inputs: dict) -> jax.Array:
    """tokens (+ optional modality embeds prefix) -> (B, S, D)."""
    x = params["embed"][inputs["tokens"]] * math.sqrt(cfg.d_model)
    if "embeds" in inputs:  # vision/audio stub: precomputed patch embeds
        x = jnp.concatenate([inputs["embeds"].astype(x.dtype), x], axis=1)
    return x


def encoder_fwd(params, cfg: ModelConfig, src_embeds) -> jax.Array:
    x = src_embeds.astype(jnp.bfloat16)
    for stacked in params["enc"]["runs"]:
        x = _run_fwd(stacked, cfg, "attn", x, None, causal=False)
    return L.rmsnorm(x, params["enc"]["final_norm"])


def trunk_fwd(params, cfg: ModelConfig, inputs: dict) -> jax.Array:
    """Embeddings + layer stack + final norm -> hidden states (B, S, D)."""
    enc_out = None
    if cfg.enc_dec:
        enc_out = encoder_fwd(params, cfg, inputs["src_embeds"])
    x = embed_inputs(params, cfg, inputs)
    for stacked, (kind, _) in zip(params["runs"], cfg.runs()):
        x = _run_fwd(stacked, cfg, kind, x, enc_out, causal=True)
    return L.rmsnorm(x, params["final_norm"])


def _head(params, cfg: ModelConfig):
    return params["embed"].T if cfg.tie_embeddings else params["lm_head"]


def model_fwd(params, cfg: ModelConfig, inputs: dict) -> jax.Array:
    """Full-sequence forward -> logits (B, S, V)."""
    x = trunk_fwd(params, cfg, inputs)
    head = _head(params, cfg)
    return (x @ head.astype(x.dtype)).astype(jnp.float32)


def _ce_chunk(x, labels, head):
    """Cross-entropy partial sums for one sequence chunk."""
    logits = (x @ head.astype(x.dtype)).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    return ((lse - gold) * mask).sum(), mask.sum()


def loss_fn(params, cfg: ModelConfig, batch: dict,
            ce_chunk: int = 512) -> jax.Array:
    """Mean next-token cross-entropy over the *local* batch shard.

    The unembedding + softmax is scanned over sequence chunks with remat so
    the (B, S, V) logits tensor is never materialised — at vocab 256k and
    S=4k that buffer alone would exceed HBM."""
    x = trunk_fwd(params, cfg, batch)
    labels = batch["labels"]
    if "embeds" in batch:  # loss only over the token positions
        x = x[:, batch["embeds"].shape[1]:]
    head = _head(params, cfg)
    B, S, D = x.shape
    if S % ce_chunk or S <= ce_chunk:
        nll, cnt = _ce_chunk(x, labels, head)
        return nll / jnp.maximum(cnt, 1.0)
    n = S // ce_chunk
    xc = x.reshape(B, n, ce_chunk, D).swapaxes(0, 1)
    lc = labels.reshape(B, n, ce_chunk).swapaxes(0, 1)
    body = jax.checkpoint(_ce_chunk, prevent_cse=False)

    def step(carry, xl):
        nll, cnt = carry
        dn, dc = body(xl[0], xl[1], head)
        return (nll + dn, cnt + dc), None

    (nll, cnt), _ = lax.scan(step, (jnp.float32(0.0), jnp.float32(0.0)),
                             (xc, lc))
    return nll / jnp.maximum(cnt, 1.0)


# ---------------------------------------------------------------------- #
# Serving: cache init / prefill / decode
# ---------------------------------------------------------------------- #

def init_cache(cfg: ModelConfig, B: int, s_max: int, src_len: int = 0) -> list:
    """One cache entry per run, stacked on the run dim."""
    cache = []
    hd, Hkv = cfg.head_dim, cfg.n_kv_heads
    R = cfg.d_rnn or cfg.d_model
    H6 = cfg.d_model // cfg.rwkv_head_dim
    for kind, n in cfg.runs():
        if kind in ("attn", "local"):
            windowed = kind == "local"
            s_c = min(cfg.window, s_max) if windowed else s_max
            ent = {"k": jnp.zeros((n, B, s_c, Hkv, hd), jnp.bfloat16),
                   "v": jnp.zeros((n, B, s_c, Hkv, hd), jnp.bfloat16)}
            if cfg.enc_dec:
                ent["xk"] = jnp.zeros((n, B, src_len, Hkv, hd), jnp.bfloat16)
                ent["xv"] = jnp.zeros((n, B, src_len, Hkv, hd), jnp.bfloat16)
            cache.append(ent)
        elif kind == "mla":
            dt = jnp.dtype(cfg.dtype)
            cache.append({
                "c": jnp.zeros((n, B, s_max, cfg.mla.kv_lora_rank), dt),
                "pe": jnp.zeros((n, B, s_max, cfg.mla.qk_rope_dim), dt)})
        elif kind == "rglru":
            cache.append({"h": jnp.zeros((n, B, R), jnp.float32),
                          "conv": jnp.zeros((n, B, 3, R), jnp.bfloat16)})
        elif kind == "rwkv6":
            hd6 = cfg.rwkv_head_dim
            cache.append({"S": jnp.zeros((n, B, H6, hd6, hd6), jnp.float32),
                          "x_tm": jnp.zeros((n, B, cfg.d_model), jnp.bfloat16),
                          "x_cm": jnp.zeros((n, B, cfg.d_model), jnp.bfloat16)})
    return cache


def _layer_prefill(p, cfg, kind, x, enc_out, keep_full=False):
    """Returns (x_out, cache_entry) for one layer."""
    if kind == "mla":
        y, c, k_pe = L.mla_prefill(p["attn"], cfg, L.rmsnorm(x, p["norm1"]))
        x = x + y
        x = x + _mlp(p["mlp"], cfg, L.rmsnorm(x, p["norm2"]))
        return x, {"c": c, "pe": k_pe}
    if kind in ("attn", "local"):
        window = cfg.window if kind == "local" else None
        y, ck, cv = L.attention_prefill(p["attn"], cfg,
                                        L.rmsnorm(x, p["norm1"]), window=window,
                                        keep_full=keep_full)
        x = x + y
        ent = {"k": ck, "v": cv}
        if enc_out is not None:
            ent["xk"], ent["xv"] = L.cross_kv(p["xattn"], cfg, enc_out)
            x = x + L.attention_fwd(p["xattn"], cfg, L.rmsnorm(x, p["norm_x"]),
                                    kv_src=enc_out)
        x = x + _mlp(p["mlp"], cfg, L.rmsnorm(x, p["norm2"]))
        return x, ent
    if kind == "rglru":
        y, h, conv = L.rglru_prefill(p["rec"], cfg, L.rmsnorm(x, p["norm1"]))
        x = x + y
        x = x + _mlp(p["mlp"], cfg, L.rmsnorm(x, p["norm2"]))
        return x, {"h": h, "conv": conv.astype(jnp.bfloat16)}
    if kind == "rwkv6":
        xn = L.rmsnorm(x, p["norm1"])
        y, st = L.rwkv6_fwd(p["rwkv"], cfg, xn, return_state=True)
        x = x + y
        xn2 = L.rmsnorm(x, p["norm2"])
        x = x + L.rwkv6_channel_mix(p["rwkv"], cfg, xn2)
        return x, {"S": st["S"], "x_tm": xn[:, -1].astype(jnp.bfloat16),
                   "x_cm": xn2[:, -1].astype(jnp.bfloat16)}
    raise ValueError(kind)


def prefill(params, cfg: ModelConfig, inputs: dict, s_max: int, *,
            last_pos=None, full_local_cache: bool = False):
    """Process the prompt; return (last-token logits, cache, pos).

    ``last_pos`` ((B,) int32) selects each row's last *real* token for the
    logits instead of column -1 — right-padded variable-length prompts are
    then safe: causality keeps pad tokens out of the real positions' scores,
    and decode overwrites/masks the pad cache entries.  ``full_local_cache``
    keeps windowed layers' caches unwrapped at full length (paged serving
    stores them that way and masks at read time)."""
    enc_out = None
    src_len = 0
    if cfg.enc_dec:
        enc_out = encoder_fwd(params, cfg, inputs["src_embeds"])
        src_len = enc_out.shape[1]
    x = embed_inputs(params, cfg, inputs)
    S = x.shape[1]
    cache = []
    for stacked, (kind, n) in zip(params["runs"], cfg.runs()):
        body = jax.checkpoint(functools.partial(
            _layer_prefill, cfg=cfg, kind=kind, enc_out=enc_out,
            keep_full=full_local_cache),
        prevent_cse=False)

        def step(x, p, body=body):
            x, ent = body(p, x=x)
            return x, ent

        x, ents = lax.scan(step, x, stacked)
        # Pad attention caches out to s_max so decode can update in place.
        if kind in ("attn", "local"):
            s_c = ents["k"].shape[2]
            tgt = s_max if full_local_cache or kind != "local" \
                else min(cfg.window, s_max)
            if s_c < tgt:
                pad = [(0, 0), (0, 0), (0, tgt - s_c), (0, 0), (0, 0)]
                ents["k"] = jnp.pad(ents["k"], pad)
                ents["v"] = jnp.pad(ents["v"], pad)
        elif kind == "mla" and S < s_max:
            ents = {k: jnp.pad(v, [(0, 0), (0, 0), (0, s_max - S), (0, 0)])
                    for k, v in ents.items()}
        cache.append(ents)
    x = L.rmsnorm(x, params["final_norm"])
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    if last_pos is None:
        xl = x[:, -1:]
    else:
        xl = x[jnp.arange(x.shape[0]), last_pos][:, None]
    logits = (xl @ head.astype(x.dtype)).astype(jnp.float32)
    return logits, cache, S


def _layer_decode(p, cfg, kind, x, ent, pos):
    if kind == "mla":
        y, c, k_pe = L.mla_decode(p["attn"], cfg, L.rmsnorm(x, p["norm1"]),
                                  ent["c"], ent["pe"], pos)
        x = x + y
        x = x + _mlp(p["mlp"], cfg, L.rmsnorm(x, p["norm2"]))
        return x, {"c": c, "pe": k_pe}
    if kind in ("attn", "local"):
        y, ck, cv = L.attention_decode(p["attn"], cfg, L.rmsnorm(x, p["norm1"]),
                                       ent["k"], ent["v"], pos,
                                       windowed=(kind == "local"))
        x = x + y
        ent = dict(ent, k=ck, v=cv)
        if "xk" in ent:
            x = x + L.cross_attention_decode(p["xattn"], cfg,
                                             L.rmsnorm(x, p["norm_x"]),
                                             ent["xk"], ent["xv"])
        x = x + _mlp(p["mlp"], cfg, L.rmsnorm(x, p["norm2"]))
        return x, ent
    if kind == "rglru":
        y, h, conv = L.rglru_decode(p["rec"], cfg, L.rmsnorm(x, p["norm1"]),
                                    ent["h"], ent["conv"].astype(jnp.bfloat16))
        x = x + y
        x = x + _mlp(p["mlp"], cfg, L.rmsnorm(x, p["norm2"]))
        return x, {"h": h, "conv": conv.astype(jnp.bfloat16)}
    if kind == "rwkv6":
        xn = L.rmsnorm(x, p["norm1"])
        st = {"S": ent["S"], "x_tm": ent["x_tm"].astype(xn.dtype)}
        y, st = L.rwkv6_decode(p["rwkv"], cfg, xn, st)
        x = x + y
        xn2 = L.rmsnorm(x, p["norm2"])
        y2, x_cm = L.rwkv6_channel_mix_decode(p["rwkv"], cfg, xn2,
                                              ent["x_cm"].astype(xn2.dtype))
        x = x + y2
        return x, {"S": st["S"], "x_tm": st["x_tm"].astype(jnp.bfloat16),
                   "x_cm": x_cm.astype(jnp.bfloat16)}
    raise ValueError(kind)


def decode_step(params, cfg: ModelConfig, cache: list, tokens, pos):
    """One-token serve step.  tokens: (B,1) int32; pos: scalar int32.
    Returns (logits (B,1,V), new_cache)."""
    x = params["embed"][tokens] * math.sqrt(cfg.d_model)
    new_cache = []
    for stacked, ent, (kind, n) in zip(params["runs"], cache, cfg.runs()):
        def step(x, p_ent, kind=kind):
            p, e = p_ent
            x, e2 = _layer_decode(p, cfg, kind, x, e, pos)
            return x, e2

        x, ent2 = lax.scan(step, x, (stacked, ent))
        new_cache.append(ent2)
    x = L.rmsnorm(x, params["final_norm"])
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = (x @ head.astype(x.dtype)).astype(jnp.float32)
    return logits, new_cache


# ---------------------------------------------------------------------- #
# Paged serving: block-pool cache / per-request-position decode
# ---------------------------------------------------------------------- #

PAGED_KINDS = ("attn", "local", "mla")


def paged_arch_check(cfg: ModelConfig) -> None:
    """Paged serving covers pure-attention stacks (attn/local/mla, no
    enc-dec).

    Recurrent kinds (rglru/rwkv6) carry positionless state that right-padded
    variable-length prefill would corrupt, and enc-dec needs per-request
    encoder outputs — neither fits the shared-pool layout."""
    bad = [k for k, _ in cfg.runs() if k not in PAGED_KINDS]
    if bad or cfg.enc_dec:
        raise ValueError(
            f"paged serving supports attention-only decoder stacks; "
            f"got kinds {bad or ['enc_dec']}")


def init_paged_pools(cfg: ModelConfig, n_blocks: int, block_size: int) -> list:
    """One pool set per run: a k/v pair (run, n_blocks, block_size, Hkv,
    hd) for attn/local runs; for mla runs the latents ``c`` (run, n_blocks,
    block_size, kv_lora_rank) and rope keys ``pe`` (run, n_blocks,
    block_size * qk_rope_dim) in ``cfg.dtype``: kv_lora_rank +
    qk_rope_dim values a token a layer, no head axis (the layout is
    ``layers.paged_mla_decode``'s).

    Physical block 0 is reserved as the null block — allocators must never
    hand it to a request, so inactive batch slots (block table all-zero) can
    scatter into it without touching live data."""
    paged_arch_check(cfg)
    hd, Hkv = cfg.head_dim, cfg.n_kv_heads
    pools = []
    for kind, n in cfg.runs():
        if kind == "mla":
            a, dt = cfg.mla, jnp.dtype(cfg.dtype)
            pools.append({
                "c": jnp.zeros((n, n_blocks, block_size, a.kv_lora_rank), dt),
                "pe": jnp.zeros((n, n_blocks, block_size * a.qk_rope_dim),
                                dt)})
            continue
        shape = (n, n_blocks, block_size, Hkv, hd)
        pools.append({"k": jnp.zeros(shape, jnp.bfloat16),
                      "v": jnp.zeros(shape, jnp.bfloat16)})
    return pools


def scatter_prefill_cache(pools: list, cache: list, blocks, block_size: int,
                          row: int = 0) -> list:
    """Copy one request's dense prefill cache (from ``prefill`` with
    ``full_local_cache=True``) into its allocated physical blocks.

    cache entries: (run, B, S_p, ...) — k and v (..., Hkv, hd), or mla's
    latents c and rope keys pe — with S_p % block_size == 0, each laid into
    its pool's blocks;
    ``blocks``: the request's physical block ids, len == S_p // block_size.
    Returns the updated pools list."""
    blocks = jnp.asarray(blocks, jnp.int32)
    out = []
    for pool, ent in zip(pools, cache):
        new = {}
        for name in pool:
            n, _, S_p = ent[name].shape[:3]
            if S_p % block_size:
                raise ValueError(f"prefill length {S_p} not a multiple of "
                                 f"block_size {block_size}")
            nb = S_p // block_size
            if nb != len(blocks):
                raise ValueError(f"need {nb} blocks, got {len(blocks)}")
            rows = ent[name][:, row].reshape(n, nb, *pool[name].shape[2:])
            new[name] = pool[name].at[:, blocks].set(rows)
        out.append(new)
    return out


def _layer_decode_paged(p, cfg, kind, x, ent, layer, block_tables, pos):
    xn = L.rmsnorm(x, p["norm1"])
    if kind == "mla":
        y, c, k_pe = L.paged_mla_decode(p["attn"], cfg, xn, ent["c"],
                                        ent["pe"], layer, block_tables, pos)
        ent = {"c": c, "pe": k_pe}
    else:
        window = cfg.window if kind == "local" else None
        y, pk, pv = L.paged_attention_decode(
            p["attn"], cfg, xn, ent["k"], ent["v"], layer, block_tables, pos,
            window=window)
        ent = {"k": pk, "v": pv}
    x = x + y
    return x + _mlp(p["mlp"], cfg, L.rmsnorm(x, p["norm2"])), ent


def decode_step_paged(params, cfg: ModelConfig, pools: list, block_tables,
                      tokens, pos):
    """One-token serve step over paged pools.  tokens: (B,1) int32;
    block_tables: (B, max_blocks) int32; pos: (B,) int32 per-slot.
    Returns (logits (B,1,V), new_pools).

    Each run's pools ride the scan's carry, so a layer writes its new rows
    into them and reads its keys and values from them in place; jit the
    step with ``pools`` donated to keep a second set of pools out of it."""
    x = params["embed"][tokens] * math.sqrt(cfg.d_model)
    new_pools = []
    for stacked, ent, (kind, n) in zip(params["runs"], pools, cfg.runs()):
        def step(carry, p_layer, kind=kind):
            x, e = carry
            p, layer = p_layer
            return _layer_decode_paged(p, cfg, kind, x, e, layer,
                                       block_tables, pos), None

        (x, ent2), _ = lax.scan(step, (x, ent), (stacked, jnp.arange(n)))
        new_pools.append(ent2)
    x = L.rmsnorm(x, params["final_norm"])
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = (x @ head.astype(x.dtype)).astype(jnp.float32)
    return logits, new_pools
