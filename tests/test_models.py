"""Per-architecture smoke tests + decode/prefill equivalence."""
import dataclasses
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, list_archs
from repro.models import layers as L
from repro.models import transformer as T

KEY = jax.random.PRNGKey(0)


def _batch(cfg, B=2, S=16):
    if cfg.enc_dec:
        return {"src_embeds": jax.random.normal(KEY, (B, 8, cfg.d_model),
                                                jnp.bfloat16),
                "tokens": jnp.ones((B, S), jnp.int32),
                "labels": jnp.ones((B, S), jnp.int32)}
    if cfg.frontend == "vision":
        return {"embeds": jax.random.normal(KEY, (B, 4, cfg.d_model),
                                            jnp.bfloat16),
                "tokens": jnp.ones((B, S - 4), jnp.int32),
                "labels": jnp.ones((B, S - 4), jnp.int32)}
    return {"tokens": jnp.ones((B, S), jnp.int32),
            "labels": jnp.ones((B, S), jnp.int32)}


@pytest.mark.parametrize("arch", list_archs())
def test_smoke_forward_and_train_step(arch):
    """Reduced config: one forward + one SGD-style grad step, no NaNs."""
    cfg = get_config(arch, smoke=True)
    params = T.init_model(KEY, cfg)
    batch = _batch(cfg)
    logits = T.model_fwd(params, cfg, batch)
    S_tok = batch["tokens"].shape[1]
    n_prefix = 0 if cfg.enc_dec else (4 if cfg.frontend == "vision" else 0)
    assert logits.shape == (2, S_tok + n_prefix, cfg.vocab)
    assert bool(jnp.isfinite(logits).all())

    loss, grads = jax.value_and_grad(
        lambda p: T.loss_fn(p, cfg, batch))(params)
    assert np.isfinite(float(loss))
    gn = sum(float(jnp.abs(g.astype(jnp.float32)).sum())
             for g in jax.tree.leaves(grads))
    assert np.isfinite(gn) and gn > 0


@pytest.mark.parametrize("arch", [
    "qwen3_4b",            # GQA + qk_norm
    "gemma3_12b",          # 5:1 local:global windows
    "olmoe_1b_7b",         # MoE top-8
    "recurrentgemma_2b",   # RG-LRU + local attn
    "rwkv6_1p6b",          # attention-free
    "seamless_m4t_medium", # enc-dec cross-attention
    "pixtral_12b",         # vision prefix
])
def test_prefill_decode_matches_full_forward(arch):
    """prefill(S) + decode(1) must equal the full forward over S+1 tokens."""
    cfg = get_config(arch, smoke=True)
    params = T.init_model(KEY, cfg)
    B, S = 2, 8
    toks = jax.random.randint(KEY, (B, S + 1), 0, cfg.vocab)
    inp_full = {"tokens": toks}
    inp_pre = {"tokens": toks[:, :S]}
    prefix = 0
    if cfg.enc_dec:
        src = jax.random.normal(KEY, (B, 4, cfg.d_model), jnp.bfloat16)
        inp_full["src_embeds"] = inp_pre["src_embeds"] = src
    if cfg.frontend == "vision":
        emb = jax.random.normal(KEY, (B, 4, cfg.d_model), jnp.bfloat16)
        inp_full["embeds"] = inp_pre["embeds"] = emb
        prefix = 4
    full = T.model_fwd(params, cfg, inp_full)
    logits_p, cache, pos = T.prefill(params, cfg, inp_pre, s_max=S + prefix + 4)
    logits_d, _ = T.decode_step(params, cfg, cache, toks[:, S:S + 1],
                                jnp.int32(pos))
    np.testing.assert_allclose(np.asarray(logits_p[:, 0]),
                               np.asarray(full[:, prefix + S - 1]),
                               atol=0.08, rtol=0.05)
    np.testing.assert_allclose(np.asarray(logits_d[:, 0]),
                               np.asarray(full[:, prefix + S]),
                               atol=0.08, rtol=0.05)


def test_sliding_window_cache_wraps():
    """Decode past the window: entries must wrap and old keys be masked."""
    cfg = get_config("gemma3_12b", smoke=True)  # window=8 after shrink
    params = T.init_model(KEY, cfg)
    B, S = 1, 8
    toks = jax.random.randint(KEY, (B, S + 4), 0, cfg.vocab)
    full = T.model_fwd(params, cfg, {"tokens": toks})
    _, cache, pos = T.prefill(params, cfg, {"tokens": toks[:, :S]},
                              s_max=S + 8)
    p = jnp.int32(pos)
    for i in range(4):  # decode 4 tokens past the window boundary
        logits_d, cache = T.decode_step(params, cfg, cache,
                                        toks[:, S + i:S + i + 1], p + i)
        np.testing.assert_allclose(np.asarray(logits_d[:, 0]),
                                   np.asarray(full[:, S + i]),
                                   atol=0.1, rtol=0.05)


def test_param_count_sane():
    """Full-config param counts in the right ballpark for the known models."""
    expect = {"tinyllama_1p1b": (0.9e9, 1.4e9),
              "qwen3_4b": (3e9, 5e9),
              "gemma3_12b": (9e9, 14e9),
              "olmoe_1b_7b": (5e9, 8.5e9),
              "rwkv6_1p6b": (1.2e9, 2.2e9)}
    for arch, (lo, hi) in expect.items():
        n = get_config(arch).param_count()
        assert lo < n < hi, f"{arch}: {n/1e9:.2f}B outside [{lo/1e9},{hi/1e9}]"


def test_moe_active_params_less_than_total():
    cfg = get_config("olmoe_1b_7b")
    assert cfg.active_param_count() < cfg.param_count() * 0.4


# ---------------------------------------------------------------------- #
# DeepSeek-V2: latent attention, YaRN, held expert shares
# ---------------------------------------------------------------------- #
#
# The program runs these in float32 (params cast, cfg.dtype float32), so
# that it and the float32 reference (bench/configs/mla_ref.py) differ only
# by the order of float32 sums: observed 6e-7 on logits of size ~2 and
# 3e-8 on the MoE layer; the rotation reads 5e-4 at positions up to 8191,
# where the program's float32 frequencies and the reference's float64 ones
# rounded to float32 differ by an ulp.  TOL sits above those and below
# what the same computation in bfloat16 reads (observed 0.010, 0.0024 and
# 0.028), and each test checks that the bfloat16 version fails it.

TOL = 1e-3
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def mla_ref():
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from bench import harness
    return harness.reference_module({"reference": "mla_ref"})


def _mla_cfg(held=None, first=0, shared=True):
    cfg = get_config("deepseek_v2_lite_16b", smoke=True)
    moe = dataclasses.replace(cfg.moe, n_held=held, first_held=first,
                              shared_expert=shared)
    return dataclasses.replace(cfg, moe=moe, dtype="float32")


def _ref_dict(cfg):
    """The reference's configuration keys for a program config."""
    a, y, m = cfg.mla, cfg.yarn, cfg.moe
    return dict(
        hidden_size=cfg.d_model, num_attention_heads=cfg.n_heads,
        kv_lora_rank=a.kv_lora_rank, qk_nope_head_dim=a.qk_nope_dim,
        qk_rope_head_dim=a.qk_rope_dim, v_head_dim=a.v_head_dim,
        rope_theta=cfg.rope_theta, rms_norm_eps=cfg.norm_eps,
        num_experts_per_tok=m.top_k, norm_topk_prob=m.norm_topk_prob,
        routed_scaling_factor=1.0,
        held_experts_first=m.first_held,
        tie_word_embeddings=cfg.tie_embeddings,
        rope_scaling=dict(type="yarn", factor=y.factor,
                          original_max_position_embeddings=(
                              y.original_max_position),
                          beta_fast=y.beta_fast, beta_slow=y.beta_slow,
                          mscale=y.mscale, mscale_all_dim=y.mscale_all_dim))


def _f32(tree):
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)


def _ref_weights(cfg, params):
    """(top leaves, one tree per layer) of the program's params."""
    top = {k: params[k] for k in ("embed", "final_norm", "lm_head")
           if k in params}
    layers = [jax.tree.map(lambda a, i=i: a[i], run)
              for run, (_, n) in zip(params["runs"], cfg.runs())
              for i in range(n)]
    return top, layers


def _paged_logits(cfg, params, toks, S, BS):
    """Prefill S tokens, scatter them into paged pools, then decode the
    rest teacher-forced through the absorbed paged step."""
    n_new = toks.shape[1] - S
    max_blocks = -(-(S + n_new) // BS)
    pools = T.init_paged_pools(cfg, 1 + max_blocks, BS)
    lp, cache, _ = T.prefill(params, cfg, {"tokens": toks[:, :S]}, S,
                             full_local_cache=True)
    pools = T.scatter_prefill_cache(pools, cache, list(range(1, S // BS + 1)),
                                    BS)
    table = jnp.arange(1, max_blocks + 1, dtype=jnp.int32)[None]
    out = [lp[0, 0]]
    for i in range(n_new - 1):
        lg, pools = T.decode_step_paged(params, cfg, pools, table,
                                        toks[:, S + i:S + i + 1],
                                        jnp.asarray([S + i], jnp.int32))
        out.append(lg[0, 0])
    return np.stack([np.asarray(x) for x in out])


@pytest.mark.parametrize("held, first", [(None, 0), (2, 2)],
                         ids=["all-experts", "held-2-of-8"])
def test_mla_prefill_then_absorbed_paged_decode_match_reference(
        mla_ref, held, first):
    """Prefill, then decode through the latent pools with the absorbed
    query, gives the reference's full decompressed forward's logits at
    every position, across block boundaries, with all experts held and
    with a share of them."""
    cfg = _mla_cfg(held, first)
    params = _f32(T.init_model(KEY, cfg))
    S, n_new, BS = 8, 7, 4
    toks = jax.random.randint(KEY, (1, S + n_new), 0, cfg.vocab)
    got = _paged_logits(cfg, params, toks, S, BS)
    top, layers = _ref_weights(cfg, params)
    padded = jnp.zeros(mla_ref.padded_len(S + n_new), jnp.int32)
    padded = padded.at[:S + n_new].set(toks[0])
    c = _ref_dict(cfg)
    want = np.asarray(mla_ref.forward(c, top, layers, padded))
    want = want[S - 1:S + n_new - 1]
    np.testing.assert_allclose(got, want, rtol=0, atol=TOL)
    low = np.asarray(mla_ref.forward(c, top, layers, padded, "bf16"))
    assert np.abs(low[S - 1:S + n_new - 1] - want).max() > TOL


def test_moe_held_shares_add_up_to_the_uncut_layer(mla_ref):
    """Four shares of 2 of the 8 experts, each routing over all 8 and
    adding only its own experts' part, plus the shared experts counted
    once, give the reference's whole MoE layer."""
    cfg = _mla_cfg()
    p = jax.tree.map(lambda a: a[0], _f32(T.init_model(KEY, cfg))["runs"][1])
    p = p["mlp"]
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 16, cfg.d_model))
    total = L.mlp_fwd(p["shared"], cfg, x)
    for first in range(0, cfg.moe.n_experts, 2):
        share = _mla_cfg(2, first, shared=False)
        ps = {"router": p["router"],
              **{k: p[k][first:first + 2]
                 for k in ("w_in", "w_gate", "w_out")}}
        total = total + L.moe_fwd(ps, share, x)
    c = _ref_dict(cfg)
    want = np.asarray(mla_ref.moe(c, p, x[0], None))
    np.testing.assert_allclose(np.asarray(total[0]), want, rtol=0, atol=TOL)
    low = np.asarray(mla_ref.moe(c, p, x[0], "bf16"))
    assert np.abs(low - want).max() > TOL


def test_yarn_rope_and_mscale_match_reference(mla_ref):
    """At DeepSeek-V2-Lite's rope widths: YaRN's frequencies are the
    installed transformers port's and the reference's, rotating pairs
    agrees with the reference at positions 0..S (past the 4096 original
    positions), and the softmax scale carries mscale(40, 0.707)^2."""
    cfg = get_config("deepseek_v2_lite_16b")
    c = _ref_dict(cfg)
    freq = L.mla_rope_freq(cfg)
    np.testing.assert_allclose(freq, mla_ref.rope_freq(c), rtol=1e-6)
    import types

    from transformers.modeling_rope_utils import _compute_yarn_parameters
    hf = types.SimpleNamespace(
        rope_theta=10000.0, head_dim=64, hidden_size=2048,
        num_attention_heads=16, max_position_embeddings=163840,
        rope_scaling=c["rope_scaling"])
    inv, attention_factor = _compute_yarn_parameters(hf, "cpu")
    np.testing.assert_allclose(freq, inv.numpy(), rtol=1e-6)
    assert attention_factor == 1.0
    S = 8192
    x = jax.random.normal(KEY, (S, 2, 64))
    pos = jnp.arange(S)
    want = np.asarray(mla_ref.rope(x, pos, c))
    np.testing.assert_allclose(np.asarray(L.rope_pairs(x, pos, freq)), want,
                               rtol=0, atol=TOL)
    low = L.rope_pairs(x.astype(jnp.bfloat16), pos, freq)
    assert np.abs(np.asarray(low, np.float32) - want).max() > TOL
    m = 0.1 * 0.707 * math.log(40) + 1
    assert L.mla_softmax_scale(cfg) == pytest.approx(192 ** -0.5 * m * m,
                                                     rel=1e-12)
    assert L.mla_softmax_scale(cfg) == pytest.approx(
        mla_ref.softmax_scale(c), rel=1e-12)


def test_llama4_shared_expert_keeps_the_dense_width():
    """A shared expert with no width of its own is as wide as d_ff, as
    llama4 configures it: widths and parameter counts as before."""
    for smoke, n in ((False, 106735375360), (True, 271936)):
        cfg = get_config("llama4_scout_17b_a16e", smoke=smoke)
        assert cfg.moe.d_ff_shared is None
        shapes = jax.eval_shape(lambda: T.init_model(KEY, cfg))
        shared = shapes["runs"][0]["mlp"]["shared"]
        assert shared["wi"].shape[-1] == cfg.d_ff
        assert cfg.param_count() == n
        assert sum(x.size for x in jax.tree.leaves(shapes)) == n
