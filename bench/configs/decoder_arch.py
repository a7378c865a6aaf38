"""Architecture of a decoder-only stack in which every layer is full causal
attention followed by one SwiGLU MLP (``num_experts`` absent) or one top-k
MoE whose experts are as wide as ``intermediate_size``.  A configuration
file names it with ``"arch": "decoder_arch"``.

An arch module is what ``bench/harness.py``, ``bench/weights.py`` and
``bench/flops.py`` know of a configuration beyond its top-level widths
(``hidden_size``, ``vocab_size``, ``tie_word_embeddings``, the attention
heads).  It provides:

- ``model_config(c)``: the program's ``ModelConfig``; the only function
  that imports the program, and only when called;
- ``layer_specs(c, layer)``: ``{path: (shape, dtype, scale)}`` of one
  layer's leaves, in the layout the program's ``init_model`` gives that
  layer;
- ``residual_writers(c, layer)``: the paths of that layer whose output
  columns are added to the residual stream;
- ``routing(c)``: ``(router path, E, k)`` when tokens are routed to the
  top k of E experts (E the router's width), else None;
- ``layer_work(c, layer, S)``: (weights one token multiplies by in that
  layer, query-key pairs one sequence of length S attends there).
"""
from __future__ import annotations

from bench.flops import causal_pairs
from bench.weights import BF16, F32, GAIN, NORM_SCALE


def model_config(c: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.models.config import ModelConfig, MoECfg

    L = int(c["num_hidden_layers"])
    moe = None
    if c.get("num_experts"):
        moe = MoECfg(n_experts=int(c["num_experts"]),
                     top_k=int(c["num_experts_per_tok"]),
                     d_ff_expert=int(c["intermediate_size"]))
    return ModelConfig(
        name=c["name"], n_layers=L, d_model=int(c["hidden_size"]),
        n_heads=int(c["num_attention_heads"]),
        n_kv_heads=int(c["num_key_value_heads"]),
        head_dim=int(c["head_dim"]), d_ff=int(c["intermediate_size"]),
        vocab=int(c["vocab_size"]), pattern=("attn",) * L,
        rope_theta=float(c["rope_theta"]),
        tie_embeddings=bool(c["tie_word_embeddings"]), moe=moe,
        norm_eps=float(c["rms_norm_eps"]),
        family="moe" if moe else "dense")


def layer_specs(c: dict, layer: int) -> dict:
    """path -> (shape of one layer, dtype, scale); every layer alike."""
    D, hd = c["hidden_size"], c["head_dim"]
    Q, KV = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    F = c["intermediate_size"]
    s = {("norm1",): ((D,), F32, NORM_SCALE),
         ("norm2",): ((D,), F32, NORM_SCALE),
         ("attn", "wq"): ((D, Q), BF16, GAIN * D ** -0.5),
         ("attn", "wk"): ((D, KV), BF16, GAIN * D ** -0.5),
         ("attn", "wv"): ((D, KV), BF16, GAIN * D ** -0.5),
         ("attn", "wo"): ((Q, D), BF16, GAIN * Q ** -0.5)}
    if c.get("num_experts"):
        E = c["num_experts"]
        s |= {("mlp", "router"): ((D, E), F32, D ** -0.5),
              ("mlp", "w_in"): ((E, D, F), BF16, GAIN * D ** -0.5),
              ("mlp", "w_gate"): ((E, D, F), BF16, GAIN * D ** -0.5),
              ("mlp", "w_out"): ((E, F, D), BF16, GAIN * F ** -0.5)}
    else:
        s |= {("mlp", "wi"): ((D, F), BF16, GAIN * D ** -0.5),
              ("mlp", "wg"): ((D, F), BF16, GAIN * D ** -0.5),
              ("mlp", "wo"): ((F, D), BF16, GAIN * F ** -0.5)}
    return s


def residual_writers(c: dict, layer: int) -> tuple:
    return (("attn", "wo"),
            ("mlp", "w_out") if c.get("num_experts") else ("mlp", "wo"))


def routing(c: dict):
    if not c.get("num_experts"):
        return None
    return ("mlp", "router"), c["num_experts"], c["num_experts_per_tok"]


def layer_work(c: dict, layer: int, S: int) -> tuple[int, int]:
    """(weights one token multiplies by, active experts only; causal
    pairs of a sequence of length S)."""
    D, hd, F = c["hidden_size"], c["head_dim"], c["intermediate_size"]
    H, Hkv = c["num_attention_heads"], c["num_key_value_heads"]
    attn = D * H * hd + 2 * D * Hkv * hd + H * hd * D
    if c.get("num_experts"):
        mlp = c["num_experts_per_tok"] * 3 * D * F + D * c["num_experts"]
    else:
        mlp = 3 * D * F
    return attn + mlp, causal_pairs(S)
