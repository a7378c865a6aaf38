"""Architecture of a DeepSeek-V2 stack: multi-head latent attention (no
query LoRA) in every layer, a dense SwiGLU MLP in the first
``first_k_dense_replace`` layers and a top-k MoE with shared experts in
the rest.  A configuration file names it with ``"arch": "mla_arch"``; the
contract is ``bench/configs/decoder_arch.py``'s.

The MoE may be this chip's share of an expert-parallel deployment:
``n_routed_experts`` experts held here, from id ``held_experts_first``,
while the router chooses among the published count
(``published["n_routed_experts"]`` where the file cuts it).

Beside the contract it counts what this stack's own readers price:
:func:`prefill_flops`, :func:`flash_fwd` (the prefill's flash kernel at
MLA's widths) and :func:`decode_weight_bytes`.
"""
from __future__ import annotations

import math

import numpy as np

from bench.flops import causal_pairs
from bench.weights import BF16, F32, GAIN, NORM_SCALE

# what the program runs and this module counts; a file asking for more is
# refused rather than run as something else
EXPRESSED = {"q_lora_rank": None, "moe_layer_freq": 1,
             "scoring_func": "softmax", "topk_method": "greedy",
             "n_group": 1, "topk_group": 1, "hidden_act": "silu",
             "routed_scaling_factor": 1}


def _router_experts(c: dict) -> int:
    """How many experts the router chooses among."""
    return int(c.get("published", {}).get("n_routed_experts",
                                          c["n_routed_experts"]))


def _dims(c: dict):
    return (c["hidden_size"], c["num_attention_heads"], c["kv_lora_rank"],
            c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"])


def _is_moe(c: dict, layer: int) -> bool:
    return layer >= c["first_k_dense_replace"]


def model_config(c: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.models.config import MLACfg, ModelConfig, MoECfg, YarnCfg

    for key, want in EXPRESSED.items():
        if c.get(key, want) != want:
            raise ValueError(f"{c['name']}: the program runs {key}={want!r}, "
                             f"the file asks for {c[key]!r}")
    D, H, r, dn, dr, dv = _dims(c)
    L, E, held = (c["num_hidden_layers"], _router_experts(c),
                  c["n_routed_experts"])
    Fe = c["moe_intermediate_size"]
    moe = MoECfg(n_experts=E, top_k=c["num_experts_per_tok"],
                 d_ff_expert=Fe, shared_expert=c["n_shared_experts"] > 0,
                 d_ff_shared=c["n_shared_experts"] * Fe or None,
                 norm_topk_prob=bool(c["norm_topk_prob"]),
                 n_held=None if held == E else held,
                 first_held=int(c.get("held_experts_first", 0)))
    yarn, rs = None, c.get("rope_scaling")
    if rs:
        if rs["type"] != "yarn":
            raise ValueError(f"{c['name']}: rope scaling {rs['type']!r}")
        yarn = YarnCfg(factor=float(rs["factor"]),
                       original_max_position=int(
                           rs["original_max_position_embeddings"]),
                       beta_fast=float(rs["beta_fast"]),
                       beta_slow=float(rs["beta_slow"]),
                       mscale=float(rs["mscale"]),
                       mscale_all_dim=float(rs["mscale_all_dim"]))
    return ModelConfig(
        name=c["name"], n_layers=L, d_model=D, n_heads=H,
        n_kv_heads=c["num_key_value_heads"], head_dim=dn + dr,
        d_ff=c["intermediate_size"], vocab=c["vocab_size"],
        pattern=("mla",) * L, rope_theta=float(c["rope_theta"]),
        tie_embeddings=bool(c["tie_word_embeddings"]), moe=moe,
        mla=MLACfg(kv_lora_rank=r, qk_nope_dim=dn, qk_rope_dim=dr,
                   v_head_dim=dv),
        yarn=yarn, first_k_dense=c["first_k_dense_replace"],
        norm_eps=float(c["rms_norm_eps"]), family="moe")


def _score_temperature(c: dict) -> float:
    """YaRN's mscale(factor, mscale_all_dim)^2, by which the softmax scale
    exceeds (qk_nope + qk_rope)^-0.5 (1.2608^2 for DeepSeek-V2)."""
    rs = c.get("rope_scaling") or {}
    if not rs.get("mscale_all_dim") or rs["factor"] <= 1:
        return 1.0
    return (0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0) ** 2


def _attn_specs(c: dict) -> dict:
    """The query is drawn at GAIN / mscale^2, so that the scaled scores
    spread as a plain-scaled head's do at GAIN (std 3, as in the dense
    stack): trained weights learn alongside YaRN's temperature, random ones
    do not.  At GAIN the scores spread 1.59 times wider and attention so
    sharp that bfloat16 rounding alone (the reference rounded to bfloat16)
    leaves 53% of the residual stream off the float32 one after 27
    layers; at GAIN / mscale^2, 15% (1024 tokens, 2 held experts, CPU)."""
    D, H, r, dn, dr, dv = _dims(c)
    q_gain = GAIN / _score_temperature(c)
    return {("attn", "wq"): ((D, H * (dn + dr)), BF16, q_gain * D ** -0.5),
            ("attn", "wkv_a"): ((D, r + dr), BF16, GAIN * D ** -0.5),
            ("attn", "kv_norm"): ((r,), F32, NORM_SCALE),
            ("attn", "wkv_b"): ((r, H * (dn + dv)), BF16, GAIN * r ** -0.5),
            ("attn", "wo"): ((H * dv, D), BF16, GAIN * (H * dv) ** -0.5)}


def _swiglu_specs(prefix: tuple, D: int, F: int) -> dict:
    return {prefix + ("wi",): ((D, F), BF16, GAIN * D ** -0.5),
            prefix + ("wg",): ((D, F), BF16, GAIN * D ** -0.5),
            prefix + ("wo",): ((F, D), BF16, GAIN * F ** -0.5)}


def layer_specs(c: dict, layer: int) -> dict:
    """path -> (shape of one layer, dtype, scale)."""
    D = c["hidden_size"]
    s = {("norm1",): ((D,), F32, NORM_SCALE),
         ("norm2",): ((D,), F32, NORM_SCALE), **_attn_specs(c)}
    if not _is_moe(c, layer):
        return s | _swiglu_specs(("mlp",), D, c["intermediate_size"])
    E, held, F = (_router_experts(c), c["n_routed_experts"],
                  c["moe_intermediate_size"])
    s |= {("mlp", "router"): ((D, E), F32, D ** -0.5),
          ("mlp", "w_in"): ((held, D, F), BF16, GAIN * D ** -0.5),
          ("mlp", "w_gate"): ((held, D, F), BF16, GAIN * D ** -0.5),
          ("mlp", "w_out"): ((held, F, D), BF16, GAIN * F ** -0.5)}
    if c["n_shared_experts"]:
        s |= _swiglu_specs(("mlp", "shared"), D, c["n_shared_experts"] * F)
    return s


def residual_writers(c: dict, layer: int) -> tuple:
    if not _is_moe(c, layer):
        return (("attn", "wo"), ("mlp", "wo"))
    return (("attn", "wo"), ("mlp", "w_out")) + (
        (("mlp", "shared", "wo"),) if c["n_shared_experts"] else ())


def routing(c: dict):
    return ("mlp", "router"), _router_experts(c), c["num_experts_per_tok"]


def _expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def _k_held(c: dict) -> int:
    """Top k times the experts held: over the router's width E, the
    experts one token multiplies by here, in expectation."""
    return c["num_experts_per_tok"] * c["n_routed_experts"]


def layer_work(c: dict, layer: int, S: int) -> tuple[int, int]:
    """(weights one token multiplies by in the layer: every attention
    projection, k_nope and v decompressed from the latent, and the MLP, or
    of the MoE the router, the shared experts and the held share's part of
    the top k; causal pairs of a sequence of length S)."""
    D, H, r, dn, dr, dv = _dims(c)
    attn = D * H * (dn + dr) + D * (r + dr) + r * H * (dn + dv) + H * dv * D
    if not _is_moe(c, layer):
        return attn + 3 * D * c["intermediate_size"], causal_pairs(S)
    E = _router_experts(c)
    mlp = (_k_held(c) * _expert_params(c) // E + D * E
           + 3 * D * c["n_shared_experts"] * c["moe_intermediate_size"])
    return attn + mlp, causal_pairs(S)


def attention_flops(c: dict, pairs: int) -> float:
    """Scores at the query-key width and weighted values at v_head_dim of
    one layer over ``pairs`` (query, key) pairs."""
    _, H, _, dn, dr, dv = _dims(c)
    return 2.0 * H * (dn + dr + dv) * pairs


def prefill_flops(c: dict, S: int) -> float:
    """One prompt of length S: every layer, logits for the last token."""
    total = 2.0 * c["hidden_size"] * c["vocab_size"]
    for layer in range(c["num_hidden_layers"]):
        params, pairs = layer_work(c, layer, S)
        total += 2.0 * params * S + attention_flops(c, pairs)
    return total


def flash_fwd(c: dict, S: int) -> tuple[float, float]:
    """(flops, bytes) of one layer's prefill attention over one sequence
    of length S, at MLA's own widths: q k^T at qk_nope + qk_rope and p v
    at v_head_dim, causal pairs only; read q, k (qk wide) and v, write o
    (v wide) and the f32 lse."""
    _, H, _, dn, dr, dv = _dims(c)
    return (attention_flops(c, causal_pairs(S)),
            2.0 * S * H * (2 * (dn + dr) + 2 * dv) + 4.0 * H * S)


def decode_weight_bytes(c: dict) -> float:
    """Weight bytes one decode token must read: every layer's leaves but
    the routed experts, the held experts' expected share of its top k,
    the final norm and the head; not the embedding, of which it reads one
    row."""
    from bench import weights
    total = 0.0
    for name, (shape, dtype, _) in weights.top_specs(c).items():
        if name != "embed":
            total += _nbytes(shape, dtype)
    expert = 2.0 * _expert_params(c)
    for layer in range(c["num_hidden_layers"]):
        for path, (shape, dtype, _) in layer_specs(c, layer).items():
            if path[-1] not in ("w_in", "w_gate", "w_out"):
                total += _nbytes(shape, dtype)
        if _is_moe(c, layer):
            total += expert * _k_held(c) / _router_experts(c)
    return total


def _nbytes(shape, dtype) -> float:
    return float(math.prod(shape) * np.dtype(dtype).itemsize)


def latent_row_bytes(c: dict) -> float:
    """Bytes of one cached row: the latent and the rope key, bfloat16."""
    return 2.0 * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
