"""Operations and bytes of the work a cell asks for, counted from its
shapes: the same whatever implements it.  Taken from the analytic models of
``benchmarks/roofline.py`` (model FLOPs over active parameters, attention
counted over the pairs it needs, the head only where logits are read) and
from the flash algorithm's own passes.

What a layer does is its arch module's ``layer_work(c, layer, S)`` (see
``bench/configs/decoder_arch.py``): the weights one token multiplies by
there and the query-key pairs a sequence of length S attends there.  The
counts of a whole step sum it over the program's layers, so nothing here
names an architecture.  A multiply-add counts two operations.  ``c`` is a
configuration file.
"""
from __future__ import annotations

from bench import harness


def _dims(c: dict):
    return (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"], c["vocab_size"])


def causal_pairs(S: int) -> int:
    """(query, key) pairs a causal sequence of length S attends."""
    return S * (S + 1) // 2


def attention_flops(c: dict, pairs: int) -> float:
    """Scores and weighted values of one layer over ``pairs`` (query, key)
    pairs."""
    _, H, _, hd, _ = _dims(c)
    return 4.0 * H * hd * pairs


def _layers_fwd(c: dict, S: int) -> float:
    """Every layer's matrix products and attention over one sequence of
    length S."""
    arch = harness.arch_module(c)
    total = 0.0
    for layer in range(len(harness.model_config(c).pattern)):
        params, pairs = arch.layer_work(c, layer, S)
        total += 2.0 * params * S + attention_flops(c, pairs)
    return total


def prefill_flops(c: dict, S: int) -> float:
    """One prompt of length S: every layer, logits for the last token."""
    D, *_, V = _dims(c)
    return _layers_fwd(c, S) + 2.0 * D * V


def train_step_flops(c: dict, rows: int, S: int) -> float:
    """Forward and backward (3x forward) of ``rows`` sequences of length
    S, logits at every position; recomputation is not counted."""
    D, *_, V = _dims(c)
    return 3.0 * (_layers_fwd(c, S) + 2.0 * D * V * S) * rows


# ---------------------------------------------------------------------- #
# The flash kernels, one call = one layer's attention over B sequences
# ---------------------------------------------------------------------- #

def _qkvo_bytes(c: dict, B: int, S: int, itemsize: int = 2) -> float:
    _, H, Hkv, hd, _ = _dims(c)
    return float(B * S * hd * (2 * H + 2 * Hkv) * itemsize)


def flash_fwd(c: dict, B: int, S: int) -> tuple[float, float]:
    """(flops, bytes): q k^T and p v; read q, k, v, write o and lse."""
    _, H, _, _, _ = _dims(c)
    return (B * attention_flops(c, causal_pairs(S)),
            _qkvo_bytes(c, B, S) + 4.0 * B * H * S)


def flash_bwd_dq(c: dict, B: int, S: int) -> tuple[float, float]:
    """(flops, bytes): recomputed q k^T, dO v^T and dS k; reads q, k, v,
    dO, lse and delta, writes dq."""
    _, H, _, hd, _ = _dims(c)
    return (1.5 * B * attention_flops(c, causal_pairs(S)),
            _qkvo_bytes(c, B, S) + 2.0 * B * S * H * hd + 8.0 * B * H * S)


def flash_bwd_dkv(c: dict, B: int, S: int) -> tuple[float, float]:
    """(flops, bytes): recomputed q k^T, dO v^T, p^T dO and dS^T q; reads
    q, k, v, dO, lse and delta, writes dk and dv."""
    _, H, Hkv, hd, _ = _dims(c)
    return (2.0 * B * attention_flops(c, causal_pairs(S)),
            _qkvo_bytes(c, B, S) + 2.0 * B * S * Hkv * hd * 2
            + 8.0 * B * H * S)


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """Least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])
