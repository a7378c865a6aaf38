"""Roofline table builder: merges the dry-run JSON (HLO collective census,
memory analysis, compile facts) with ANALYTIC compute/memory models.

Why analytic models: XLA's ``cost_analysis()`` counts every while-loop body
ONCE, so scan-over-layers (and the chunked-attention scans) under-count
FLOPs/bytes by orders of magnitude (observed: 2000x on tinyllama).  We keep
the raw numbers for reference but derive the roofline terms from structural
models with known trip counts.  The collective term comes from the HLO
census (reliable: collectives are never inside scans in our programs — the
gradient sync runs once per step, TP collectives are unrolled per run).

Conventions (documented in EXPERIMENTS.md §Roofline):
  * train FLOPs factor: forward 1x + backward 2x + remat re-forward 1x = 4x
    for layer compute; 3x for the (non-rematted) CE head.
  * our attention computes the FULL masked S x S score (no causal block
    skipping) -> attention FLOPs count S, not S/2; the MODEL_FLOPS ratio
    surfaces exactly this waste.
  * bytes: weights read thrice (fwd/remat/bwd) + grad write + ZeRO-1 opt
    traffic; activations ~14 x-sized r/w per layer + flash K/V re-reads;
    decode: the KV cache read dominates.
"""
from __future__ import annotations

import json
import os
import sys

from repro.configs import get_config, list_archs, SHAPES
from repro.configs.shapes import applicable
from repro.core.costmodel import TPU_V5E, roofline_terms

RESULTS = os.path.join(os.path.dirname(__file__), "results", "dryrun.json")


# ---------------------------------------------------------------------- #
# Analytic FLOPs
# ---------------------------------------------------------------------- #

def _mlp_flops_per_tok(cfg):
    if cfg.moe is not None:
        m = cfg.moe
        routed = 2 * 3 * cfg.d_model * m.d_ff_expert * m.top_k
        shared = 2 * 3 * cfg.d_model * cfg.d_ff_shared if m.shared_expert else 0
        router = 2 * cfg.d_model * m.n_experts
        return routed + shared + router
    mult = 3 if cfg.activation in ("swiglu", "geglu") else 2
    return 2 * mult * cfg.d_model * cfg.d_ff


def attn_kv_eff(S, causal, window, block_skip, chunk=512):
    """Average kv positions COMPUTED per query under the flash blocking.

    block_skip=False: the pre-skip implementation computes every (i,j) block
    (full S).  block_skip=True: exact count of on-band blocks (lax.cond skip
    in models.layers), averaged over q blocks.

    Public: benchmarks/bench_kernels.py uses this for the Pallas flash
    kernels' analytic FLOPs (the kernels skip off-band blocks with pl.when,
    the same blocking this function counts)."""
    if not block_skip:
        return min(S, window + chunk) if (window and not causal) else S
    cq = ck = min(chunk, S)
    nq, nk = S // cq, S // ck
    total = 0
    for i in range(nq):
        for j in range(nk):
            need = True
            if causal:
                need &= j * ck <= i * cq + cq - 1
            if window is not None:
                need &= (i * cq) - (j * ck + ck - 1) < window
            total += ck if need else 0
    return total / nq


def _layer_flops_per_tok(cfg, kind, kv_len, block_skip=False, decode=False):
    D = cfg.d_model
    if kind in ("attn", "local"):
        window = cfg.window if kind == "local" else None
        if decode:  # one query against the whole (windowed) cache
            eff = min(kv_len, window) if window else kv_len
        else:
            eff = attn_kv_eff(kv_len, True, window, block_skip)
        proj = 2 * (D * cfg.q_dim + 2 * D * cfg.kv_dim + cfg.q_dim * D)
        attn = 4 * cfg.n_heads * cfg.head_dim * eff
        return proj + attn + _mlp_flops_per_tok(cfg)
    if kind == "rglru":
        R = cfg.d_rnn or D
        proj = 2 * (2 * D * R + 2 * R * R + R * D)
        return proj + 30 * R + _mlp_flops_per_tok(cfg)
    if kind == "rwkv6":
        hd = cfg.rwkv_head_dim
        H = D // hd
        tm = 2 * 6 * D * D + 6 * H * hd * hd      # projections + wkv state
        cm = 2 * (2 * D * cfg.d_ff + D * D)       # channel mix
        return tm + cm
    raise ValueError(kind)


def flops_estimate(cfg, shape, block_skip: bool = False) -> float:
    """Global FLOPs for one step of (cfg x shape)."""
    B, S = shape.global_batch, shape.seq_len
    if shape.kind == "decode":
        tokens, kv_len, layer_f, head_f = B, S, 1.0, 1.0
    elif shape.kind == "prefill":
        tokens, kv_len, layer_f, head_f = B * S, S, 1.0, 1.0
    else:
        tokens, kv_len, layer_f, head_f = B * S, S, 4.0, 3.0
    dec = shape.kind == "decode"
    per_tok = sum(_layer_flops_per_tok(cfg, k, kv_len, block_skip, dec)
                  for k in cfg.pattern)
    if cfg.enc_dec:
        per_tok += cfg.enc_dec.n_enc_layers * _layer_flops_per_tok(
            cfg, "attn", kv_len, block_skip, dec)
        per_tok += cfg.n_layers * 2 * (cfg.d_model * cfg.q_dim
                                       + cfg.q_dim * cfg.d_model)  # cross
    head = 2 * cfg.d_model * cfg.vocab
    if shape.kind == "prefill":
        head_tokens = B  # prefill emits last-token logits only
    else:
        head_tokens = tokens
    return layer_f * per_tok * tokens + head_f * head * head_tokens


def model_flops(cfg, shape) -> float:
    """The 6*N*D (train) / 2*N*D (inference) yardstick over ACTIVE params,
    excluding the input embedding table (a lookup, not a matmul) but keeping
    the tied LM head via the +D*V term only where logits are computed."""
    B, S = shape.global_batch, shape.seq_len
    tokens = B if shape.kind == "decode" else B * S
    mult = 6 if shape.kind == "train" else 2
    n = cfg.active_param_count() - cfg.vocab * cfg.d_model
    head_tokens = B if shape.kind == "prefill" else tokens
    hm = 3 if shape.kind == "train" else 1
    return mult * n * tokens + hm * 2 * cfg.d_model * cfg.vocab * head_tokens


# ---------------------------------------------------------------------- #
# Analytic bytes (per chip)
# ---------------------------------------------------------------------- #

def bytes_estimate_per_chip(cfg, shape, mesh_shape) -> float:
    model = mesh_shape.get("model", 1)
    data = mesh_shape.get("data", 1)
    pods = mesh_shape.get("pod", 1)
    chips = model * data * pods
    B, S = shape.global_batch, shape.seq_len
    P = cfg.param_count()
    Wc = P * 2 / model                      # bf16 weights per chip
    if shape.kind == "train":
        toks_local = B * S / (data * pods)
        w = 3 * Wc + Wc                     # fwd+remat+bwd reads, grad write
        w += 2 * P * 12 / (model * data)    # ZeRO-1 m/v/master r+w (f32)
        act = 14 * toks_local * cfg.d_model * 2 * cfg.n_layers
        # flash K/V re-reads: every q chunk (cq=512) streams all K,V
        kv_rereads = sum(
            (min(S, cfg.window) if k == "local" else S) / 512
            * 2 * cfg.kv_dim * 2
            for k in cfg.pattern if k in ("attn", "local"))
        act += toks_local * kv_rereads * 3  # fwd + bwd(dq) + bwd(dkv) passes
        return w + act
    if shape.kind == "prefill":
        toks_local = B * S / (data * pods)
        act = 8 * toks_local * cfg.d_model * 2 * cfg.n_layers
        kv_rereads = sum(
            (min(S, cfg.window) if k == "local" else S) / 512
            * 2 * cfg.kv_dim * 2
            for k in cfg.pattern if k in ("attn", "local"))
        return Wc + act + toks_local * kv_rereads
    # decode: weights + full cache read once per token
    cache = 0.0
    for k in cfg.pattern:
        if k == "attn":
            cache += B * S * 2 * cfg.kv_dim * 2
        elif k == "local":
            cache += B * min(S, cfg.window) * 2 * cfg.kv_dim * 2
        elif k == "rwkv6":
            hd = cfg.rwkv_head_dim
            cache += B * (cfg.d_model // hd) * hd * hd * 4
        elif k == "rglru":
            cache += B * (cfg.d_rnn or cfg.d_model) * 4
    return Wc + cache / chips


# ---------------------------------------------------------------------- #
# Table builder
# ---------------------------------------------------------------------- #

def build_table(mesh: str = "16x16", comm: str = "multilevel",
                tag: str | None = None, block_skip: bool = True) -> list[dict]:
    with open(RESULTS) as f:
        res = json.load(f)
    chips = 512 if mesh == "2x16x16" else 256
    mesh_shape = ({"pod": 2, "data": 16, "model": 16} if mesh == "2x16x16"
                  else {"data": 16, "model": 16})
    rows = []
    for arch in list_archs()[:10]:
        for sname, shape in SHAPES.items():
            key = f"{arch}|{sname}|{mesh}|{comm}" + (f"|{tag}" if tag else "")
            rec = res.get(key)
            # prefer the optimized (hillclimbed) record where one exists
            for t in ("ep", "sp"):
                opt = res.get(f"{arch}|{sname}|{mesh}|{comm}|{t}")
                if opt and "error" not in opt:
                    rec = opt
            cfg = get_config(arch)
            ok, why = applicable(cfg, shape)
            if not ok:
                rows.append({"arch": arch, "shape": sname, "skipped": why})
                continue
            if rec is None or "error" in rec:
                rows.append({"arch": arch, "shape": sname,
                             "error": (rec or {}).get("error", "missing")})
                continue
            fl = flops_estimate(cfg, shape, block_skip=block_skip)
            mb = bytes_estimate_per_chip(cfg, shape, mesh_shape)
            terms = roofline_terms(
                hlo_flops=fl, hlo_bytes=mb * chips,
                ici_bytes=rec["ici_mb_per_chip"] * 1e6,
                dcn_bytes=rec["dcn_mb_per_chip"] * 1e6,
                chips=chips, hw=TPU_V5E)
            mf = model_flops(cfg, shape)
            rows.append({
                "arch": arch, "shape": sname, "mesh": mesh,
                "compute_s": terms["compute_s"], "memory_s": terms["memory_s"],
                "collective_s": terms["collective_s"], "bound": terms["bound"],
                "step_s": terms["step_s"],
                "model_flops": mf, "est_flops": fl,
                "useful_frac": mf / fl,
                "roofline_frac": (mf / (chips * TPU_V5E.peak_flops))
                                 / terms["step_s"],
                "ici_mb": rec["ici_mb_per_chip"],
                "dcn_mb": rec["dcn_mb_per_chip"],
                "compile_s": rec["compile_s"],
                "raw_hlo_gflops": rec["hlo_gflops"],
                "counts": rec.get("collective_counts", {}),
            })
    return rows


def _emit(rows, out) -> None:
    print("arch,shape,bound,compute_s,memory_s,collective_s,step_s,"
          "roofline_frac,useful_frac,ici_gb,dcn_mb", file=out)
    for r in rows:
        if "skipped" in r or "error" in r:
            print(f"{r['arch']},{r['shape']},"
                  f"{r.get('skipped') or r.get('error')}", file=out)
            continue
        print(f"{r['arch']},{r['shape']},{r['bound']},"
              f"{r['compute_s']:.5f},{r['memory_s']:.5f},"
              f"{r['collective_s']:.5f},{r['step_s']:.5f},"
              f"{r['roofline_frac']:.3f},{r['useful_frac']:.3f},"
              f"{r['ici_mb']/1e3:.2f},{r['dcn_mb']:.1f}", file=out)


def main(out=sys.stdout, block_skip: bool = True) -> None:
    for mesh in ("16x16", "2x16x16"):
        try:
            rows = build_table(mesh, block_skip=block_skip)
        except FileNotFoundError:
            print(f"# no dryrun results for {mesh}", file=out)
            continue
        print(f"# mesh {mesh}", file=out)
        _emit(rows, out)
        csv = os.path.join(os.path.dirname(RESULTS),
                           f"roofline_{mesh.replace('x', '_')}.csv")
        with open(csv, "w") as f:
            _emit(rows, f)


if __name__ == "__main__":
    main()
