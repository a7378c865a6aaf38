"""Model configuration: one dataclass covering every assigned architecture.

Layer stacks are described by a per-layer ``pattern`` of block kinds:
  "attn"    full causal self-attention
  "local"   sliding-window self-attention (window = cfg.window)
  "rglru"   RG-LRU recurrent block (Griffin / recurrentgemma)
  "rwkv6"   RWKV-6 "Finch" linear-attention block with data-dependent decay
  "mla"     multi-head latent attention (DeepSeek-V2): keys and values come
            from one compressed latent per token (``cfg.mla``)
Every block is followed by an MLP (or MoE) sublayer except "rwkv6", which
uses the RWKV channel-mix in place of the MLP.  With ``cfg.moe`` set, the
first ``first_k_dense`` layers keep a dense MLP of width ``d_ff`` and the
rest are MoE; a change of MLP kind ends a run (``runs()``).
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

__all__ = ["MoECfg", "MLACfg", "YarnCfg", "EncDecCfg", "ModelConfig"]


@dataclasses.dataclass(frozen=True)
class MoECfg:
    n_experts: int
    top_k: int
    d_ff_expert: int
    capacity_factor: float = 1.25
    # a shared dense expert alongside the routed ones, d_ff_shared wide
    # (None: as wide as the model's dense d_ff, llama4-style)
    shared_expert: bool = False
    d_ff_shared: int | None = None
    # gates: the chosen top_k router probabilities renormalised to sum to 1
    # (True), or as the softmax over all experts gave them (DeepSeek's
    # norm_topk_prob false)
    norm_topk_prob: bool = True
    # expert parallelism's share held by this device: experts
    # [first_held, first_held + n_held) of the n_experts the router chooses
    # among (None: all of them).  Tokens routed elsewhere get nothing here.
    n_held: int | None = None
    first_held: int = 0

    @property
    def held(self) -> int:
        return self.n_experts if self.n_held is None else self.n_held


@dataclasses.dataclass(frozen=True)
class MLACfg:
    """Multi-head latent attention widths (DeepSeek-V2, no query LoRA)."""
    kv_lora_rank: int        # the latent c every head's k_nope and v come from
    qk_nope_dim: int         # per-head query/key dims without rotation
    qk_rope_dim: int         # rotated dims; one rope key shared by all heads
    v_head_dim: int

    @property
    def qk_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim

    @property
    def latent_dim(self) -> int:
        """Width of one cached row: the latent, then the rope key."""
        return self.kv_lora_rank + self.qk_rope_dim


@dataclasses.dataclass(frozen=True)
class YarnCfg:
    """YaRN RoPE scaling (arXiv:2309.00071), as DeepSeek-V2 configures it."""
    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class EncDecCfg:
    n_enc_layers: int
    n_dec_layers: int


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    pattern: tuple[str, ...]            # len == n_layers (decoder side)
    window: int = 1024                  # sliding-window size for "local"
    qk_norm: bool = False
    rope_theta: float = 10000.0
    activation: str = "swiglu"          # swiglu | gelu
    tie_embeddings: bool = True
    moe: MoECfg | None = None
    enc_dec: EncDecCfg | None = None
    d_rnn: int | None = None            # RG-LRU recurrence width
    rwkv_head_dim: int = 64
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"
    # which family flag ("dense"|"moe"|"vlm"|"hybrid"|"audio"|"ssm")
    family: str = "dense"
    # PaLM/GPT-J-style parallel residual block: y = x + attn(n(x)) + mlp(n(x))
    # — halves the per-layer tensor-parallel all-reduces (perf variant; the
    # paper-faithful configs keep sequential blocks)
    parallel_block: bool = False
    # modality frontend stub: number of non-token embedding positions
    frontend: str | None = None         # None | "vision" | "audio"
    mla: MLACfg | None = None           # widths of the "mla" block kind
    yarn: YarnCfg | None = None         # RoPE scaling of the "mla" kind
    first_k_dense: int = 0              # leading layers with a dense MLP

    # ------------------------------------------------------------------ #
    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    @property
    def subquadratic(self) -> bool:
        """True iff a 500k-token decode is feasible (no full-attention layer)."""
        return all(k in ("rglru", "rwkv6", "local") for k in self.pattern)

    def layer_moe(self, layer: int) -> bool:
        """Whether decoder layer ``layer``'s MLP is the MoE."""
        return self.moe is not None and layer >= self.first_k_dense

    def runs(self) -> list[tuple[str, int]]:
        """Maximal runs of layers alike in block kind and MLP kind (scan
        groups)."""
        out: list[tuple[str, int]] = []
        for i, k in enumerate(self.pattern):
            if (out and out[-1][0] == k
                    and self.layer_moe(i) == self.layer_moe(i - 1)):
                out[-1] = (k, out[-1][1] + 1)
            else:
                out.append((k, 1))
        return out

    def run_firsts(self) -> list[int]:
        """The first layer of each of ``runs()``."""
        out, first = [], 0
        for _, n in self.runs():
            out.append(first)
            first += n
        return out

    def param_count(self) -> int:
        """Total parameters (embedding + blocks); MoE counts every expert
        this device holds."""
        D, F, V = self.d_model, self.d_ff, self.vocab
        emb = V * D * (1 if self.tie_embeddings else 2)
        total = emb + D  # final norm
        n_dec = self.enc_dec.n_dec_layers if self.enc_dec else self.n_layers
        for i, kind in enumerate(self.pattern):
            total += self._block_params(kind, self.layer_moe(i))
        if self.enc_dec:
            for _ in range(self.enc_dec.n_enc_layers):
                total += self._block_params("attn", self.moe is not None)
            # decoder cross-attention on top of the pattern blocks
            total += n_dec * self._attn_params()
        return total

    def _attn_params(self) -> int:
        D = self.d_model
        return D * self.q_dim + 2 * D * self.kv_dim + self.q_dim * D

    def _mla_params(self) -> int:
        D, H, a = self.d_model, self.n_heads, self.mla
        return (D * H * a.qk_dim + D * a.latent_dim + a.kv_lora_rank
                + a.kv_lora_rank * H * (a.qk_nope_dim + a.v_head_dim)
                + H * a.v_head_dim * D)

    @property
    def d_ff_shared(self) -> int:
        """Width of the MoE's shared expert."""
        return self.moe.d_ff_shared or self.d_ff

    def _mlp_params(self, moe: bool) -> int:
        D, F = self.d_model, self.d_ff
        if moe:
            E, Fe = self.moe.n_experts, self.moe.d_ff_expert
            routed = self.moe.held * (3 if self.activation == "swiglu" else 2) * self.d_model * Fe
            shared = (3 * D * self.d_ff_shared) if self.moe.shared_expert else 0
            return routed + shared + D * E  # + router
        mult = 3 if self.activation in ("swiglu", "geglu") else 2
        return mult * D * F

    def _block_params(self, kind: str, moe: bool) -> int:
        D = self.d_model
        if kind in ("attn", "local"):
            return self._attn_params() + self._mlp_params(moe) + 2 * D
        if kind == "mla":
            return self._mla_params() + self._mlp_params(moe) + 2 * D
        if kind == "rglru":
            R = self.d_rnn or D
            return (2 * D * R + 2 * R * R + 4 * R + R * D
                    + self._mlp_params(moe) + 2 * D)
        if kind == "rwkv6":
            # time-mix (r,k,v,g,w proj + out) + channel-mix (k,v,r)
            return 7 * D * D + 2 * D * self.d_ff + 2 * D
        raise ValueError(kind)

    def active_param_count(self) -> int:
        """Active params per token (MoE: the top_k experts, of which a
        held share computes held / n_experts here, in expectation)."""
        if self.moe is None:
            return self.param_count()
        m = self.moe
        expert = 3 * self.d_model * m.d_ff_expert
        routed_held = m.held * expert
        routed_active = m.top_k * m.held * expert // m.n_experts
        n_moe = sum(map(self.layer_moe, range(len(self.pattern))))
        return self.param_count() - n_moe * (routed_held - routed_active)


def pattern_repeat(base: Sequence[str], n_layers: int) -> tuple[str, ...]:
    out: list[str] = []
    while len(out) < n_layers:
        out.extend(base)
    return tuple(out[:n_layers])
