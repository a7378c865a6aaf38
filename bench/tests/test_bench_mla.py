"""DeepSeek-V2-Lite through the harness: its configuration keeps every
published number but the experts held here, its arch module builds the
program's own tree and counts, a tiny stack of the same shape is served
and checked against the plain reference on the CPU, and the cell's
readers read what the program reports."""
import time

import jax
import numpy as np
import pytest

from bench import flops, harness, tracing, weights
from bench.run import View

CELL = "deepseek-v2-lite.longdoc-single"
# DeepSeek-V2-Lite's config.json (the numbers and shapes; no token ids)
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 10944,
    "kv_lora_rank": 512, "max_position_embeddings": 163840,
    "model_type": "deepseek_v2", "moe_intermediate_size": 1408,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 2, "norm_topk_prob": False,
    "num_attention_heads": 16, "num_experts_per_tok": 6,
    "num_hidden_layers": 27, "num_key_value_heads": 16, "q_lora_rank": None,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 1,
    "scoring_func": "softmax", "seq_aux": True, "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "greedy", "v_head_dim": 128,
    "vocab_size": 102400}

TINY = dict(
    name="tiny-mla", reference="mla_ref", arch="mla_arch",
    hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
    num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, q_lora_rank=None, n_routed_experts=2,
    held_experts_first=2, published={"n_routed_experts": 8},
    n_shared_experts=2, num_experts_per_tok=2, first_k_dense_replace=1,
    vocab_size=256, tie_word_embeddings=False, norm_topk_prob=False,
    routed_scaling_factor=1.0, rope_theta=10000.0, rms_norm_eps=1e-6,
    rope_scaling=PUBLISHED["rope_scaling"])
CHAT = dict(kind="serve", mesh=[1, 1, 1], clients=1, block_size=16,
            prefill_token_budget=256, kv_pool_tokens=1024,
            prompt_lens=[16, 32, 64], prompt_weights=[0.3, 0.4, 0.3],
            output=dict(median=8, sigma=0.8, min=2, max=24), block=16,
            requests=4096, warmup_steps=4, check_tokens=32)
# from readings on the CPU over fourteen seeds: sound runs 0-0.0374, the
# float8 control 0.215-1.095
LIMIT = 0.1


@pytest.fixture(scope="module")
def cell():
    return harness.find_cell(CELL)


def test_the_configuration_is_the_published_one_but_the_held_experts(cell):
    c = cell.config
    changed = {k for k, v in PUBLISHED.items() if c[k] != v}
    assert changed == set(c["reduced"]) == {"n_routed_experts"}
    assert c["published"] == {"n_routed_experts": 64}
    assert c["n_routed_experts"] == 16 and c["held_experts_first"] == 0
    assert c["program_cannot_express"] == {}
    spec = harness.benchmark_spec()
    conf, = [x for x in spec["configs"] if x["name"] == c["name"]]
    assert conf["reduced"] == c["reduced"]
    assert cell.limits["limits"].keys() == {"max_logit_gap"}
    assert [m["name"] for m in cell.end_to_end] == [
        "out_tok_per_s", "itl_p95_s", "setup_s"]
    assert {"mla.prefill_mfu", "mla.flash_fwd_roofline",
            "mla.decode_hbm_share"} <= {m["name"] for m in cell.per_layer}


def test_model_config_and_tree_are_the_programs(cell):
    from repro.models import transformer as T
    from repro.models.config import MLACfg, ModelConfig, MoECfg, YarnCfg
    c = cell.config
    cfg = harness.model_config(c)
    assert cfg == ModelConfig(
        name="deepseek-v2-lite-16b", n_layers=27, d_model=2048, n_heads=16,
        n_kv_heads=16, head_dim=192, d_ff=10944, vocab=102400,
        pattern=("mla",) * 27, rope_theta=10000.0, tie_embeddings=False,
        moe=MoECfg(n_experts=64, top_k=6, d_ff_expert=1408,
                   shared_expert=True, d_ff_shared=2816,
                   norm_topk_prob=False, n_held=16,
                   first_held=0),
        mla=MLACfg(512, 128, 64, 128),
        yarn=YarnCfg(40.0, 4096, 32.0, 1.0, 0.707, 0.707),
        first_k_dense=1, norm_eps=1e-6, family="moe")
    assert cfg.runs() == [("mla", 1), ("mla", 26)]
    tree = jax.eval_shape(lambda: weights.make_params(c, 7))
    want = jax.eval_shape(lambda: T.init_model(jax.random.PRNGKey(0), cfg))
    assert jax.tree.structure(tree) == jax.tree.structure(want)
    assert [(x.shape, x.dtype) for x in jax.tree.leaves(tree)] == [
        (x.shape, x.dtype) for x in jax.tree.leaves(want)]
    n = sum(x.size for x in jax.tree.leaves(tree))
    assert n == cfg.param_count() == 4_910_345_728


def test_layer_work_counts_the_programs_active_parameters(cell):
    """Every layer's weights one token multiplies by, plus what no matmul
    reads per token (the norms) and the embedding and head, is the
    program's active parameter count; the prefill's FLOPs are those
    weights twice per token plus MLA attention at 192 + 128 wide."""
    c = cell.config
    arch = harness.arch_module(c)
    cfg = harness.model_config(c)
    D, V, r = 2048, 102400, 512
    per_token = sum(arch.layer_work(c, i, 1)[0] for i in range(27))
    norms = 27 * (2 * D + r) + D
    assert per_token + norms + 2 * V * D == cfg.active_param_count()
    S = 4096
    pairs = flops.causal_pairs(S)
    assert arch.prefill_flops(c, S) == (2.0 * per_token * S
                                        + 27 * 2 * 16 * 320 * pairs
                                        + 2.0 * D * V)
    f, b = arch.flash_fwd(c, S)
    assert f == 2 * 16 * 320 * pairs
    assert b == 2 * S * 16 * (2 * 192 + 2 * 128) + 4 * 16 * S


def test_decode_weight_bytes(cell):
    """A decode token reads every weight but the routed experts and the
    embedding, and 6 x 16/64 of an MoE layer's experts."""
    c = cell.config
    arch = harness.arch_module(c)
    D, V, Fe = 2048, 102400, 1408
    attn = D * 16 * 192 + D * 576 + 512 * 16 * 256 + 16 * 128 * D
    bf16 = (27 * attn + 3 * D * 10944 + 26 * 3 * D * 2 * Fe + D * V)
    f32 = 27 * (2 * D + 512) + 26 * D * 64 + D
    experts = 26 * 1.5 * 3 * D * Fe
    assert arch.decode_weight_bytes(c) == 2.0 * (bf16 + experts) + 4.0 * f32


def _tiny_cell(per_layer=()):
    spec = harness.benchmark_spec()
    e2e = [m for m in spec["end_to_end"]
           if m["name"] in ("out_tok_per_s", "itl_p95_s", "setup_s")]
    return harness.Cell("tiny.mla", 1, TINY, CHAT,
                        {"limits": {"max_logit_gap": LIMIT}}, e2e,
                        list(per_layer))


def test_tiny_mla_is_served_correct_and_the_control_is_not():
    """`bench/serve_cell.py` over the program's MLA stack with a held share,
    checked against the plain reference: correct, and the float8
    control's gap above the limit on the same sample."""
    from bench import run as R
    res, checks = R.execute(_tiny_cell(), 2 ** 33 + 1, 1.0, False,
                            jax.devices()[:1], t_start=time.perf_counter(),
                            peak=None, control=True)
    assert res["correct"], checks
    assert res["control"]["max_logit_gap"] <= LIMIT
    assert res["control"]["control_max_logit_gap"] > LIMIT
    assert res["metrics"]["out_tok_per_s"]["value"] > 0


def test_decode_hbm_share_reads_the_executors_counters():
    """The reader prices the arch module's weight bytes and the live
    latent rows the executor counted per decode call; without the
    counters (a program that has none) it reads nothing."""
    from bench import run as R
    cell = harness.find_cell(CELL)
    c = dict(cell.config)
    peak = harness.peaks("TPU v5 lite")
    read = harness.metric_reader("mla.decode_hbm_share")
    res, _ = R.execute(_tiny_cell(cell.per_layer), 2 ** 33 + 3, 1.0, True,
                       jax.devices()[:1], t_start=time.perf_counter(),
                       peak=peak)
    assert res["metrics"]["mla.decode_hbm_share"]["value"] > 0

    class Counters(dict):
        def counter(self, name):
            return self[name]

    class N:
        def __init__(self, v):
            self.value = v

    class Call:
        def __init__(self, t0, t1):
            self.kind, self.t0, self.t1 = "decode", t0, t1

    counters = Counters({"repro.decode.calls": N(4),
                         "repro.mla.latent_rows_live": N(4 * 27 * 1000)})
    ex = type("Ex", (), {"metrics": counters})()
    tx = type("Tx", (), {"calls": [Call(1.0, 1.01), Call(2.0, 2.01)]})()
    run = View(kind="serve", config=c, window=(0.0, 3.0), tx=tx,
               out={"executor": ex}, peak=peak)
    arch = harness.arch_module(c)
    nbytes = arch.decode_weight_bytes(c) + 27 * 1000 * 1152
    assert read(run) == pytest.approx(
        100.0 * nbytes / 0.01 / 819e9, rel=1e-9)
    run.out = {"executor": object()}
    assert read(run) is None


def test_flash_roofline_prices_mla_widths():
    """One forward kernel event of 4 ms inside a 4096-token prefill span
    reads the least time of 4096 tokens' causal attention at 192 + 128
    wide (compute bound), over 4 ms."""
    cell = harness.find_cell(CELL)
    c = cell.config
    peak = harness.peaks("TPU v5 lite")
    text = ("%fusion.3 = (bf16[16,1,4096,192]{3,2,1,0}, f32[128,1,512]"
            "{2,1,0}) custom-call(%a, %b, %c), custom_call_target="
            "\"tpu_custom_call\"")
    op = tracing.Op(text, "fusion.3 (custom-call)", "custom-call",
                    1_000_000, 5_000_000)
    red = tracing.Reduced((0, 10_000_000), {"/device:TPU:0": [op]},
                          [("bench.prefill.4096", 500_000, 6_000_000)])
    read = harness.metric_reader("mla.flash_fwd_roofline")
    need = 2 * 16 * 320 * flops.causal_pairs(4096) / 197e12
    assert read(View(trace=red, config=c, peak=peak)) == pytest.approx(
        100.0 * need / 4e-3, rel=1e-9)
    assert np.isclose(need, 0.436e-3, rtol=0.01)
