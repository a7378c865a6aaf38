"""Cells small enough for a CPU test: the same drivers, configurations of
the same shape as the benchmark's, a few layers and narrow widths."""
from bench import harness

DENSE = dict(name="tiny-dense", reference="decoder_ref", arch="decoder_arch",
             hidden_size=64, intermediate_size=128, num_hidden_layers=2,
             num_attention_heads=4, num_key_value_heads=2, head_dim=16,
             vocab_size=512, tie_word_embeddings=True, rope_theta=10000.0,
             rms_norm_eps=1e-6)
MOE = dict(name="tiny-moe", reference="decoder_ref", arch="decoder_arch",
           hidden_size=64, intermediate_size=32, num_hidden_layers=1,
           num_attention_heads=4, num_key_value_heads=4, head_dim=16,
           vocab_size=256, tie_word_embeddings=False, num_experts=8,
           num_experts_per_tok=2, norm_topk_prob=True, rope_theta=10000.0,
           rms_norm_eps=1e-6)
CHAT = dict(kind="serve", mesh=[1, 1, 1], clients=1, block_size=16,
            prefill_token_budget=256, kv_pool_tokens=1024,
            prompt_lens=[16, 32, 64], prompt_weights=[0.3, 0.4, 0.3],
            output=dict(median=8, sigma=0.8, min=2, max=24), block=16,
            requests=4096, warmup_steps=4, check_tokens=32)
OPT = dict(lr=1e-3, betas=[0.9, 0.95], eps=1e-8, weight_decay=0.1,
           clip_norm=1.0, warmup_steps=10, total_steps=10000)


def job(mesh=(1, 1, 1)):
    return dict(kind="train", mesh=list(mesh), seq_len=128,
                batch_per_chip=2, comm_mode="multilevel", zero1=True,
                optimizer=OPT)


# limits for these sizes, under the names the committed training cells
# compare, set from readings on the CPU: one client served, sound runs
# 0-0.032 and the float8 control 0.83-1.73 over six seeds; training (the
# router's margin in place), sound runs at most 0.030 and 0.0030 for
# grad_diff and update_norm_gap over four seeds, the control's grad_diff
# 0.246-0.288, half of the batch 0.895 and 0.146
SERVE_LIMITS = {"max_logit_gap": 0.2}
TRAIN_LIMITS = {"grad_diff": 0.1, "update_norm_gap": 0.01}


def cell(kind: str, chips: int = 1, mesh=(1, 1, 1)) -> harness.Cell:
    spec = harness.benchmark_spec()
    if kind == "serve":
        names = ("out_tok_per_s", "itl_p95_s", "setup_s")
        c, t, lim = DENSE, CHAT, SERVE_LIMITS
    else:
        names = ("train_tok_per_s", "setup_s")
        c, t, lim = MOE, job(mesh), TRAIN_LIMITS
    e2e = [m for m in spec["end_to_end"] if m["name"] in names]
    return harness.Cell(f"tiny.{kind}", chips, c, t, {"limits": lim}, e2e,
                        [])


def run(kind: str, seconds: float = 1.0, fault=None, seed: int = 2 ** 33 + 1,
        chips: int = 1, mesh=(1, 1, 1)):
    """(result, checks) of one run on the CPU."""
    import time

    import jax

    from bench import run as R
    c = cell(kind, chips, mesh)
    return R.execute(c, seed, seconds, False, jax.devices()[:chips],
                     t_start=time.perf_counter(), peak=None, fault=fault)
