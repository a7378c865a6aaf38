"""Serving subsystem: paged-attention numerics, block allocator, scheduler,
and open-loop load generation.

The numeric core — paged decode must be *bit-identical* to the dense cache
path for full-attention stacks — runs in-process on the default 1-device
view; the multi-request greedy-equivalence test drives the real
``JaxExecutor`` through the scheduler and checks every generated token
against a per-request dense reference decode.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import transformer as T
from repro.serving import (SLO, BlockAllocator, JaxExecutor, OutOfBlocks,
                           ReqState, Scheduler, SimExecutor, blocks_needed,
                           build_block_tables, bursty_arrivals,
                           default_compute_model, make_requests,
                           poisson_arrivals, summarize)

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------- #
# kv_cache: host-side block bookkeeping
# ---------------------------------------------------------------------- #

def test_blocks_needed():
    assert blocks_needed(0, 16) == 1     # a request always holds >= 1 block
    assert blocks_needed(1, 16) == 1
    assert blocks_needed(16, 16) == 1
    assert blocks_needed(17, 16) == 2
    assert blocks_needed(33, 16) == 3


def test_block_allocator_never_hands_out_null_block():
    alloc = BlockAllocator(8, 4)
    assert alloc.capacity == 7
    got = alloc.alloc(7)
    assert 0 not in got
    assert sorted(got) == list(range(1, 8))
    assert got == list(range(1, 8))      # deterministic low-id-first order


def test_block_allocator_all_or_nothing_oom():
    alloc = BlockAllocator(4, 4)
    alloc.alloc(2)
    n_free_before = alloc.n_free
    with pytest.raises(OutOfBlocks):
        alloc.alloc(2)                   # only 1 free
    assert alloc.n_free == n_free_before  # nothing partially taken
    assert alloc.can_alloc(1) and not alloc.can_alloc(2)


def test_block_allocator_free_validation():
    alloc = BlockAllocator(4, 4)
    got = alloc.alloc(2)
    alloc.free(got)
    assert alloc.n_free == alloc.capacity
    with pytest.raises(ValueError):
        alloc.free([got[0]])             # double free
    with pytest.raises(ValueError):
        alloc.free([0])                  # null block is not freeable
    with pytest.raises(ValueError):
        alloc.free([99])                 # out of range
    with pytest.raises(ValueError):
        BlockAllocator(1, 4)             # no room beside the null block


def test_build_block_tables_pads_with_null_block():
    tab = build_block_tables([[3, 1], [2]], max_blocks=3, n_slots=4)
    assert tab.dtype == np.int32 and tab.shape == (4, 3)
    np.testing.assert_array_equal(
        tab, [[3, 1, 0], [2, 0, 0], [0, 0, 0], [0, 0, 0]])
    with pytest.raises(ValueError):
        build_block_tables([[1, 2, 3, 4]], max_blocks=3)


# ---------------------------------------------------------------------- #
# loadgen: open-loop arrival processes
# ---------------------------------------------------------------------- #

def test_poisson_arrivals_rate_and_determinism():
    a = poisson_arrivals(50.0, 40.0, seed=3)
    b = poisson_arrivals(50.0, 40.0, seed=3)
    assert a == b
    assert all(0 <= t < 40.0 for t in a)
    assert a == sorted(a)
    # ~2000 expected arrivals: the realized rate should be within 10%
    assert 0.9 * 2000 < len(a) < 1.1 * 2000
    with pytest.raises(ValueError):
        poisson_arrivals(0.0, 1.0)


def test_bursty_arrivals_preserve_mean_rate():
    a = bursty_arrivals(50.0, 60.0, seed=0, burst_factor=8.0, duty=0.125)
    assert 0.85 * 3000 < len(a) < 1.15 * 3000
    # ON windows really are denser: first 12.5% of each period carries
    # burst_factor/1 = 8x the average density
    on = sum(1 for t in a if (t % 2.0) / 2.0 < 0.125)
    assert on > 0.8 * len(a)             # duty 1/8 at 8x rate => ~all arrivals
    with pytest.raises(ValueError):
        bursty_arrivals(50.0, 1.0, burst_factor=10.0, duty=0.2)  # >1 mean
    with pytest.raises(ValueError):
        bursty_arrivals(50.0, 1.0, duty=1.5)


def test_make_requests_ranges_and_determinism():
    arr = [0.0, 0.5, 1.0]
    r1 = make_requests(arr, vocab=128, prompt_len=(4, 9), gen_len=(2, 5),
                       slo=SLO(0.2, 0.05), seed=7)
    r2 = make_requests(arr, vocab=128, prompt_len=(4, 9), gen_len=(2, 5),
                       slo=SLO(0.2, 0.05), seed=7)
    assert len(r1) == 3
    for a, b in zip(r1, r2):
        np.testing.assert_array_equal(a.prompt, b.prompt)
        assert a.max_new_tokens == b.max_new_tokens
    for r in r1:
        assert 4 <= r.prompt_len <= 9 and 2 <= r.max_new_tokens <= 5
        assert r.prompt.dtype == np.int32 and int(r.prompt.max()) < 128
        assert r.state is ReqState.WAITING
        assert r.slo.ttft_deadline(r.arrival_s) == r.arrival_s + 0.2


# ---------------------------------------------------------------------- #
# scheduler: continuous batching over the token-fabricating executor
# ---------------------------------------------------------------------- #

def _sched(**kw):
    base = dict(n_blocks=1 + 16, block_size=4, max_slots=4, s_max=32,
                prefill_token_budget=64)
    base.update(kw)
    return Scheduler(SimExecutor(vocab=64, block_size=base["block_size"]),
                     **base)


def test_scheduler_validates_arguments():
    with pytest.raises(ValueError):
        _sched(policy="lifo")
    with pytest.raises(ValueError):
        _sched(mode="sparse")
    with pytest.raises(ValueError):
        _sched(s_max=30)                 # not a multiple of block_size


def test_continuous_batching_requests_join_and_leave():
    """Staggered arrivals with a slow compute model: the running batch must
    overlap requests (continuous batching) and every request must finish
    with exactly its requested token count and sane timestamps."""
    arr = [0.0, 0.0, 0.01, 0.02, 0.03, 0.04]
    reqs = make_requests(arr, vocab=64, prompt_len=(3, 9), gen_len=(4, 12),
                         seed=1)
    sch = _sched(compute_model=default_compute_model(1e9,
                                                     flops_per_s=1e12))
    rep = sch.run(reqs)
    assert all(r.state is ReqState.DONE for r in reqs)
    assert 2 <= rep.max_concurrent <= 4
    for r in reqs:
        assert len(r.tokens) == r.max_new_tokens
        assert r.first_token_s >= r.arrival_s
        assert r.finish_s >= r.first_token_s
        assert r.pos == r.prompt_len + r.max_new_tokens - 1
        assert r.blocks == [] and r.slot == -1   # resources returned
    s = rep.summary()
    assert s["n_done"] == 6 and s["n_shed"] == 0
    assert s["throughput_tok_s"] > 0


def test_paged_beats_dense_at_equal_block_budget():
    """Dense reserves worst-case ceil(s_max/block) blocks per request at
    admission; paged allocates on demand — at an equal budget paged must
    sustain strictly more concurrent requests."""
    conc = {}
    for mode in ("paged", "dense"):
        reqs = make_requests([0.0] * 12, vocab=64, prompt_len=4, gen_len=4,
                             seed=2)
        sch = _sched(mode=mode, n_blocks=1 + 3 * 8, block_size=4, s_max=32,
                     max_slots=12)      # dense fits exactly 3 requests
        rep = sch.run(reqs)
        assert all(r.state is ReqState.DONE for r in reqs)
        conc[mode] = rep.max_concurrent
    assert conc["dense"] == 3
    assert conc["paged"] > conc["dense"]


def test_slo_policy_sheds_and_beats_fifo_tail():
    """Overload: fifo's queue pushes p99 TTFT far past the deadline; the slo
    policy sheds expired requests and keeps the served tail inside it."""
    slo = SLO(ttft_s=0.05, tpot_s=0.02)
    arr = poisson_arrivals(200.0, 1.0, seed=1)   # ~200 req into a tiny server
    out = {}
    for policy in ("fifo", "slo"):
        reqs = make_requests(arr, vocab=64, prompt_len=(4, 12), gen_len=(4, 8),
                             slo=slo, seed=2)
        sch = _sched(policy=policy, max_slots=2, prefill_token_budget=16,
                     compute_model=default_compute_model(
                         1e9, flops_per_s=0.5e12))
        out[policy] = (sch.run(reqs).summary(), reqs)
    f, _ = out["fifo"]
    s, sreqs = out["slo"]
    assert f["ttft_p99_s"] > slo.ttft_s          # fifo is genuinely overloaded
    assert s["ttft_p99_s"] < f["ttft_p99_s"]
    assert s["n_shed"] > 0
    for r in sreqs:
        if r.state is ReqState.SHED:
            assert r.finish_s is not None and r.first_token_s is None
    assert 0 < s["slo_attainment"] <= 1.0


def test_over_budget_prompt_still_admitted_when_idle():
    reqs = make_requests([0.0], vocab=64, prompt_len=24, gen_len=2, seed=0)
    rep = _sched(prefill_token_budget=8).run(reqs)   # prompt 3x the budget
    assert reqs[0].state is ReqState.DONE
    assert rep.steps >= 1


def test_impossible_request_fails_loudly():
    reqs = make_requests([0.0], vocab=64, prompt_len=100, gen_len=2, seed=0)
    with pytest.raises(RuntimeError, match="needs more memory"):
        _sched(n_blocks=1 + 8, s_max=128).run(reqs)  # 25 blocks > capacity 8


def test_all_stalled_oom_evicts_youngest():
    """Two growing requests exhaust the pool; the deadlock breaks by
    shedding the youngest and recycling its blocks into the survivor."""
    reqs = make_requests([0.0, 0.001], vocab=64, prompt_len=4, gen_len=12,
                         seed=0)
    # nonzero step cost so the second arrival lands while the first runs
    sch = _sched(n_blocks=1 + 4, block_size=4, s_max=16, max_slots=2,
                 compute_model=default_compute_model(1e9, flops_per_s=1e12))
    rep = sch.run(reqs)
    assert rep.stalled_steps > 0
    assert reqs[0].state is ReqState.DONE        # older request survives
    assert reqs[1].state is ReqState.SHED        # younger one evicted
    assert len(reqs[0].tokens) == reqs[0].max_new_tokens


def test_scheduler_prices_network_through_engine():
    """With the PR 5 engine wired in, step time includes the decode gathers
    on the multilevel topology (the compute model here is zero)."""
    from repro.core import Communicator
    from repro.core.engine import Engine
    from repro.core.topology import paper_fig8_topology

    comm = Communicator(paper_fig8_topology(), backend="sim", policy="paper")
    reqs = make_requests([0.0] * 4, vocab=64, prompt_len=4, gen_len=4, seed=0)
    replicas = [tuple(range(g * 8, (g + 1) * 8)) for g in range(6)]
    sch = _sched(engine=Engine(comm, policy="priority", age_rate=1e6),
                 replicas=replicas, weight_bytes=1e6, gather_bytes=4096.0,
                 bcast_every=2)
    rep = sch.run(reqs)
    assert all(r.state is ReqState.DONE for r in reqs)
    assert rep.now > 0                           # network time advanced the clock
    s = summarize(reqs)
    assert s["ttft_p50_s"] > 0


# ---------------------------------------------------------------------- #
# paged attention numerics vs the dense cache path
# ---------------------------------------------------------------------- #

def _dense_decode_logits(cfg, params, toks, S, n_new):
    """Reference: dense prefill + decode_step, teacher-forced on toks."""
    logits_p, cache, pos = T.prefill(params, cfg, {"tokens": toks[:, :S]},
                                     s_max=S + n_new)
    out = [np.asarray(logits_p)]
    for i in range(n_new):
        lg, cache = T.decode_step(params, cfg, cache, toks[:, S + i:S + i + 1],
                                  jnp.int32(pos + i))
        out.append(np.asarray(lg))
    return out


def _paged_decode_logits(cfg, params, toks, S, n_new, BS):
    """Same computation through the paged pools (pool scatter + block-table
    gather), growing the block table on demand."""
    assert S % BS == 0
    max_blocks = blocks_needed(S + n_new, BS) + 1
    n_blocks = 1 + max_blocks
    alloc = BlockAllocator(n_blocks, BS)
    pools = T.init_paged_pools(cfg, n_blocks, BS)
    blocks = alloc.alloc(S // BS)
    logits_p, cache, _ = T.prefill(params, cfg, {"tokens": toks[:, :S]}, S,
                                   full_local_cache=True)
    pools = T.scatter_prefill_cache(pools, cache, blocks, BS)
    out = [np.asarray(logits_p)]
    for i in range(n_new):
        pos = S + i
        if blocks_needed(pos + 1, BS) > len(blocks):
            blocks.extend(alloc.alloc(1))
        table = jnp.asarray(build_block_tables([blocks], max_blocks))
        lg, pools = T.decode_step_paged(params, cfg, pools, table,
                                        toks[:, S + i:S + i + 1],
                                        jnp.asarray([pos], jnp.int32))
        out.append(np.asarray(lg))
    return out


def test_paged_decode_bit_identical_full_attention():
    """Pure-attention stack: the block-table gather reconstructs the logical
    token order exactly, so paged logits must be *bit-identical* to dense —
    across several block-boundary crossings (block_size 4, 10 steps)."""
    cfg = get_config("qwen3_4b", smoke=True)
    params = T.init_model(KEY, cfg)
    S, n_new, BS = 8, 10, 4
    toks = jax.random.randint(KEY, (1, S + n_new), 0, cfg.vocab)
    dense = _dense_decode_logits(cfg, params, toks, S, n_new)
    paged = _paged_decode_logits(cfg, params, toks, S, n_new, BS)
    for i, (d, p) in enumerate(zip(dense, paged)):
        np.testing.assert_array_equal(d, p, err_msg=f"step {i}")


def test_paged_decode_windowed_matches_through_wrap():
    """Windowed layers: dense wraps the cache modulo the window, paged keeps
    it unwrapped and masks at read time.  Before the window fills the paths
    must agree bit-for-bit; past it (different storage, same math) the
    logits must still agree numerically with identical argmax."""
    cfg = get_config("gemma3_12b", smoke=True)   # window=8 after shrink
    params = T.init_model(KEY, cfg)
    S, n_new, BS = 8, 6, 4
    toks = jax.random.randint(KEY, (1, S + n_new), 0, cfg.vocab)
    dense = _dense_decode_logits(cfg, params, toks, S, n_new)
    paged = _paged_decode_logits(cfg, params, toks, S, n_new, BS)
    np.testing.assert_array_equal(dense[0], paged[0])  # prefill logits
    for i in range(1, n_new + 1):
        np.testing.assert_allclose(dense[i], paged[i], rtol=0, atol=1e-4,
                                   err_msg=f"step {i}")
        assert int(np.argmax(dense[i])) == int(np.argmax(paged[i]))


def test_prefill_last_pos_right_padded():
    """Right-padded variable-length prefill: last_pos logits must equal the
    unpadded prefill's (causality keeps pads out of real scores)."""
    cfg = get_config("qwen3_4b", smoke=True)
    params = T.init_model(KEY, cfg)
    L, S_p = 6, 12
    toks = jax.random.randint(KEY, (1, L), 0, cfg.vocab)
    padded = jnp.zeros((1, S_p), jnp.int32).at[:, :L].set(toks)
    ref, _, _ = T.prefill(params, cfg, {"tokens": toks}, s_max=L)
    got, _, _ = T.prefill(params, cfg, {"tokens": padded}, s_max=S_p,
                          last_pos=jnp.asarray([L - 1]))
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


@pytest.mark.parametrize("arch", ["rwkv6_1p6b", "recurrentgemma_2b",
                                  "seamless_m4t_medium"])
def test_paged_arch_check_rejects_stateful_stacks(arch):
    cfg = get_config(arch, smoke=True)
    with pytest.raises(ValueError, match="attention-only"):
        T.paged_arch_check(cfg)
    with pytest.raises(ValueError):
        T.init_paged_pools(cfg, 8, 4)


def _small_jax_serve():
    """Three requests of variable prompt lengths and staggered finishes
    for the real paged executor on two slots, so slots recycle mid-run."""
    cfg = get_config("qwen3_4b", smoke=True)
    BS, s_max = 4, 24
    prompts = [3, 8, 5]                  # padded lengths 4 / 8 / 8
    gens = [6, 3, 5]                     # staggered finishes recycle slots
    reqs = make_requests([0.0] * 3, vocab=cfg.vocab, prompt_len=4, gen_len=4,
                         seed=0)
    rng = np.random.default_rng(0)
    for r, L, g in zip(reqs, prompts, gens):
        r.prompt = rng.integers(0, cfg.vocab, (L,)).astype(np.int32)
        r.max_new_tokens = g

    ex = JaxExecutor(cfg, None, n_blocks=1 + 2 * (s_max // BS), block_size=BS,
                     max_slots=2, max_blocks=s_max // BS, seed=0)
    sch = Scheduler(ex, n_blocks=1 + 2 * (s_max // BS), block_size=BS,
                    max_slots=2, s_max=s_max, prefill_token_budget=16)
    return cfg, ex, sch, reqs


def _dense_greedy(cfg, params, prompt, n_new):
    """The first ``n_new`` greedy tokens of a standalone dense prefill and
    decode loop over ``prompt``."""
    logits, cache, pos = T.prefill(params, cfg,
                                   {"tokens": jnp.asarray(prompt)[None, :]},
                                   s_max=len(prompt) + n_new)
    ref = [int(np.argmax(np.asarray(logits[0, -1])))]
    for i in range(n_new - 1):
        lg, cache = T.decode_step(params, cfg, cache,
                                  jnp.asarray([[ref[-1]]], jnp.int32),
                                  jnp.int32(pos + i))
        ref.append(int(np.argmax(np.asarray(lg[0, 0]))))
    return ref


def test_scheduler_jax_executor_greedy_equivalence():
    """End to end: the continuous-batching scheduler over the real paged
    executor must emit, per request, exactly the greedy tokens of a
    standalone dense prefill+decode loop — with variable prompt lengths,
    staggered finishes, and slots being recycled mid-run."""
    cfg, _, sch, reqs = _small_jax_serve()
    params_key = jax.random.PRNGKey(0)
    rep = sch.run(reqs)
    assert all(r.state is ReqState.DONE for r in reqs)
    assert rep.max_concurrent == 2       # slots recycled across 3 requests

    params = T.init_model(params_key, cfg)   # JaxExecutor used seed=0 too
    for r in reqs:
        ref = _dense_greedy(cfg, params, r.prompt, r.max_new_tokens)
        assert r.tokens == ref, f"request {r.rid} diverged"


def test_decode_step_touches_only_new_rows():
    """The executor's decode step writes each slot's new K/V row into the
    stacked pools and gathers from them in place: no value of the lowered
    or the compiled step has a whole layer pool's shape (no slice of a
    layer's pool, no write-back, no copy), and the donated pools alias
    the step's outputs."""
    _, ex, _, _ = _small_jax_serve()
    layer = ex.pools[0]["k"].shape[1:]
    assert ex.pools[0]["k"].dtype == jnp.bfloat16
    n = ex.max_slots
    lowered = ex._decode.lower(
        params=ex.params, pools=ex.pools, block_tables=jnp.asarray(ex.tables),
        tokens=jnp.zeros((n, 1), jnp.int32), pos=jnp.zeros((n,), jnp.int32))
    compiled = lowered.compile()
    for text, shape in [
            (lowered.as_text(), "tensor<" + "x".join(map(str, layer)) + "xbf16>"),
            (compiled.as_text(), "bf16[" + ",".join(map(str, layer)) + "]")]:
        whole = [ln.strip() for ln in text.splitlines() if shape in ln]
        assert not whole, whole[:3]
    pool_bytes = sum(x.nbytes for x in jax.tree.leaves(ex.pools))
    assert compiled.memory_analysis().alias_size_in_bytes >= pool_bytes


def test_jax_executor_decode_donates_pools():
    """Every decode hands the executor's pools to the step, which deletes
    the buffers it was given, and the pools it returns carry on: a prefill
    into the second slot between decodes of the first, then decodes of
    both, still give each request the dense greedy tokens."""
    cfg, ex, _, _ = _small_jax_serve()
    BS = ex.block_size
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab, (L,)).astype(np.int32)
               for L in (5, 3)]
    free = iter(range(1, 1 + 2 * ex.max_blocks))
    held, out = [], []

    def prefill(slot):
        L = len(prompts[slot])
        held.append([next(free) for _ in range(blocks_needed(L, BS))])
        out.append([ex.prefill(slot, held[slot], prompts[slot])])

    def decode(slots):
        pos = [len(prompts[s]) + len(out[s]) - 1 for s in slots]
        for s, p in zip(slots, pos):
            if p // BS >= len(held[s]):
                held[s].append(next(free))
                ex.extend(s, held[s][-1])
        before = ex.pools[0]["k"]
        nxt = ex.decode(slots, [out[s][-1] for s in slots], pos)
        assert before.is_deleted()
        assert not ex.pools[0]["k"].is_deleted()
        for s, t in zip(slots, nxt):
            out[s].append(t)

    prefill(0)
    for _ in range(3):
        decode([0])
    prefill(1)
    for _ in range(5):
        decode([0, 1])
    assert [len(o) for o in out] == [9, 6]
    for slot, (prompt, toks) in enumerate(zip(prompts, out)):
        ref = _dense_greedy(cfg, ex.params, prompt, len(toks))
        assert toks == ref, f"slot {slot} diverged"


def test_served_path_spans_land_in_the_profilers_trace(tmp_path):
    """Under the profiler every scheduler step carries its admit, price
    and retire spans, each decode call its four phases between price and
    retire, all in order and without overlap; tracing changes no token."""
    import glob
    import re
    from unittest import mock

    from jax.profiler import ProfileData

    *_, sch, plain = _small_jax_serve()
    sch.run(plain)
    *_, ex, sch, reqs = _small_jax_serve()
    with mock.patch.object(ex, "decode", wraps=ex.decode) as dec, \
            jax.profiler.trace(str(tmp_path)):
        rep = sch.run(reqs)
    assert [r.tokens for r in reqs] == [r.tokens for r in plain]

    pb, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                   for p in ProfileData.from_file(pb).planes
                   if p.name.startswith("/host:")
                   for line in p.lines for e in line.events
                   if e.name.startswith("repro."))
    assert all(b <= a2 for (_, b, _), (a2, _, _) in zip(spans, spans[1:]))
    decode = ("repro.decode.inputs repro.decode.launch "
              "repro.decode.sample repro.decode.readback ")
    step = re.compile(f"repro.sched.admit repro.sched.price "
                      f"(?:{decode})?repro.sched.retire ")
    names = "".join(n + " " for _, _, n in spans)
    assert len(step.findall(names)) == rep.steps
    assert step.sub("", names) == ""
    assert names.count(decode) == dec.call_count > 0


# ---------------------------------------------------------------------- #
# DeepSeek-V2: the latent pool and the absorbed decode
# ---------------------------------------------------------------------- #

def _mla_cfg(held=None, dtype="bfloat16"):
    import dataclasses
    cfg = get_config("deepseek_v2_lite_16b", smoke=True)
    return dataclasses.replace(
        cfg, dtype=dtype, moe=dataclasses.replace(cfg.moe, n_held=held))


def test_latent_pool_holds_576_values_a_token_a_layer():
    """At DeepSeek-V2-Lite's widths each run's pool holds, per layer and
    token, the 512-wide latent and the 64-wide rope key and nothing else:
    no head axis, 27 x 576 bfloat16 values a token."""
    cfg = get_config("deepseek_v2_lite_16b")
    nb, BS = 2065, 16
    pools = jax.eval_shape(lambda: T.init_paged_pools(cfg, nb, BS))
    assert [(n, {k: v.shape for k, v in p.items()})
            for (_, n), p in zip(cfg.runs(), pools)] == [
        (n, {"c": (n, nb, BS, 512), "pe": (n, nb, BS * 64)})
        for n in (1, 26)]
    per_token = sum(x.size for x in jax.tree.leaves(pools)) // (nb * BS)
    assert per_token == 27 * 576
    assert {x.dtype for x in jax.tree.leaves(pools)} == {jnp.dtype("bfloat16")}


def _avals(jaxpr):
    """Every value a jaxpr and the jaxprs inside it compute."""
    for eqn in jaxpr.eqns:
        yield from (v.aval for v in eqn.outvars)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _avals(sub)


def _holds_heads_of_context(avals, S, H, d):
    """Whether some value has a heads axis and as many values as every
    head's key or value of an S-token context."""
    return any(H in getattr(a, "shape", ()) and a.size >= S * H * d
               for a in avals)


def test_absorbed_decode_never_decompresses_the_cache():
    """The paged decode step reads only latent rows: no value in it holds
    per-head keys or values of the context, while the prefill, which
    decompresses them, does."""
    cfg = _mla_cfg()
    H, a = cfg.n_heads, cfg.mla
    d = min(a.qk_nope_dim, a.v_head_dim)
    BS, MB = 8, 32  # the context outweighs any weight with a heads axis
    S = MB * BS
    params = jax.eval_shape(lambda: T.init_model(KEY, cfg))
    pools = jax.eval_shape(lambda: T.init_paged_pools(cfg, 1 + MB, BS))
    i32 = jnp.int32
    decode = jax.make_jaxpr(lambda p, pl: T.decode_step_paged(
        p, cfg, pl, jnp.zeros((1, MB), i32), jnp.zeros((1, 1), i32),
        jnp.zeros((1,), i32)))(params, pools)
    prefill = jax.make_jaxpr(lambda p: T.prefill(
        p, cfg, {"tokens": jnp.zeros((1, S), i32)}, S))(params)
    assert not _holds_heads_of_context(_avals(decode.jaxpr), S, H, d)
    assert _holds_heads_of_context(_avals(prefill.jaxpr), S, H, d)


def test_jax_executor_serves_mla_with_latent_counters():
    """The scheduler over the real executor serves the MLA stack (a share
    of 2 of 8 experts held) through its latent pools: every request's
    tokens are a standalone dense prefill + decode loop's greedy tokens,
    and the executor counts the latent rows each decode call gathers and
    the rows that hold a decoded slot's tokens."""
    cfg = _mla_cfg(held=2, dtype="float32")
    BS, s_max, slots = 4, 24, 2
    reqs = make_requests([0.0] * 3, vocab=cfg.vocab, prompt_len=4,
                         gen_len=4, seed=0)
    rng = np.random.default_rng(0)
    for r, n, g in zip(reqs, [3, 8, 5], [6, 3, 5]):
        r.prompt = rng.integers(0, cfg.vocab, (n,)).astype(np.int32)
        r.max_new_tokens = g
    ex = JaxExecutor(cfg, None, n_blocks=1 + slots * (s_max // BS),
                     block_size=BS, max_slots=slots,
                     max_blocks=s_max // BS, seed=0)
    ex.params = jax.tree.map(lambda x: x.astype(jnp.float32), ex.params)
    calls, live = [], 0
    decode = ex.decode

    def counted(slots_, tokens, pos):
        nonlocal live
        calls.append(len(slots_))
        live += sum(p + 1 for p in pos)
        return decode(slots_, tokens, pos)

    ex.decode = counted
    sch = Scheduler(ex, n_blocks=1 + slots * (s_max // BS), block_size=BS,
                    max_slots=slots, s_max=s_max, prefill_token_budget=16)
    sch.run(reqs)
    assert all(r.state is ReqState.DONE for r in reqs)
    for r in reqs:
        assert r.tokens == _dense_greedy(cfg, ex.params, r.prompt,
                                         r.max_new_tokens), r.rid
    m = ex.metrics
    assert m.counter("repro.decode.calls").value == len(calls)
    assert m.counter("repro.mla.latent_rows_gathered").value == \
        len(calls) * 3 * slots * s_max
    assert m.counter("repro.mla.latent_rows_live").value == 3 * live
