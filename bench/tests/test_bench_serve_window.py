"""The windowed serving metrics, on the CPU: the program's Scheduler over
its SimExecutor behind the benchmark's timed wrapper, on a fake clock
that advances a fixed time per prefill and per decode."""
import numpy as np
import pytest

from bench import loadgen, serve_cell

PREFILL_S, DECODE_S = 0.5, 0.125     # exact in binary


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


class Ticking:
    """SimExecutor whose calls take PREFILL_S and DECODE_S of the clock."""

    def __init__(self, clock):
        from repro.serving import SimExecutor
        self.sim = SimExecutor(vocab=64, block_size=4)
        self.block_size = 4
        self.clock = clock

    def prefill(self, slot, blocks, tokens):
        self.clock.t += PREFILL_S
        return self.sim.prefill(slot, blocks, tokens)

    def decode(self, slots, tokens, pos):
        self.clock.t += DECODE_S
        return self.sim.decode(slots, tokens, pos)

    def extend(self, slot, block):
        pass

    def release(self, slot):
        pass


def serve(max_new, clients=2, warmup=2, seconds=1.0, prompt=8, late=0.0):
    """Run until the window closes.  Every request has a prompt of
    ``prompt`` tokens and ``max_new[i]`` output tokens."""
    from repro.serving import Request, Scheduler
    clock = Clock()
    reqs = [Request(rid=i, arrival_s=0.0,
                    prompt=np.full(prompt, i, np.int32), max_new_tokens=m)
            for i, m in enumerate(max_new)]
    tx = serve_cell.TimedExecutor(Ticking(clock), reqs, warmup_steps=warmup,
                                  seconds=seconds, clock=clock,
                                  open_window=lambda: clock.t + late)
    sch = Scheduler(tx, n_blocks=64, block_size=4, max_slots=clients,
                    s_max=64, policy="priority", prefill_token_budget=64)
    with pytest.raises(serve_cell.Stop):
        sch.run(reqs)
    return tx


def test_window_opens_after_warmup_and_cuts_at_the_first_late_call():
    tx = serve([50, 50])
    # two prefills (1.0 s) then two decodes: the window opens at 101.25
    assert tx.window == (101.25, 102.25)
    # calls run until the first one that starts at or after the close
    assert tx.calls[-1].t0 < tx.window[1] <= tx.calls[-1].t1
    assert len([c for c in tx.calls if c.kind == "decode"]) == 2 + 8


def test_closed_loop_send_times():
    # request 0 is short: it frees its slot for request 2, which is sent
    # at that release; 1 and 0 were sent when the scheduler started
    tx = serve([2, 50, 50], warmup=1, seconds=3.0)
    assert serve_cell.sent_time(tx, 0, 2) == 100.0
    assert serve_cell.sent_time(tx, 1, 2) == 100.0
    # tokens of request 0: its prefill ends 100.5, the first decode 101.125
    assert tx.tok_times[0] == [100.5, 101.125]
    assert serve_cell.sent_time(tx, 2, 2) == 101.125
    # request 2 is admitted at the next step: its first token 0.5 s later
    assert tx.tok_times[2][0] == 101.625


def test_gaps_straddling_the_window_edge_count_by_their_later_token():
    # the window opens 1/16 s after the second decode: (101.3125, 101.5625]
    tx = serve([50, 50], warmup=2, seconds=0.25, late=0.0625)
    assert tx.window == (101.3125, 101.5625)
    assert tx.tok_times[0] == [100.5, 101.125, 101.25, 101.375, 101.5,
                               101.625]
    st = serve_cell.window_stats(tx, 2)
    # tokens at 101.375 and 101.5 of each request lie inside
    assert st["tokens"] == 4
    # 101.25 -> 101.375 straddles the opening and counts; 101.5 -> 101.625
    # ends after the close and does not
    assert sorted(st["gaps"]) == [0.125] * 4


def test_ttft_counts_only_first_tokens_inside_the_window():
    tx = serve([2, 2, 2, 2, 50, 50], clients=2, warmup=1, seconds=2.0)
    w0, w1 = tx.window
    st = serve_cell.window_stats(tx, 2)
    inside = [rid for rid, ts in tx.tok_times.items() if w0 < ts[0] <= w1]
    assert 0 not in inside and len(st["ttft"]) == len(inside) > 0
    want = sorted(tx.tok_times[r][0] - serve_cell.sent_time(tx, r, 2)
                  for r in inside)
    assert sorted(st["ttft"]) == pytest.approx(want)


def test_finished_sample_holds_the_longest():
    tx = serve([3, 9, 3, 3, 4, 50], clients=2, warmup=1, seconds=5.0)
    done = serve_cell.finished_in_window(tx)
    assert 1 in done
    pick = serve_cell.correctness_sample(tx, seed=5, target=12)
    assert pick[0] == max(done, key=lambda r: tx.max_new[r])
    assert sum(tx.max_new[r] for r in pick) >= 12 or set(pick) == set(done)


MIX = {"prompt_lens": [16, 32, 64], "prompt_weights": [0.3, 0.4, 0.3],
       "output": {"median": 8, "sigma": 0.8, "min": 2, "max": 24},
       "block": 16, "requests": 48}


def test_the_same_seed_gives_the_same_requests():
    a = loadgen.serve_requests(MIX, 512, seed=2 ** 33 + 9)
    b = loadgen.serve_requests(MIX, 512, seed=2 ** 33 + 9)
    assert len(a) == 48
    for x, y in zip(a, b):
        assert x["max_new"] == y["max_new"]
        np.testing.assert_array_equal(x["prompt"], y["prompt"])


def test_every_seed_gets_the_same_sizes_in_the_same_order():
    a = loadgen.serve_requests(MIX, 512, seed=1)
    b = loadgen.serve_requests(MIX, 512, seed=2)
    assert [(len(r["prompt"]), r["max_new"]) for r in a] == [
        (len(r["prompt"]), r["max_new"]) for r in b]
    assert any((x["prompt"] != y["prompt"]).any() for x, y in zip(a, b))
    # each block holds the stratified set: 30/40/30% of 16 prompts
    for lo in range(0, 48, 16):
        lens = sorted(len(r["prompt"]) for r in a[lo:lo + 16])
        assert lens == [16] * 5 + [32] * 6 + [64] * 5


def test_training_rows_differ_by_step_and_repeat_by_seed():
    job = {"seq_len": 32}
    r0 = loadgen.train_rows(job, 100, 7, 0, 4)
    assert r0.shape == (4, 33)
    np.testing.assert_array_equal(r0, loadgen.train_rows(job, 100, 7, 0, 4))
    assert not np.array_equal(r0, loadgen.train_rows(job, 100, 7, 1, 4))
    assert len({tuple(r) for r in r0}) == 4
