"""A serving cell: the program's ``Scheduler.run`` over its ``JaxExecutor``,
closed loop, timed on the host clock around each executor call.

Wiring follows ``launch/serve.serve``: a priority ``Engine`` over the
``sim`` communicator of the mesh prices each step's request gathers against
the periodic weight broadcast, so that host work is on the timed path.  The
slots, blocks and prefill budget are the traffic file's.

Closed loop: every request is queued at arrival 0 and ``max_slots`` is the
client count, so a finished request's slot goes to the next one.  The
first ``clients`` requests count as sent when the scheduler starts, and
request ``n`` after them as sent when the ``(n - clients)``-th slot was
released (admission is first come, first served).  The window opens after
``warmup_steps`` decode calls and closes at the first executor call after
``seconds`` more; that call raises :class:`Stop` out of the scheduler.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time

import numpy as np

from bench import harness, loadgen


class Stop(Exception):
    """Raised out of the scheduler at the first call after the window."""


@dataclasses.dataclass
class Call:
    kind: str          # "prefill" | "decode"
    t0: float
    t1: float
    size: int          # prompt length, or slots decoded


class TimedExecutor:
    """Delegates every call to the program's executor; stamps each prefill
    and decode on ``clock`` and keeps each request's token times.

    ``open_window(t)`` is called once, after the ``warmup_steps``-th decode
    returns; it returns the window's start and sets its close.
    """

    def __init__(self, inner, requests, *, warmup_steps: int,
                 seconds: float, clock=time.perf_counter,
                 annotate=None, open_window=None):
        self.inner = inner
        self.block_size = inner.block_size
        self.clock = clock
        self.annotate = annotate or (lambda name: contextlib.nullcontext())
        self.warmup_steps = warmup_steps
        self.seconds = seconds
        self.open_window = open_window or (lambda: clock())
        self.rid_of = {id(r.prompt): r.rid for r in requests}
        self.max_new = {r.rid: r.max_new_tokens for r in requests}
        self.calls: list[Call] = []
        self.tok_times: dict[int, list[float]] = {}
        self.releases: list[float] = []
        self.evicted: dict[int, float] = {}
        self.slot_rid: dict[int, int] = {}
        self.n_decodes = 0
        self.start = clock()
        self.window: tuple[float, float] | None = None

    def _gate(self):
        if self.window is not None and self.clock() >= self.window[1]:
            raise Stop

    def prefill(self, slot, blocks, tokens):
        self._gate()
        rid = self.rid_of[id(tokens)]
        t0 = self.clock()
        with self.annotate(f"bench.prefill.{len(tokens)}"):
            tok = self.inner.prefill(slot, blocks, tokens)
        t1 = self.clock()
        self.calls.append(Call("prefill", t0, t1, int(len(tokens))))
        self.slot_rid[slot] = rid
        self.tok_times[rid] = [t1]
        return tok

    def decode(self, slots, tokens, pos):
        self._gate()
        t0 = self.clock()
        with self.annotate("bench.decode"):
            out = self.inner.decode(slots, tokens, pos)
        t1 = self.clock()
        self.calls.append(Call("decode", t0, t1, len(slots)))
        for s in slots:
            self.tok_times[self.slot_rid[s]].append(t1)
        self.n_decodes += 1
        if self.window is None and self.n_decodes >= self.warmup_steps:
            w0 = self.open_window()
            self.window = (w0, w0 + self.seconds)
        return out

    def extend(self, slot, block):
        self.inner.extend(slot, block)

    def release(self, slot):
        self.inner.release(slot)
        t = self.clock()
        rid = self.slot_rid.pop(slot, None)
        if rid is not None and len(self.tok_times[rid]) < self.max_new[rid]:
            self.evicted[rid] = t
        self.releases.append(t)


def window_stats(tx: TimedExecutor, clients: int) -> dict:
    """Tokens, inter-token gaps and TTFTs inside the window.

    A gap counts when its later token lands in the window (its earlier
    token may precede it); a TTFT counts when the first token lands in the
    window."""
    w0, w1 = tx.window
    inside = lambda t: w0 < t <= w1
    tokens, gaps, ttft = 0, [], []
    for rid, ts in tx.tok_times.items():
        tokens += sum(1 for t in ts if inside(t))
        gaps += [b - a for a, b in zip(ts, ts[1:]) if inside(b)]
        if inside(ts[0]):
            ttft.append(ts[0] - sent_time(tx, rid, clients))
    failed = sum(1 for t in tx.evicted.values() if inside(t))
    attempted = sum(1 for ts in tx.tok_times.values()
                    if any(inside(t) for t in ts)) + failed
    return {"tokens": tokens, "gaps": gaps, "ttft": ttft,
            "attempted": attempted, "failed": failed}


def sent_time(tx: TimedExecutor, rid: int, clients: int) -> float:
    return tx.start if rid < clients else tx.releases[rid - clients]


def finished_in_window(tx: TimedExecutor) -> list[int]:
    w0, w1 = tx.window
    return [rid for rid, ts in tx.tok_times.items()
            if len(ts) == tx.max_new[rid] and ts[-1] <= w1
            and rid not in tx.evicted]


def correctness_sample(tx: TimedExecutor, seed: int, target: int) -> list:
    """Finished requests to check: the one with the most served tokens,
    then others drawn from the seed until ``target`` tokens are in."""
    done = finished_in_window(tx)
    if not done:
        return []
    longest = max(done, key=lambda r: (tx.max_new[r], -r))
    rest = [r for r in done if r != longest]
    order = loadgen.rng_for(seed, 3).permutation(len(rest))
    picked, n = [longest], tx.max_new[longest]
    for i in order:
        if n >= target:
            break
        picked.append(rest[i])
        n += tx.max_new[rest[i]]
    return picked


# ---------------------------------------------------------------------- #
# The cell
# ---------------------------------------------------------------------- #

def build(cell, seed: int, mesh):
    """The program's executor and scheduler for this cell, serving the
    seed's weights from ``bench/weights.py``, and the requests."""
    from unittest import mock

    import jax
    from repro.core.engine import Engine
    from repro.launch.mesh import mesh_communicator
    from repro.models import transformer as T
    from repro.serving import (JaxExecutor, Request, Scheduler, SLO,
                               default_compute_model)

    from bench import weights

    c, mix = cell.config, cell.traffic
    cfg = harness.model_config(c)
    bs = int(mix["block_size"])
    clients = int(mix["clients"])
    s_max = max(mix["prompt_lens"]) + int(mix["output"]["max"])
    s_max += (-s_max) % bs
    n_blocks = 1 + int(mix["kv_pool_tokens"]) // bs
    params = weights.make_params(c, seed)
    want = jax.tree.structure(jax.eval_shape(
        lambda: T.init_model(jax.random.PRNGKey(0), cfg)))
    if jax.tree.structure(params) != want:
        raise RuntimeError("the weights' tree differs from the program's "
                           f"init_model: {want}")
    # the executor makes its weights with init_model: hand it these
    with mock.patch.object(T, "init_model", lambda key, cfg: params):
        ex = JaxExecutor(cfg, mesh, n_blocks=n_blocks, block_size=bs,
                         max_slots=clients, max_blocks=s_max // bs)
    wbytes = float(sum(x.size * x.dtype.itemsize
                       for x in jax.tree.leaves(params)))
    pods = mesh.shape.get("pod", 1)
    data = mesh.shape.get("data", 1)
    model = mesh.shape.get("model", 1)
    wcomm = mesh_communicator(mesh, backend="sim", policy="paper")
    replicas = [tuple(range(g * model, (g + 1) * model))
                for g in range(pods * data)]
    eng = Engine(wcomm, policy="priority", age_rate=wbytes)
    sch = Scheduler(
        ex, n_blocks=n_blocks, block_size=bs, max_slots=clients,
        s_max=s_max, policy="priority",
        prefill_token_budget=int(mix["prefill_token_budget"]),
        compute_model=default_compute_model(cfg.active_param_count(),
                                            model_size=model),
        engine=eng, replicas=replicas, weight_bytes=wbytes,
        gather_bytes=float(cfg.d_model * 2) / model, bcast_every=16)
    reqs = [Request(rid=i, arrival_s=0.0, prompt=r["prompt"],
                    max_new_tokens=r["max_new"], slo=SLO())
            for i, r in enumerate(loadgen.serve_requests(mix, cfg.vocab,
                                                         seed))]
    return ex, sch, reqs


def warm_up(ex, mix: dict) -> None:
    """Compile every program the window drives: one prefill per prompt
    length (with its scatter into blocks) and the decode step over every
    slot.  The slots are released again."""
    bs = ex.block_size
    for L in mix["prompt_lens"]:
        nb = L // bs
        ex.prefill(0, list(range(1, nb + 1)), np.zeros(L, np.int32))
    slots = list(range(ex.max_slots))
    for s in slots:
        ex.tables[s, 0] = 1
    ex.decode(slots, [0] * len(slots), [1] * len(slots))
    for s in slots:
        ex.release(s)


def run(cell, seed: int, seconds: float, mesh, *, tracer=None,
        wrap_executor=None) -> dict:
    """Set up, warm up, serve until the window closes.  Returns the timed
    executor, the requests and the set-up end time.  ``wrap_executor``
    lets a test break the executor underneath the scheduler."""
    import jax

    mix = cell.traffic
    ex, sch, reqs = build(cell, seed, mesh)
    with jax.set_mesh(mesh):
        warm_up(ex, mix)
    inner = wrap_executor(ex) if wrap_executor else ex
    setup_end = {}

    def open_window():
        if tracer is not None:
            tracer.start()
        setup_end["t"] = time.perf_counter()
        return setup_end["t"]

    tx = TimedExecutor(inner, reqs, warmup_steps=int(mix["warmup_steps"]),
                       seconds=seconds,
                       annotate=tracer.annotate if tracer else None,
                       open_window=open_window)
    sch.ex = tx
    try:
        with jax.set_mesh(mesh):
            sch.run(reqs)
    except Stop:
        pass
    finally:
        if tracer is not None and tracer.active:
            tracer.stop()
    if tx.window is None or tx.clock() < tx.window[1]:
        raise RuntimeError("the traffic ran out before the window closed: "
                           "raise the mix's 'requests'")
    return {"tx": tx, "executor": ex, "requests": reqs,
            "window_start": setup_end["t"]}


def check(cell, seed: int, out: dict, control: bool = False) -> dict:
    """Readings for ``correct``: the widest gap of a served token below the
    reference's best logit over the sample.  ``control`` adds the widest
    gap of the tokens the float8 reference puts first on the same prompts
    and tokens (``control_max_logit_gap``)."""
    ref = harness.reference_module(cell.config)
    tx, reqs = out["tx"], out["requests"]
    by_rid = {r.rid: r for r in reqs}
    sample = correctness_sample(tx, seed, int(cell.traffic["check_tokens"]))
    if not sample:
        return {}
    items = [(by_rid[r].prompt, list(by_rid[r].tokens)) for r in sample]
    gaps, ctrl = ref.served_gaps(cell.config, seed, items, control=control)
    got = {"max_logit_gap": float(max(g.max() for g in gaps))}
    if control:
        got["control_max_logit_gap"] = float(max(g.max() for g in ctrl))
        got["served_tokens_checked"] = float(sum(len(g) for g in gaps))
    return got
