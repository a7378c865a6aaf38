"""Tests for the unified Communicator API: op dispatch, plan caching,
tree_rounds properties, sim equivalence, and cross-backend agreement."""
import pytest

from repro.core import Communicator, OPS, SimResult, Tree, size_bucket
from repro.core import schedule as S
from repro.core.simulator import simulate
from repro.core.topology import Topology, WAN, LAN, SMP, paper_fig8_topology
from repro.core.trees import (binomial_tree, build_multilevel_tree,
                              chain_tree, flat_tree, postal_tree,
                              PAPER_POLICY)
from repro.core.tree_exec import tree_rounds


@pytest.fixture(scope="module")
def fig8():
    return paper_fig8_topology()


# ------------------------------------------------------------------ #
# tree_rounds properties across every builder (satellite coverage).
# ------------------------------------------------------------------ #

def _round_trees(n=23):
    topo = paper_fig8_topology()
    members = list(range(n))
    return {
        "flat": flat_tree(0, members),
        "binomial": binomial_tree(3, members),
        "chain": chain_tree(0, members),
        "postal2": postal_tree(0, members, lam=2),
        "postal5": postal_tree(5, members, lam=5),
        "multilevel": build_multilevel_tree(topo, 7),
    }


@pytest.mark.parametrize("kind", list(_round_trees()))
def test_tree_rounds_properties(kind):
    """Rounds have disjoint (src,dst) pairs, every non-root rank receives
    exactly once, and parents never inject before they have received."""
    tree = _round_trees()[kind]
    rounds = tree_rounds(tree)
    recv_round = {tree.root: -1}
    for r, edges in enumerate(rounds):
        assert edges, f"empty round {r}"
        srcs = [s for s, _ in edges]
        dsts = [d for _, d in edges]
        # disjointness: one injection per sender, one receive per dst
        assert len(srcs) == len(set(srcs)), (kind, r, "double injection")
        assert len(dsts) == len(set(dsts)), (kind, r, "double receive")
        assert not set(srcs) & set(dsts), (kind, r, "rank sends and receives")
        for s, d in edges:
            assert s in recv_round and recv_round[s] < r, \
                (kind, r, "parent injects before receiving")
            assert d not in recv_round, (kind, r, "duplicate receive")
            recv_round[d] = r
    assert set(recv_round) == set(tree.members())
    # edge set is exactly the tree's edges
    flat = {e for edges in rounds for e in edges}
    assert flat == {(p, c) for p, cs in tree.children.items() for c in cs}


def test_tree_rounds_deep_chain():
    """The iterative schedule/simulator paths survive very deep trees."""
    n = 3000
    t = chain_tree(0, range(n))
    assert t.depth() == n - 1
    assert len(t.subtree_sizes()) == n
    topo = Topology([[0]] * n, [WAN, SMP])
    done = simulate(S.reduce(t, 1e3), topo)  # recursive version overflowed
    assert len(done) == n


def test_validate_raises_value_error():
    """Tree.validate must raise real exceptions, not bare asserts — and must
    terminate (with an error) on cyclic children maps."""
    with pytest.raises(ValueError, match="invalid tree"):
        Tree(0, {0: [1], 1: [0]}).validate()  # cycle
    with pytest.raises(ValueError, match="invalid tree"):
        Tree(0, {0: [1, 1]}).validate()       # duplicate child
    with pytest.raises(ValueError, match="root .* has a parent"):
        Tree(0, {0: [1], 2: [0, 1]}).validate()
    good = binomial_tree(0, range(8))
    good.validate()  # no raise


# ------------------------------------------------------------------ #
# Sim backend: equivalence with direct schedule + simulate calls.
# ------------------------------------------------------------------ #

def test_sim_backend_matches_direct_calls(fig8):
    """The sim backend executes the plan's LOWERED rounds IR: results equal
    a direct lower + simulate_rounds of the same plan, and for unsegmented
    tree plans the overall time stays equivalent to the whole-message
    schedule simulation (the IR only refines per-rank sender accounting)."""
    from repro.core.simulator import simulate_rounds

    comm = Communicator(fig8, policy="paper", backend="sim")
    tree = build_multilevel_tree(fig8, 5, policy=PAPER_POLICY)
    for op, nb in [("bcast", 64e3), ("reduce", 1e3), ("gather", 16e3),
                   ("scatter", 16e3), ("allreduce", 64e3),
                   ("allgather", 4e3)]:
        spec = OPS[op]
        res = (getattr(comm, op)(nb, root=5) if spec.rootful
               else comm._run(op, nb, 5))
        assert isinstance(res, SimResult)
        plan = comm.plan(op, root=5, nbytes=nb)
        assert plan.tree.children == tree.children, op
        assert plan.algorithm == "tree" and plan.segment is None, op
        direct = simulate_rounds(plan.lower(nb), fig8)
        assert res.completion == direct, op
        if op in ("bcast", "reduce", "allreduce"):
            sched_t = max(simulate(getattr(S, op)(tree, nb), fig8).values())
            # fold-drain order at a receiver differs (emission vs child
            # order), shifting per-message overheads only
            assert res.time == pytest.approx(sched_t, rel=5e-3), op
    b = comm._run("barrier", None, 5)
    assert b.completion == simulate_rounds(
        comm.plan("barrier", root=5).lower(0.0), fig8)


def test_all_seven_ops_dispatch(fig8):
    comm = Communicator(fig8, policy="auto", backend="sim")
    assert set(OPS) == {"bcast", "reduce", "barrier", "gather", "scatter",
                        "allreduce", "allgather"}
    times = {}
    for op in OPS:
        if op == "barrier":
            times[op] = comm.barrier().time
        elif OPS[op].rootful:
            times[op] = getattr(comm, op)(8e3, root=0).time
        else:
            times[op] = getattr(comm, op)(8e3).time
    assert all(t > 0 for t in times.values()), times


def test_unknown_op_and_backend_rejected(fig8):
    with pytest.raises(KeyError):
        Communicator(fig8).plan("alltoall")
    with pytest.raises(ValueError, match="unknown backend"):
        Communicator(fig8, backend="mpi")
    with pytest.raises(ValueError, match="not a member"):
        Communicator(fig8, members=[0, 1, 2]).bcast(1e3, root=40)


# ------------------------------------------------------------------ #
# Plan cache: repeat calls must re-run nothing.
# ------------------------------------------------------------------ #

def test_plan_cache_hit_builds_nothing(fig8):
    comm = Communicator(fig8, policy="auto", backend="sim")
    comm.bcast(64e3, root=0)
    info1 = comm.cache_info()
    assert info1.misses == 1 and info1.tree_builds == 3  # auto: 3 candidates
    r2 = comm.bcast(64e3, root=0)
    info2 = comm.cache_info()
    assert info2.hits == info1.hits + 1
    assert info2.tree_builds == info1.tree_builds, "second call rebuilt trees"
    assert r2.time > 0
    # same size-bucket, different exact size: still a plan hit
    comm.bcast(65e3, root=0)
    assert comm.cache_info().tree_builds == info1.tree_builds
    # different root or op: new plan
    comm.bcast(64e3, root=1)
    comm.reduce(64e3, root=0)
    assert comm.cache_info().tree_builds > info1.tree_builds


def test_plan_identity_and_rounds_cached(fig8):
    # size-independent policy: ONE plan per (op, root), any message size —
    # so plan() inspection and a later execution share the cache entry
    comm = Communicator(fig8, policy="paper")
    p1 = comm.plan("bcast", root=0, nbytes=17e3)
    p2 = comm.plan("bcast", root=0, nbytes=900e3)
    assert p1 is p2
    # size-dependent policy: one plan per size octave
    ad = Communicator(fig8, policy="adaptive")
    assert ad.plan("bcast", root=0, nbytes=17e3) is \
        ad.plan("bcast", root=0, nbytes=20e3)
    assert ad.plan("bcast", root=0, nbytes=17e3) is not \
        ad.plan("bcast", root=0, nbytes=900e3)
    r1 = p1.rounds
    assert p1.rounds is r1  # memoised
    assert p1.schedule(32e3) is p1.schedule(32e3)


def test_size_bucket():
    assert size_bucket(0) == -1 and size_bucket(None) == -1
    assert size_bucket(1) == 0
    assert size_bucket(1024) == size_bucket(2000) == 10
    assert size_bucket(2048) == 11


def test_size_bucket_boundaries():
    """Satellite coverage: the degenerate and boundary inputs."""
    assert size_bucket(-1) == -1 and size_bucket(-0.5) == -1
    # sub-2-byte payloads clamp into bucket 0 (log2 < 1 -> int -> <= 0)
    assert size_bucket(0.25) == 0
    assert size_bucket(0.5) == 0
    assert size_bucket(1.0) == 0
    assert size_bucket(1.999) == 0
    # exact powers of two open their own octave
    for k in (1, 2, 10, 20, 30):
        assert size_bucket(2.0 ** k) == k
        assert size_bucket(2.0 ** k - 1) == k - 1
        assert size_bucket(2.0 ** k + 1) == k


def test_plan_cache_eviction_order_and_clear_stats():
    from repro.core import PlanCache

    cache = PlanCache(maxsize=2)
    built = []

    def make(tag):
        def build():
            built.append(tag)
            return tag  # the cache is value-agnostic
        return build

    cache.get_or_build("a", make("a"))
    cache.get_or_build("b", make("b"))
    cache.get_or_build("a", make("a"))          # hit: refreshes a's LRU slot
    cache.get_or_build("c", make("c"))          # evicts b (LRU), not a
    assert built == ["a", "b", "c"]
    cache.get_or_build("a", make("a2"))
    assert built == ["a", "b", "c"]             # a survived the eviction
    cache.get_or_build("b", make("b2"))         # b was evicted: rebuilt
    assert built == ["a", "b", "c", "b2"]
    assert cache.hits == 2 and cache.misses == 4
    cache.clear()
    assert len(cache) == 0
    assert cache.hits == 0 and cache.misses == 0
    assert cache.maxsize == 2                   # capacity is configuration
    cache.get_or_build("a", make("a3"))
    assert cache.misses == 1 and cache.hits == 0


def test_per_call_policy_never_served_stale_plan(fig8):
    """Regression: the cache key omitted the policy, so a per-call
    ``policy=`` override could be handed a plan built under the
    communicator's default policy (and vice versa)."""
    comm = Communicator(fig8, policy="paper", backend="sim")
    p_paper = comm.plan("bcast", root=0, nbytes=64e3)
    p_obliv = comm.plan("bcast", root=0, nbytes=64e3, policy="oblivious")
    assert p_obliv is not p_paper
    assert p_obliv.tree.children != p_paper.tree.children
    # the paper plan crosses the WAN once; the oblivious binomial does not
    wan = lambda t: sum(1 for p, cs in t.children.items() for c in cs
                        if fig8.comm_level(p, c) == 0)
    assert wan(p_paper.tree) == 1 and wan(p_obliv.tree) > 1
    # both entries coexist: repeat calls hit their own entry
    assert comm.plan("bcast", root=0, nbytes=64e3) is p_paper
    assert comm.plan("bcast", root=0, nbytes=64e3,
                     policy="oblivious") is p_obliv
    # an explicit override equal to the default shares the default entry
    assert comm.plan("bcast", root=0, nbytes=64e3, policy="paper") is p_paper
    # per-call size-dependent policies bucket by size even when the
    # communicator default would not
    a1 = comm.plan("bcast", root=0, nbytes=17e3, policy="adaptive")
    a2 = comm.plan("bcast", root=0, nbytes=900e3, policy="adaptive")
    assert a1 is not a2


def test_stats_counts_hits_misses_evictions_repairs(fig8):
    """Satellite: Communicator.stats() exposes plan reuse as counters so
    the engine and benchmarks can ASSERT it instead of timing it."""
    comm = Communicator(fig8, policy="paper", backend="sim", cache_size=2)
    comm.bcast(64e3, root=0)
    comm.bcast(64e3, root=0)
    st = comm.stats()
    assert (st.hits, st.misses, st.evictions) == (1, 1, 0)
    assert st.tree_builds == 1 and st.repairs == 0
    assert (st.currsize, st.maxsize) == (1, 2)
    comm.bcast(64e3, root=1)
    comm.bcast(64e3, root=2)      # capacity 2: evicts the root-0 plan
    assert comm.stats().evictions == 1
    comm.bcast(64e3, root=0)      # rebuilt: a miss, not a hit
    st = comm.stats()
    assert st.misses == 4 and st.evictions == 2
    comm.repair(failed=[40])
    assert comm.stats().repairs == 1
    comm.repair(failed=[40])      # already gone: not a repair
    assert comm.stats().repairs == 1
    # cache_info() keeps its legacy shape
    ci = comm.cache_info()
    assert (ci.hits, ci.misses) == (st.hits, st.misses)


def test_stats_monotonic_and_tree_builds_exactly_accounted():
    """Regression (observability): every CommStats counter is monotone
    across the full elastic lifecycle, and ``tree_builds`` is EXACTLY
    accounted — under a fixed policy it equals the miss count (one tree
    per build), repair() splices without building, refresh() invalidates
    without building (the rebuild is charged to the next miss), and a
    capacity eviction charges one rebuild when the victim re-plans."""
    import dataclasses

    import repro.core.discovery as D

    topo = paper_fig8_topology()   # private copy: refresh mutates levels
    comm = Communicator(topo, policy="paper", backend="sim", cache_size=2)
    prev = comm.stats()

    def step(expect_builds):
        nonlocal prev
        st = comm.stats()
        for f in ("hits", "misses", "evictions", "tree_builds", "repairs"):
            assert getattr(st, f) >= getattr(prev, f), (f, prev, st)
        # the exact identity: policy="paper" builds ONE tree per miss
        assert st.tree_builds == st.misses == expect_builds, (prev, st)
        prev = st
        return st

    comm.plan("bcast", root=0, nbytes=64e3)
    comm.plan("bcast", root=1, nbytes=64e3)
    step(2)
    comm.plan("bcast", root=0, nbytes=64e3)           # hit
    assert step(2).hits == 1

    rep = comm.repair(failed=[40])                    # splice, not rebuild
    assert rep.repaired == 2 and rep.evicted == 0
    assert step(2).repairs == 1
    comm.plan("bcast", root=0, nbytes=64e3)           # repaired plan: a hit
    assert step(2).hits == 2

    drifted = Topology(topo.coords, [dataclasses.replace(
        topo.levels[0], latency=topo.levels[0].latency * 3)]
        + list(topo.levels[1:]))
    probes = D.targeted_probes(drifted,
                               D.representative_pairs(topo, comm.members))
    assert comm.refresh(probes).refreshed
    step(2)                                           # invalidate ≠ build
    comm.plan("bcast", root=0, nbytes=64e3)           # rebuild under new costs
    assert step(3).misses == 3

    comm.plan("bcast", root=1, nbytes=64e3)
    comm.plan("bcast", root=2, nbytes=64e3)           # capacity 2: evicts
    assert step(5).evictions == 1
    comm.plan("bcast", root=0, nbytes=64e3)           # victim re-plans
    st = step(6)
    assert st.evictions == 2 and st.hits == 2
    # the registry enforces monotonicity at the type level, not by promise
    with pytest.raises(ValueError, match="cannot decrease"):
        comm.metrics.counter("comm.tree_builds").inc(-1)


def test_nbytes_of_pinned_sizing_semantics(fig8):
    """Satellite: gather/allgather/scatter plans are sized by the PER-RANK
    contribution.  Scalars already mean that; a device-shaped scatter
    operand is the root's full [P, ...] buffer and must be divided down,
    while gather/allgather operands are the local shard (already
    per-rank)."""
    import numpy as np

    comm = Communicator(fig8, policy="paper", backend="sim")
    P = fig8.nprocs
    # scalars pass through for every sized op
    for op in ("bcast", "reduce", "allreduce", "gather", "scatter",
               "allgather"):
        assert comm._nbytes_of(op, 12345.0) == 12345.0
    assert comm._nbytes_of("barrier", 999.0) == 0.0
    assert comm._nbytes_of("bcast", None) == 0.0
    # device operands: local-shard bytes ...
    shard = np.zeros((64, 8), np.float32)
    assert comm._nbytes_of("gather", shard) == shard.nbytes
    assert comm._nbytes_of("allgather", shard) == shard.nbytes
    assert comm._nbytes_of("bcast", shard) == shard.nbytes
    # ... except scatter, whose operand aggregates all P chunks
    full = np.zeros((P, 64), np.float32)
    assert comm._nbytes_of("scatter", full) == full.nbytes / P
    # regression: the aggregate sizing put scatter plans P size-octaves
    # too high — per-rank sizing must land in the per-chunk bucket
    from repro.core import size_bucket
    assert size_bucket(comm._nbytes_of("scatter", full)) == \
        size_bucket(full.nbytes / P)
    sub = Communicator(fig8, policy="paper", backend="sim",
                       members=[0, 1, 2, 16])
    assert sub._nbytes_of("scatter", np.zeros((4, 10), np.float32)) == 40.0


def test_members_subset(fig8):
    members = [0, 1, 2, 16, 17, 32, 33]
    comm = Communicator(fig8, policy="paper", members=members)
    tree = comm.plan("bcast", root=16, nbytes=1e3).tree
    assert sorted(tree.members()) == sorted(members)
    assert tree.root == 16


def test_deprecated_best_tree_shim(fig8):
    from repro.core.trees import best_tree
    with pytest.warns(DeprecationWarning):
        t = best_tree(fig8, 0, "bcast", 64e3)
    t.validate()
    assert sorted(t.members()) == list(range(fig8.nprocs))


# ------------------------------------------------------------------ #
# Cross-backend agreement on a small device mesh (8 emulated devices).
# ------------------------------------------------------------------ #

def test_backend_agreement_on_mesh(subproc):
    subproc("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.launch.mesh import make_mesh
from repro.core import Communicator
from repro.core.topology import tpu_v5e_multipod

topo = tpu_v5e_multipod(pods=2, boards=2, chips_per_board=2)
ROOT = 3
x_host = np.arange(8.0, dtype=np.float32)

# --- ppermute backend: explicit tree rounds over the flat axis ---------
mesh1 = make_mesh((8,), ("all",))
pp = Communicator(topo, policy="paper", backend="ppermute", axis="all")
def run_pp(fn):
    return np.asarray(jax.jit(shard_map(
        fn, mesh=mesh1, in_specs=P("all"), out_specs=P("all")))(
            jnp.asarray(x_host)))

# --- jax backend: axis-decomposed shortcuts over (pod, fast) -----------
mesh2 = make_mesh((2, 4), ("pod", "fast"))
jx = Communicator(topo, backend="jax", slow_axis="pod", fast_axes=("fast",))
def run_jx(fn):
    return np.asarray(jax.jit(shard_map(
        fn, mesh=mesh2, in_specs=P(("pod", "fast")),
        out_specs=P(("pod", "fast"))))(jnp.asarray(x_host)))

# --- sim backend: postal-model plan for the same topology --------------
sim = Communicator(topo, policy="paper", backend="sim")

# bcast
want = np.full(8, float(ROOT), np.float32)
np.testing.assert_allclose(run_pp(lambda v: pp.bcast(v, root=ROOT)), want)
np.testing.assert_allclose(run_jx(lambda v: jx.bcast(v, root=ROOT)), want)
# reduce (non-root ranks: zeros)
want = np.zeros(8, np.float32); want[ROOT] = x_host.sum()
np.testing.assert_allclose(run_pp(lambda v: pp.reduce(v, root=ROOT)), want)
np.testing.assert_allclose(run_jx(lambda v: jx.reduce(v, root=ROOT)), want)
# allreduce
want = np.full(8, x_host.sum(), np.float32)
np.testing.assert_allclose(run_pp(lambda v: pp.allreduce(v)), want)
np.testing.assert_allclose(run_jx(lambda v: jx.allreduce(v)), want)
# barrier returns a sync token; both must run without error
run_pp(lambda v: v + pp.barrier())
run_jx(lambda v: v + jx.barrier())

# gather/allgather/scatter: each rank's local output is a [P(,1)] buffer;
# shard_map concatenates them rank-major, so reshape to (rank, P).
pg = np.asarray(jax.jit(shard_map(lambda v: pp.gather(v, root=ROOT),
    mesh=mesh1, in_specs=P("all"), out_specs=P("all", None)))(
        jnp.asarray(x_host))).reshape(8, 8)
jg = np.asarray(jax.jit(shard_map(lambda v: jx.gather(v, root=ROOT),
    mesh=mesh2, in_specs=P(("pod", "fast")),
    out_specs=P(("pod", "fast"), None)))(jnp.asarray(x_host))).reshape(8, 8)
np.testing.assert_allclose(pg, jg)
np.testing.assert_allclose(pg[ROOT], x_host)   # root holds everything
np.testing.assert_allclose(pg[(ROOT + 1) % 8], np.zeros(8))  # non-root: 0
pa = np.asarray(jax.jit(shard_map(lambda v: pp.allgather(v),
    mesh=mesh1, in_specs=P("all"), out_specs=P("all", None)))(
        jnp.asarray(x_host))).reshape(8, 8)
ja = np.asarray(jax.jit(shard_map(lambda v: jx.allgather(v),
    mesh=mesh2, in_specs=P(("pod", "fast")),
    out_specs=P(("pod", "fast"), None)))(jnp.asarray(x_host))).reshape(8, 8)
np.testing.assert_allclose(pa, ja)
for row in pa:
    np.testing.assert_allclose(row, x_host)
# scatter: root's [P, P] buffer; rank r keeps row r, so the rank-major
# concatenation of local outputs reassembles the buffer itself.
buf = np.arange(64.0, dtype=np.float32).reshape(8, 8)
ps = np.asarray(jax.jit(shard_map(lambda v: pp.scatter(v, root=ROOT),
    mesh=mesh1, in_specs=P(None, None), out_specs=P("all")))(
        jnp.asarray(buf))).reshape(8, 8)
js = np.asarray(jax.jit(shard_map(lambda v: jx.scatter(v, root=ROOT),
    mesh=mesh2, in_specs=P(None, None),
    out_specs=P(("pod", "fast"))))(jnp.asarray(buf))).reshape(8, 8)
np.testing.assert_allclose(ps, buf)
np.testing.assert_allclose(js, buf)

# the sim backend plans the identical tree the ppermute backend executed
assert sim.plan("bcast", root=ROOT, nbytes=4.0).tree.children == \
    pp.plan("bcast", root=ROOT, nbytes=4.0).tree.children
assert sim.bcast(1e3, root=ROOT).time > 0
print("OK")
""")
