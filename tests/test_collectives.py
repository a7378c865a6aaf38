"""Multi-device collective tests (subprocess with 8 host devices)."""
import pytest


def test_multilevel_psum_equals_flat(subproc):
    subproc("""
import jax, jax.numpy as jnp, numpy as np
from jax import shard_map
from repro.launch.mesh import make_mesh
from jax.sharding import PartitionSpec as P
from repro.core.collectives import multilevel_psum_tree
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
grads = {"w": jnp.arange(24., dtype=jnp.float32).reshape(4, 6),
         "b": jnp.ones((3,))}
def sync(mode):
    f = lambda g: multilevel_psum_tree(g, "pod", ["data"], mode=mode)
    return jax.jit(shard_map(f, mesh=mesh, in_specs=P(), out_specs=P(),
                             check_vma=False))(grads)
flat, ml, mlc = sync("flat"), sync("multilevel"), sync("multilevel_compress")
np.testing.assert_allclose(flat["w"], np.asarray(grads["w"])*4, rtol=1e-6)
np.testing.assert_allclose(ml["w"], flat["w"], rtol=1e-6)
np.testing.assert_allclose(mlc["w"], flat["w"], atol=0.5)  # int8 rounding
np.testing.assert_allclose(ml["b"], flat["b"], rtol=1e-6)
print("OK")
""")


def test_bucketed_psum_tree_matches_monolithic(subproc):
    """The bucketed gradient sync changes COLLECTIVE GRANULARITY only:
    per-bucket fused buffers must reproduce the monolithic flat-buffer
    result for every bucket size, in flat and multilevel modes."""
    subproc("""
import jax, jax.numpy as jnp, numpy as np
from jax import shard_map
from repro.launch.mesh import make_mesh
from jax.sharding import PartitionSpec as P
from repro.core.collectives import bucketed_psum_tree, multilevel_psum_tree
mesh = make_mesh((2, 4), ("pod", "data"))
grads = {"w": jnp.arange(24., dtype=jnp.float32).reshape(4, 6),
         "b": jnp.ones((3,)), "c": [jnp.full((5,), 2.0),
                                    jnp.arange(7., dtype=jnp.float32)]}
def sync(fn):
    return jax.jit(shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                             check_vma=False))(grads)
mono = sync(lambda g: multilevel_psum_tree(g, "pod", ["data"], mean_over=8))
for mode in ("flat", "multilevel"):
    for bb in (16.0, 64.0, 1e9):  # per-leaf .. single-bucket
        out = sync(lambda g: bucketed_psum_tree(
            g, "pod", ["data"], bucket_bytes=bb, mode=mode, mean_over=8))
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6), mono, out)
import pytest
for bad in ("multilevel_compress", "rsag"):
    try:
        bucketed_psum_tree(grads, "pod", ["data"], bucket_bytes=1.0,
                           mode=bad)
        raise SystemExit(f"mode {bad} must be rejected")
    except ValueError:
        pass
print("OK")
""")


def test_bucketed_apply_updates_matches_dense(subproc):
    """OptConfig.bucket_bytes reroutes the dense gradient sync through
    size-targeted buckets; one optimizer step must land on the same
    parameters as the per-leaf dense path."""
    subproc("""
import jax, jax.numpy as jnp, numpy as np
from jax import shard_map
from repro.launch.mesh import make_mesh
from jax.sharding import PartitionSpec as P
from repro.optim import adamw
mesh = make_mesh((2, 4), ("pod", "data"))
params = {"w": jnp.arange(32., dtype=jnp.float32).reshape(8, 4) / 32,
          "b": jnp.ones((8,), jnp.float32)}
grads = {"w": jnp.full((8, 4), 0.25, jnp.float32),
         "b": jnp.arange(8., dtype=jnp.float32) / 8}
def step(cfg):
    opt = adamw.init_opt_state(params, cfg)
    f = lambda p, g, o: adamw.apply_updates(p, g, o, cfg, "pod", 4, 8)
    new_p, _ = jax.jit(shard_map(f, mesh=mesh, in_specs=(P(), P(), P()),
                                 out_specs=(P(), P()),
                                 check_vma=False))(params, grads, opt)
    return new_p
for mode in ("flat", "multilevel"):
    dense = step(adamw.OptConfig(comm_mode=mode, zero1=False))
    buck = step(adamw.OptConfig(comm_mode=mode, zero1=False,
                                bucket_bytes=64.0))
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7), dense, buck)
print("OK")
""")


def test_opt_config_bucket_validation():
    from repro.optim.adamw import OptConfig

    with pytest.raises(ValueError, match="positive"):
        OptConfig(bucket_bytes=0.0, zero1=False)
    with pytest.raises(ValueError, match="comm_mode"):
        OptConfig(bucket_bytes=1e6, comm_mode="multilevel_compress",
                  zero1=False)
    with pytest.raises(ValueError, match="zero1"):
        OptConfig(bucket_bytes=1e6, comm_mode="multilevel", zero1=True)
    # flat mode never shards the opt state: zero1 flag is inert there
    OptConfig(bucket_bytes=1e6, comm_mode="flat", zero1=True)
    OptConfig(bucket_bytes=1e6, comm_mode="multilevel", zero1=False)


def test_quantize_int8_raises_value_error():
    """Load-bearing validation must be a real exception: a bare assert
    vanishes under ``python -O`` and turns a shape error into silently
    garbled gradients (the CI tier-1 matrix runs a ``python -O`` leg)."""
    import jax.numpy as jnp
    import pytest
    from repro.core.compression import quantize_int8

    with pytest.raises(ValueError, match="1-D buffer"):
        quantize_int8(jnp.zeros((2, 256), jnp.float32))
    with pytest.raises(ValueError, match="block"):
        quantize_int8(jnp.zeros((255,), jnp.float32))
    q, s = quantize_int8(jnp.zeros((512,), jnp.float32))
    assert q.shape == (512,) and s.shape == (2,)


def test_error_feedback_corrects_compressed_drift(subproc):
    """Regression for the dead ``apply_error_feedback`` export: the int8
    slow-axis exchange rounds every step; without the EF residual the bias
    accumulates (~linearly) in a multi-step all-reduce, with it the
    accumulated estimate stays pinned to the exact trajectory."""
    subproc("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.launch.mesh import make_mesh
from repro.core.compression import (apply_error_feedback, compressed_psum,
                                    quantize_int8, dequantize_int8)

mesh = make_mesh((2,), ("pod",))
N = 512
rng = np.random.default_rng(0)
g_host = rng.normal(size=(2, N)).astype(np.float32) * 1e-3
g = jnp.asarray(g_host).reshape(-1)          # sharded -> local (N,)

def step_noef(acc, g):
    return acc + compressed_psum(g, "pod") / 2
def step_ef(acc, ef, g):
    out, ef = compressed_psum(g, "pod", ef=ef)
    return acc + out / 2, ef

f_noef = jax.jit(shard_map(step_noef, mesh=mesh,
                           in_specs=(P("pod"), P("pod")), out_specs=P("pod")))
f_ef = jax.jit(shard_map(step_ef, mesh=mesh,
                         in_specs=(P("pod"), P("pod"), P("pod")),
                         out_specs=(P("pod"), P("pod"))))

T = 100
exact = np.zeros(N, np.float32)
acc_ne = acc_e = jnp.zeros((2 * N,), jnp.float32)
ef = jnp.zeros((2 * N,), jnp.float32)
for t in range(T):
    exact += g_host.sum(axis=0) / 2
    acc_ne = f_noef(acc_ne, g)
    acc_e, ef = f_ef(acc_e, ef, g)
err_ne = np.abs(np.asarray(acc_ne)[:N] - exact).max()
err_e = np.abs(np.asarray(acc_e)[:N] - exact).max()
# uncorrected drift grows with T; EF keeps the error at one-step rounding
assert err_e < err_ne / 10, (err_e, err_ne)

# apply_error_feedback is the local form of the same correction
gf = jnp.asarray(g_host[0])
corrected, res = apply_error_feedback(gf, jnp.zeros_like(gf))
q, s = quantize_int8(corrected)
np.testing.assert_allclose(np.asarray(corrected - res),
                           np.asarray(dequantize_int8(q, s)), atol=1e-7)
print("OK ef ratio", err_ne / err_e)
""", n_devices=2)


def test_allreduce_tree_threads_ef(subproc):
    """The fused pytree path carries the residual too: Communicator.
    allreduce_tree(mode="multilevel_compress", ef=...) returns (grads,
    new_ef), with compress_ef_zeros sizing the per-rank buffer."""
    subproc("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.launch.mesh import make_mesh
from repro.core.collectives import compress_ef_zeros
from repro.core.topology import tpu_v5e_multipod
from repro.core import Communicator

topo = tpu_v5e_multipod(pods=2, boards=1, chips_per_board=2)
mesh = make_mesh((2, 2), ("pod", "data"))
comm = Communicator(topo, backend="jax", slow_axis="pod",
                    fast_axes=("data",))
grads = {"w": jnp.full((4, 6), 1e-4, jnp.float32),
         "b": jnp.ones((7,), jnp.float32)}
ef0 = compress_ef_zeros(grads, 2)    # per-rank shard: ceil(31/2 pad) -> 16
assert ef0.shape == (16,), ef0.shape
ef_global = jnp.tile(ef0, 4)         # 4 dp ranks, flat-stacked shards

def sync(g, e):
    return comm.allreduce_tree(g, mode="multilevel_compress", ef=e)
out, ef1 = jax.jit(shard_map(
    sync, mesh=mesh, in_specs=(P(), P(("pod", "data"))),
    out_specs=(P(), P(("pod", "data"))), check_vma=False))(grads, ef_global)
np.testing.assert_allclose(np.asarray(out["w"]),
                           np.asarray(grads["w"]) * 4, atol=0.5)
assert ef1.shape == ef_global.shape
# residual is the quantisation error: folding it back reconstructs the
# exact values on the next exchange (non-zero because 1e-4 rounds at int8)
assert float(jnp.abs(ef1).max()) > 0
print("OK allreduce_tree ef")
""", n_devices=4)


def test_train_step_threads_ef_state(subproc):
    """The optimiser carries the residual: multilevel_compress training
    adds an ``ef`` buffer to the opt state, updates it every step, and
    still reduces the loss — in BOTH the ZeRO-1 (sharded) and dense
    (zero1=False) branches, whose ef spec wiring differs."""
    subproc("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.models import transformer as T
from repro.launch import step as STEP
from repro.launch.mesh import make_test_mesh
from repro.optim.adamw import OptConfig, init_opt_state
cfg = get_config("gpt-100m", smoke=True)
mesh = make_test_mesh(pods=2, data=2, model=1)
params = T.init_model(jax.random.PRNGKey(0), cfg)
for zero1 in (True, False):
    opt_cfg = OptConfig(comm_mode="multilevel_compress", zero1=zero1,
                        lr=1e-3, warmup_steps=2, total_steps=50)
    opt = init_opt_state(params, cfg=opt_cfg, n_slow=2)
    assert "ef" in opt
    # residuals diverge per pod: the state carries one row per pod rank
    for pl, el in zip(jax.tree.leaves(params), jax.tree.leaves(opt["ef"])):
        assert el.shape == (2,) + pl.shape, (el.shape, pl.shape)
    assert all(np.asarray(l).max() == 0 for l in jax.tree.leaves(opt["ef"]))
    p_sh, o_sh, b_sh = STEP.train_in_shardings(cfg, opt_cfg, mesh)
    p = jax.device_put(jax.tree.map(np.asarray, params), p_sh)
    o = jax.device_put(jax.tree.map(np.asarray, opt), o_sh)
    fn = jax.jit(STEP.make_train_fn(cfg, opt_cfg, mesh),
                 donate_argnums=(0, 1))
    losses = []
    for s in range(3):
        t = jax.random.randint(jax.random.PRNGKey(s), (8, 16), 0, cfg.vocab)
        b = {"tokens": jax.device_put(t, b_sh),
             "labels": jax.device_put(t, b_sh)}
        p, o, loss = fn(p, o, b)
        losses.append(float(loss))
    assert losses[-1] < losses[0], (zero1, losses)
    ef_mag = max(float(jnp.abs(l).max()) for l in jax.tree.leaves(o["ef"]))
    assert 0 < ef_mag < 1.0, (zero1, ef_mag)  # residual live and bounded
    # each pod quantises its OWN partial sum: rows must differ (a
    # pod-replicated spec would silently collapse them to pod 0's)
    assert any(float(jnp.abs(l[0] - l[1]).max()) > 0
               for l in jax.tree.leaves(o["ef"])), "pod residuals collapsed"
    print("OK ef state zero1 =", zero1, losses)
""", n_devices=4)


def test_tree_collectives_on_devices(subproc):
    subproc("""
import jax, jax.numpy as jnp, numpy as np
from jax import shard_map
from repro.launch.mesh import make_mesh
from jax.sharding import PartitionSpec as P
from repro.core.trees import build_multilevel_tree
from repro.core.topology import tpu_v5e_multipod
from repro.core import tree_exec
topo = tpu_v5e_multipod(pods=2, boards=2, chips_per_board=2)
mesh1 = make_mesh((8,), ("all",))
x = jnp.arange(8., dtype=jnp.float32)
for root in [0, 3, 7]:
    tree = build_multilevel_tree(topo, root=root)
    out = jax.jit(shard_map(lambda v: tree_exec.tree_bcast(v, tree, "all"),
          mesh=mesh1, in_specs=P("all"), out_specs=P("all")))(x)
    np.testing.assert_allclose(np.asarray(out), np.full(8, float(root)))
    def rd(v):
        r = tree_exec.tree_reduce(v, tree, "all")
        return jnp.where(jax.lax.axis_index("all") == tree.root, r, -1.)
    out = jax.jit(shard_map(rd, mesh=mesh1, in_specs=P("all"),
                            out_specs=P("all")))(x)
    assert float(out[root]) == 28.0, (root, out)
print("OK")
""")


def test_zero1_multilevel_trains_identically_to_flat(subproc):
    subproc("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.models import transformer as T
from repro.launch import step as STEP
from repro.launch.mesh import make_test_mesh
from repro.optim.adamw import OptConfig, init_opt_state
cfg = get_config("qwen3_4b", smoke=True)
mesh = make_test_mesh(pods=2, data=2, model=2)
ph = jax.tree.map(np.asarray, T.init_model(jax.random.PRNGKey(0), cfg))
results = {}
for mode, zero1 in [("flat", False), ("multilevel", True)]:
    opt_cfg = OptConfig(comm_mode=mode, zero1=zero1, lr=1e-2,
                        warmup_steps=2, total_steps=50)
    p_sh, o_sh, b_sh = STEP.train_in_shardings(cfg, opt_cfg, mesh)
    p = jax.device_put(ph, p_sh)
    opt = jax.device_put(jax.tree.map(np.asarray,
                         init_opt_state(p, opt_cfg)), o_sh)
    fn = jax.jit(STEP.make_train_fn(cfg, opt_cfg, mesh), donate_argnums=(0, 1))
    losses = []
    for s in range(4):
        t = jax.random.randint(jax.random.PRNGKey(s % 2), (8, 16), 0, cfg.vocab)
        b = {"tokens": jax.device_put(t, b_sh), "labels": jax.device_put(t, b_sh)}
        p, opt, loss = fn(p, opt, b)
        losses.append(float(loss))
    results[mode] = losses
    assert losses[-1] < losses[0], (mode, losses)
# ZeRO-1 multilevel must match the flat baseline numerically (same math)
np.testing.assert_allclose(results["flat"], results["multilevel"],
                           rtol=5e-3, atol=5e-3)
print("OK")
""")


def test_decode_sharded_cache(subproc):
    subproc("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.models import transformer as T
from repro.launch import step as STEP
from repro.launch.mesh import make_test_mesh
from repro.models.sharding import param_shardings
cfg = get_config("qwen3_4b", smoke=True)
mesh = make_test_mesh(pods=1, data=2, model=2)
params = T.init_model(jax.random.PRNGKey(0), cfg)
params = jax.device_put(params, param_shardings(params, mesh))
B, S = 4, 8
toks = jax.random.randint(jax.random.PRNGKey(1), (B, S + 1), 0, cfg.vocab)
with mesh:
    logits_p, cache, pos = jax.jit(
        lambda p, t: T.prefill(p, cfg, {"tokens": t}, s_max=S + 4)
    )(params, toks[:, :S])
    c_sh = STEP.cache_shardings(cfg, mesh, jax.eval_shape(lambda: cache))
    cache = jax.device_put(cache, c_sh)
    logits_d, _ = jax.jit(
        lambda p, c, t, i: T.decode_step(p, cfg, c, t, i)
    )(params, cache, toks[:, S:S+1], jnp.int32(pos))
full = T.model_fwd(params, cfg, {"tokens": toks})
np.testing.assert_allclose(np.asarray(logits_d[:, 0]),
                           np.asarray(full[:, S]), atol=0.1, rtol=0.05)
print("OK")
""")


def test_opt_config_quant_kernel_validation():
    from repro.optim.adamw import OptConfig

    OptConfig(comm_mode="multilevel_compress", quant_kernel=True)
    OptConfig(comm_mode="multilevel_compress", quant_kernel=False)
    with pytest.raises(ValueError, match="quant_kernel"):
        OptConfig(quant_kernel=True)           # default mode: multilevel
    with pytest.raises(ValueError, match="quant_kernel"):
        OptConfig(comm_mode="flat", quant_kernel=False)


def test_compress_ef_zeros_tile():
    """tile rounds the PER-RANK shard up so the fused Pallas quantiser sees
    a pad-free buffer; default tile=1 keeps the historic sizing."""
    import jax.numpy as jnp
    from repro.core.collectives import compress_ef_zeros
    from repro.core.compression import QTILE

    grads = {"w": jnp.zeros((4, 6)), "b": jnp.zeros((7,))}   # 31 elements
    assert compress_ef_zeros(grads, 2).shape == (16,)
    ef = compress_ef_zeros(grads, 2, tile=QTILE)
    assert ef.shape == (QTILE,)
    assert compress_ef_zeros(grads, 1, tile=4).shape == (32,)


def test_allreduce_tree_ef_tile_padding(subproc):
    """multilevel_psum_tree grows the flat buffer to ef.size * fast_degree
    when the residual was tiled up (compress_ef_zeros tile=...), and rejects
    residuals too small for the pytree."""
    subproc("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.launch.mesh import make_mesh
from repro.core.collectives import compress_ef_zeros, multilevel_psum_tree

mesh = make_mesh((2, 2), ("pod", "data"))
grads = {"w": jnp.full((4, 6), 1e-4, jnp.float32),
         "b": jnp.ones((7,), jnp.float32)}
ef0 = compress_ef_zeros(grads, 2, tile=12)   # 31 -> pad to 48 -> shard 24
assert ef0.shape == (24,), ef0.shape
ef_global = jnp.tile(ef0, 4)

def sync(g, e):
    return multilevel_psum_tree(g, "pod", ("data",),
                                mode="multilevel_compress", ef=e)
out, ef1 = jax.jit(shard_map(
    sync, mesh=mesh, in_specs=(P(), P(("pod", "data"))),
    out_specs=(P(), P(("pod", "data"))), check_vma=False))(grads, ef_global)
np.testing.assert_allclose(np.asarray(out["w"]),
                           np.asarray(grads["w"]) * 4, atol=0.5)
assert ef1.shape == ef_global.shape

def sync_small(g, e):
    return multilevel_psum_tree(g, "pod", ("data",),
                                mode="multilevel_compress", ef=e)
try:
    jax.jit(shard_map(
        sync_small, mesh=mesh, in_specs=(P(), P(("pod", "data"))),
        out_specs=(P(), P(("pod", "data"))), check_vma=False))(
        grads, jnp.zeros((4 * 8,), jnp.float32))   # shard 8 < needed 16
    raise SystemExit("expected ValueError for too-small ef")
except ValueError as e:
    assert "too small" in str(e), e
print("OK ef tile padding")
""")
