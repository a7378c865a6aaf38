"""Regression tests for the explicit-parallelism paths added in §Perf:
expert-parallel MoE (nested shard_map) and flash-decode (sequence-sharded
KV cache with LSE combine).  Both must be numerically equivalent to the
single-device reference paths."""
import pytest


def test_ep_moe_matches_reference(subproc):
    subproc("""
import dataclasses, jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.launch.mesh import make_mesh
from repro.configs import get_config
from repro.models import layers as L
from repro.models.sharding import param_pspecs
cfg = get_config("olmoe_1b_7b", smoke=True)
cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe,
                                                       capacity_factor=4.0))
p = L.init_moe(jax.random.PRNGKey(0), cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model), jnp.float32)
y_ref = L.moe_fwd(p, cfg, x)                       # no mesh -> ragged path
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
psh = jax.tree.map(lambda s: NamedSharding(mesh, s), param_pspecs(p, 2),
                   is_leaf=lambda v: isinstance(v, P))
pd = jax.device_put(p, psh)
xd = jax.device_put(x, NamedSharding(mesh, P(("pod", "data"))))
with jax.set_mesh(mesh):
    y_ep = jax.jit(lambda pp, xx: L.moe_fwd(pp, cfg, xx))(pd, xd)
np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_ref), atol=2e-4)
# chunked scan path must agree with the one-shot path
y_chunked = L.moe_fwd(p, cfg, x, chunk=16)
np.testing.assert_allclose(np.asarray(y_chunked), np.asarray(y_ref), atol=2e-4)
print("OK")
""")


def test_ep_moe_capacity_drops_bounded(subproc):
    """With the default capacity factor some tokens may drop under extreme
    imbalance; the output must stay finite and close to reference."""
    subproc("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.launch.mesh import make_mesh
from repro.configs import get_config
from repro.models import layers as L
from repro.models.sharding import param_pspecs
cfg = get_config("llama4_scout_17b_a16e", smoke=True)  # top-1, shared expert
p = L.init_moe(jax.random.PRNGKey(0), cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model), jnp.float32)
y_ref = L.moe_fwd(p, cfg, x)
mesh = make_mesh((1, 2, 2), ("pod", "data", "model"))
psh = jax.tree.map(lambda s: NamedSharding(mesh, s), param_pspecs(p, 2),
                   is_leaf=lambda v: isinstance(v, P))
pd = jax.device_put(p, psh)
with jax.set_mesh(mesh):
    y_ep = jax.jit(lambda pp, xx: L.moe_fwd(pp, cfg, xx))(pd, x)
assert bool(jnp.isfinite(y_ep).all())
# tolerate capacity drops: relative Frobenius error small
rel = float(jnp.linalg.norm(y_ep - y_ref) / jnp.linalg.norm(y_ref))
assert rel < 0.3, rel  # tiny-T smoke is adversarial for top-1 capacity
print("OK rel", rel)
""")


def test_sp_flash_decode_matches_full_forward(subproc):
    subproc("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.models import transformer as T
from repro.launch import step as STEP
from repro.launch.mesh import make_test_mesh
from repro.models.sharding import param_shardings
for arch in ["qwen3_4b", "gemma3_12b"]:   # full + sliding-window caches
    cfg = get_config(arch, smoke=True)
    mesh = make_test_mesh(pods=1, data=2, model=2)
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    params = jax.device_put(params, param_shardings(params, mesh))
    B, S = 4, 8
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S + 2), 0, cfg.vocab)
    full = T.model_fwd(params, cfg, {"tokens": toks})
    with jax.set_mesh(mesh):
        _, cache, pos = jax.jit(lambda p, t: T.prefill(
            p, cfg, {"tokens": t}, s_max=S + 4))(params, toks[:, :S])
        c_sh = STEP.cache_shardings(cfg, mesh, jax.eval_shape(lambda: cache))
        cache = jax.device_put(cache, c_sh)
        dec = jax.jit(lambda p, c, t, i: T.decode_step(p, cfg, c, t, i))
        l1, cache = dec(params, cache, toks[:, S:S+1], jnp.int32(pos))
        l2, cache = dec(params, cache, toks[:, S+1:S+2], jnp.int32(pos + 1))
    np.testing.assert_allclose(np.asarray(l1[:, 0]), np.asarray(full[:, S]),
                               atol=0.1, rtol=0.05)
    np.testing.assert_allclose(np.asarray(l2[:, 0]), np.asarray(full[:, S+1]),
                               atol=0.1, rtol=0.05)
print("OK")
""")


@pytest.mark.parametrize("ctx", ["auto_mesh", "dp_manual"])
def test_pallas_flash_partitions_over_mesh(subproc, ctx):
    """The Pallas flash kernel under a mesh: with every axis Auto (the
    serving path) and inside a dp-manual shard_map (the train step) it runs
    in its own shard_map over heads and batch, and matches the jnp
    lowering in value and gradient at a GQA shape."""
    subproc(f"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_test_mesh
from repro.models.layers import chunked_attention
mesh = make_test_mesh(pods=1, data=2, model=2)
B, S, H, Hkv, hd = 4, 256, 8, 2, 32                  # G = 4
ks = jax.random.split(jax.random.PRNGKey(0), 3)
q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
k = jax.random.normal(ks[1], (B, S, Hkv, hd), jnp.float32)
v = jax.random.normal(ks[2], (B, S, Hkv, hd), jnp.float32)

def attend(impl):
    def f(q, k, v):
        return chunked_attention(q, k, v, chunk_q=128, chunk_k=128,
                                 impl=impl)
    if "{ctx}" == "dp_manual":
        f = jax.shard_map(f, in_specs=P("data"), out_specs=P("data"),
                          axis_names={{"data"}}, check_vma=False)
    return f

def grads(impl):
    return jax.grad(lambda q, k, v: jnp.sum(jnp.sin(attend(impl)(q, k, v))),
                    argnums=(0, 1, 2))

with jax.set_mesh(mesh):
    jaxpr = str(jax.make_jaxpr(attend("pallas"))(q, k, v))
    want_spec = ("PartitionSpec('data', None, 'model', None)"
                 if "{ctx}" == "auto_mesh"
                 else "PartitionSpec(None, None, 'model', None)")
    assert want_spec in jaxpr, jaxpr
    assert "pallas_call" in jaxpr
    op, oj = (jax.jit(attend(i))(q, k, v) for i in ("pallas", "jnp"))
    gp, gj = (jax.jit(grads(i))(q, k, v) for i in ("pallas", "jnp"))
assert len(op.sharding.device_set) == 4
np.testing.assert_allclose(np.asarray(op), np.asarray(oj), atol=1e-5)
for name, a, b in zip(("dq", "dk", "dv"), gp, gj):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                               err_msg=name)
print("OK")
""", n_devices=4)
