"""Compile the main path's Pallas kernels at real widths for a described
TPU v5e (no chip needed): what the Mosaic compiler refuses here — block
layouts, VMEM overflow, unaligned tiles — would fail on the chip.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.kernels import backend
from repro.kernels import flash_attention as fa
from repro.kernels import quant as kq
from repro.models.layers import chunked_attention

# (B, S, H, Hkv, hd): TinyLlama-1.1B (GQA, G=8) and gpt-100m (MHA) layers
FLASH_WIDTHS = {"tinyllama-gqa": (2, 2048, 32, 4, 64),
                "gpt100m-mha": (2, 2048, 12, 12, 64)}
QUANT_N = 4 * 2 ** 20


@pytest.fixture(scope="module")
def v5e_2x2():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip executable cannot be read back from the persistent
    # cache without the chip: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    return SingleDeviceSharding(v5e_2x2[0])


def _compile_has_kernel(fn, *specs) -> bool:
    return "tpu_custom_call" in jax.jit(fn).lower(*specs).compile().as_text()


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd+bwd"])
@pytest.mark.parametrize("widths", list(FLASH_WIDTHS))
def test_flash_attention_compiles_for_v5e(one_chip, widths, grad):
    B, S, H, Hkv, hd = FLASH_WIDTHS[widths]
    q = jax.ShapeDtypeStruct((B, S, H, hd), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((B, S, Hkv, hd), jnp.bfloat16,
                              sharding=one_chip)

    def fwd(q, k, v):
        return fa.flash_attention(q, k, v, True, None, fa.DEFAULT_BLOCK_Q,
                                  fa.DEFAULT_BLOCK_K, 0, False)

    fn = fwd
    if grad:
        fn = jax.grad(lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum(),
                      argnums=(0, 1, 2))
    assert _compile_has_kernel(fn, q, kv, kv)


@pytest.mark.parametrize("ctx", ["auto_mesh", "dp_manual"])
def test_model_attention_on_mesh_compiles_for_v5e(v5e_2x2, monkeypatch, ctx):
    """The model's attention on the 2x2 host, as serving (every mesh axis
    Auto) and the ZeRO-1 train step (inside a dp-manual shard_map) call it:
    the Pallas kernels forward and backward in the shard_map that
    ``layers._pallas_flash`` puts around them."""
    monkeypatch.setattr(backend, "on_tpu", lambda: True)  # the chip's path
    mesh = Mesh(np.asarray(v5e_2x2).reshape(2, 2), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    B, S, H, Hkv, hd = FLASH_WIDTHS["tinyllama-gqa"]
    rows = NamedSharding(mesh, P("data"))
    q = jax.ShapeDtypeStruct((B, S, H, hd), jnp.bfloat16, sharding=rows)
    kv = jax.ShapeDtypeStruct((B, S, Hkv, hd), jnp.bfloat16, sharding=rows)

    attend = chunked_attention
    if ctx == "dp_manual":
        attend = jax.shard_map(chunked_attention, mesh=mesh,
                               in_specs=P("data"), out_specs=P("data"),
                               axis_names={"data"}, check_vma=False)
    fn = jax.grad(lambda q, k, v: attend(q, k, v).astype(jnp.float32).sum(),
                  argnums=(0, 1, 2))
    with jax.set_mesh(mesh):
        assert _compile_has_kernel(fn, q, kv, kv)


@pytest.mark.parametrize("kernel", ["quantize_int8", "quantize_ef_int8",
                                    "dequantize_int8"])
def test_quantiser_compiles_for_v5e(one_chip, kernel):
    x = jax.ShapeDtypeStruct((QUANT_N,), jnp.float32, sharding=one_chip)
    if kernel == "quantize_int8":
        fn, specs = (lambda x: kq.quantize_int8(x, interpret=False)), (x,)
    elif kernel == "quantize_ef_int8":
        fn = lambda x, e: kq.quantize_ef_int8(x, e, interpret=False)
        specs = (x, x)
    else:
        fn = lambda q, s: kq.dequantize_int8(q, s, interpret=False)
        specs = (jax.ShapeDtypeStruct((QUANT_N,), jnp.int8, sharding=one_chip),
                 jax.ShapeDtypeStruct((QUANT_N // kq.QBLOCK,), jnp.float32,
                                      sharding=one_chip))
    assert _compile_has_kernel(fn, *specs)
