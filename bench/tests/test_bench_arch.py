"""The architecture lives in the module a configuration file names: the
two decoder configurations build the same model, weights and work counts
as before it moved there (pins taken before the move), and a stack of
another shape (windowed and full layers, a shared expert) is built,
stepped and counted through the harness from added files alone."""
import copy
import json
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import flops, harness, weights
from bench.tests import tiny


def _config(name):
    return harness.as_run(harness.load_json(harness.BENCH, "configs",
                                            name + ".json"))


# ---------------------------------------------------------------------- #
# Pins: the decoder configurations as they were built before the move
# ---------------------------------------------------------------------- #

def _expected_model_config(name):
    from repro.models.config import ModelConfig, MoECfg
    if name == "phi4-mini-3.8b":
        return ModelConfig(
            name=name, n_layers=32, d_model=3072, n_heads=24, n_kv_heads=8,
            head_dim=128, d_ff=8192, vocab=200064, pattern=("attn",) * 32,
            rope_theta=10000.0, tie_embeddings=True, moe=None,
            norm_eps=1e-6, family="dense")
    return ModelConfig(
        name=name, n_layers=1, d_model=2048, n_heads=16, n_kv_heads=16,
        head_dim=128, d_ff=1024, vocab=6288, pattern=("attn",),
        rope_theta=10000.0, tie_embeddings=False,
        moe=MoECfg(n_experts=64, top_k=8, d_ff_expert=1024), norm_eps=1e-6,
        family="moe")


@pytest.mark.parametrize("name", ["phi4-mini-3.8b", "olmoe-1b-7b"])
def test_model_config_is_pinned(name):
    assert harness.model_config(_config(name)) == \
        _expected_model_config(name)


# per leaf: float64 sum and sum of squares of make_params(c, 2**33 + 5)
DIGESTS = {
    "tiny-dense": {
        "['embed']": (28.684169232845306, 508.73756775079346),
        "['final_norm']": (-0.39339929819107056, 1.3116638800262486),
        "['runs'][0]['attn']['wk']": (15.153787612915039, 199.2013906027314),
        "['runs'][0]['attn']['wo']": (6.588398098945618, 390.3044304088586),
        "['runs'][0]['attn']['wq']": (-22.960754871368408,
                                      384.5284142647869),
        "['runs'][0]['attn']['wv']": (-9.200442790985107,
                                      193.27559838582988),
        "['runs'][0]['mlp']['wg']": (11.381073474884033, 775.0554489763265),
        "['runs'][0]['mlp']['wi']": (-0.19391459226608276, 762.264106205047),
        "['runs'][0]['mlp']['wo']": (1.9856674671173096, 385.7054106765828),
        "['runs'][0]['norm1']": (-2.4493918418884277, 2.6765670967626676),
        "['runs'][0]['norm2']": (0.00608980655670166, 2.472691098472808),
    },
    "tiny-moe": {
        "['embed']": (210.33620649576187, 293.38943938634964),
        "['final_norm']": (-0.39339929819107056, 1.3116638800262486),
        "['lm_head']": (-2.9539974331855774, 85.48181964440316),
        "['runs'][0]['attn']['wk']": (8.95181655883789, 195.3205385881738),
        "['runs'][0]['attn']['wo']": (5.584327340126038, 170.52489982054374),
        "['runs'][0]['attn']['wq']": (-25.873481035232544,
                                      191.03355923045007),
        "['runs'][0]['attn']['wv']": (-5.293132305145264, 197.0707106077391),
        "['runs'][0]['mlp']['router']": (48.67511364817619,
                                         290.19696460878026),
        "['runs'][0]['mlp']['w_gate']": (-18.400006115436554,
                                         772.2549901206609),
        "['runs'][0]['mlp']['w_in']": (-18.617011427879333,
                                       761.7877794131339),
        "['runs'][0]['mlp']['w_out']": (22.773723125457764,
                                        1348.0615007971512),
        "['runs'][0]['norm1']": (-1.4283812642097473, 1.403809678701581),
        "['runs'][0]['norm2']": (1.903564453125, 1.145935036076807),
    },
}


@pytest.mark.parametrize("c", [tiny.DENSE, tiny.MOE], ids=lambda c: c["name"])
def test_weights_are_pinned(c):
    tree = weights.make_params(c, 2 ** 33 + 5)
    got = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(x.astype(jnp.float32), np.float64)
        got[jax.tree_util.keystr(path)] = (float(a.sum()),
                                           float((a * a).sum()))
    assert got == DIGESTS[c["name"]]


SHAPES = {
    "phi4-mini-3.8b": {
        "['embed']": ((200064, 3072), "bfloat16"),
        "['final_norm']": ((3072,), "float32"),
        "['runs'][0]['attn']['wk']": ((32, 3072, 1024), "bfloat16"),
        "['runs'][0]['attn']['wo']": ((32, 3072, 3072), "bfloat16"),
        "['runs'][0]['attn']['wq']": ((32, 3072, 3072), "bfloat16"),
        "['runs'][0]['attn']['wv']": ((32, 3072, 1024), "bfloat16"),
        "['runs'][0]['mlp']['wg']": ((32, 3072, 8192), "bfloat16"),
        "['runs'][0]['mlp']['wi']": ((32, 3072, 8192), "bfloat16"),
        "['runs'][0]['mlp']['wo']": ((32, 8192, 3072), "bfloat16"),
        "['runs'][0]['norm1']": ((32, 3072), "float32"),
        "['runs'][0]['norm2']": ((32, 3072), "float32"),
    },
    "olmoe-1b-7b": {
        "['embed']": ((6288, 2048), "bfloat16"),
        "['final_norm']": ((2048,), "float32"),
        "['lm_head']": ((2048, 6288), "bfloat16"),
        "['runs'][0]['attn']['wk']": ((1, 2048, 2048), "bfloat16"),
        "['runs'][0]['attn']['wo']": ((1, 2048, 2048), "bfloat16"),
        "['runs'][0]['attn']['wq']": ((1, 2048, 2048), "bfloat16"),
        "['runs'][0]['attn']['wv']": ((1, 2048, 2048), "bfloat16"),
        "['runs'][0]['mlp']['router']": ((1, 2048, 64), "float32"),
        "['runs'][0]['mlp']['w_gate']": ((1, 64, 2048, 1024), "bfloat16"),
        "['runs'][0]['mlp']['w_in']": ((1, 64, 2048, 1024), "bfloat16"),
        "['runs'][0]['mlp']['w_out']": ((1, 64, 1024, 2048), "bfloat16"),
        "['runs'][0]['norm1']": ((1, 2048), "float32"),
        "['runs'][0]['norm2']": ((1, 2048), "float32"),
    },
}


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_weight_shapes_are_pinned(name):
    c = _config(name)
    tree = jax.eval_shape(lambda: weights.make_params(c, 7))
    got = {jax.tree_util.keystr(p): (tuple(x.shape), str(x.dtype))
           for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}
    assert got == SHAPES[name]


@pytest.mark.parametrize("name, count, args, want", [
    ("phi4-mini-3.8b", "prefill_flops", (512,), 3351404347392.0),
    ("phi4-mini-3.8b", "prefill_flops", (1024,), 6804658716672.0),
    ("phi4-mini-3.8b", "prefill_flops", (2048,), 14020405100544.0),
    ("olmoe-1b-7b", "train_step_flops", (4, 4096), 8700731326464.0),
    ("olmoe-1b-7b", "train_step_flops", (16, 4096), 34802925305856.0),
])
def test_flop_counts_are_pinned(name, count, args, want):
    assert getattr(flops, count)(_config(name), *args) == want


# ---------------------------------------------------------------------- #
# A new architecture is new files only
# ---------------------------------------------------------------------- #

HYBRID_ARCH = '''
"""Windowed and full attention layers, each followed by a top-k MoE with
one shared expert."""
from bench.weights import BF16, F32, GAIN, NORM_SCALE

KIND = {"sliding_attention": "local", "full_attention": "attn"}


def model_config(c):
    from repro.models.config import ModelConfig, MoECfg
    pattern = tuple(KIND[t] for t in c["layer_types"])
    return ModelConfig(
        name=c["name"], n_layers=len(pattern), d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab=c["vocab_size"], pattern=pattern,
        window=c["sliding_window"], rope_theta=c["rope_theta"],
        tie_embeddings=c["tie_word_embeddings"],
        moe=MoECfg(n_experts=c["num_experts"],
                   top_k=c["num_experts_per_tok"],
                   d_ff_expert=c["moe_intermediate_size"],
                   shared_expert=c["num_shared_experts"] == 1),
        norm_eps=c["rms_norm_eps"], family="moe")


def layer_specs(c, layer):
    D, hd, E = c["hidden_size"], c["head_dim"], c["num_experts"]
    Q, KV = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    F, Fs = c["moe_intermediate_size"], c["intermediate_size"]
    return {("norm1",): ((D,), F32, NORM_SCALE),
            ("norm2",): ((D,), F32, NORM_SCALE),
            ("attn", "wq"): ((D, Q), BF16, GAIN * D ** -0.5),
            ("attn", "wk"): ((D, KV), BF16, GAIN * D ** -0.5),
            ("attn", "wv"): ((D, KV), BF16, GAIN * D ** -0.5),
            ("attn", "wo"): ((Q, D), BF16, GAIN * Q ** -0.5),
            ("mlp", "router"): ((D, E), F32, D ** -0.5),
            ("mlp", "w_in"): ((E, D, F), BF16, GAIN * D ** -0.5),
            ("mlp", "w_gate"): ((E, D, F), BF16, GAIN * D ** -0.5),
            ("mlp", "w_out"): ((E, F, D), BF16, GAIN * F ** -0.5),
            ("mlp", "shared", "wi"): ((D, Fs), BF16, GAIN * D ** -0.5),
            ("mlp", "shared", "wg"): ((D, Fs), BF16, GAIN * D ** -0.5),
            ("mlp", "shared", "wo"): ((Fs, D), BF16, GAIN * Fs ** -0.5)}


def residual_writers(c, layer):
    return (("attn", "wo"), ("mlp", "w_out"), ("mlp", "shared", "wo"))


def routing(c):
    return ("mlp", "router"), c["num_experts"], c["num_experts_per_tok"]


def layer_work(c, layer, S):
    D, hd, E = c["hidden_size"], c["head_dim"], c["num_experts"]
    H, Hkv = c["num_attention_heads"], c["num_key_value_heads"]
    F, Fs = c["moe_intermediate_size"], c["intermediate_size"]
    params = (2 * D * H * hd + 2 * D * Hkv * hd
              + c["num_experts_per_tok"] * 3 * D * F + D * E + 3 * D * Fs)
    W = c["sliding_window"]
    if KIND[c["layer_types"][layer]] == "local" and S > W:
        return params, W * (W + 1) // 2 + (S - W) * W
    return params, S * (S + 1) // 2
'''

HYBRID = dict(
    name="tiny-hybrid", source="https://example.org/tiny-hybrid",
    reference="decoder_ref", arch="hybrid_arch", reduced=[], published={},
    hidden_size=64, intermediate_size=48, moe_intermediate_size=32,
    num_hidden_layers=8, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, vocab_size=256, tie_word_embeddings=False, num_experts=8,
    num_experts_per_tok=2, num_shared_experts=1, sliding_window=8,
    layer_types=(["sliding_attention"] * 3 + ["full_attention"]) * 2,
    rope_theta=10000.0, rms_norm_eps=1e-6)


def _pairs(kind, S, W):
    """(query, key) pairs of a sequence of length S, counted one by one."""
    q, k = np.meshgrid(np.arange(S), np.arange(S), indexing="ij")
    mask = q >= k
    if kind == "local":
        mask &= q - k < W
    return int(mask.sum())


def test_a_new_architecture_is_new_files_only(tmp_path, monkeypatch):
    """A configuration of another shape is its configuration file, its arch
    module, its traffic and its limits: no existing file changes, and the
    harness builds, steps and counts it."""
    from repro.models import transformer as T

    from bench import run, train_cell
    bench = tmp_path / "bench"
    for d in ("configs", "traffic", "limits", "metrics"):
        shutil.copytree(os.path.join(harness.BENCH, d), bench / d)
    before = {p: p.read_bytes() for p in tmp_path.rglob("*") if p.is_file()}
    (bench / "configs" / "hybrid_arch.py").write_text(HYBRID_ARCH)
    (bench / "configs" / "tiny-hybrid.json").write_text(json.dumps(HYBRID))
    (bench / "traffic" / "tiny-hybrid-train.json").write_text(
        json.dumps(tiny.job()))
    (bench / "limits" / "tiny-hybrid.train.json").write_text(
        json.dumps({"limits": tiny.TRAIN_LIMITS}))
    spec = copy.deepcopy(harness.benchmark_spec())
    spec["configs"].append(dict(
        name="tiny-hybrid", source=HYBRID["source"], reduced=[],
        file="bench/configs/tiny-hybrid.json", why="windowed and full"))
    spec["workloads"].append(dict(
        name="tiny-hybrid.train", config="tiny-hybrid",
        traffic="tiny-hybrid-train", chips=1, why="training"))
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    monkeypatch.setattr(harness, "BENCH", str(bench))

    cell = harness.find_cell("tiny-hybrid.train", spec)
    c, seed = cell.config, 2 ** 33 + 9
    cfg = harness.model_config(c)
    assert cfg.runs() == [("local", 3), ("attn", 1)] * 2

    params = weights.make_params(c, seed)
    want = jax.eval_shape(lambda: T.init_model(jax.random.PRNGKey(0), cfg))
    assert jax.tree.structure(params) == jax.tree.structure(want)
    assert [x.shape for x in jax.tree.leaves(params)] == [
        x.shape for x in jax.tree.leaves(want)]
    first = 0
    for run_params, (_, n) in zip(params["runs"], cfg.runs()):
        for i in range(n):
            got = weights.make_layer(c, seed, first + i)
            jax.tree.map(lambda a, b, i=i: np.testing.assert_array_equal(
                np.asarray(a[i], np.float32), np.asarray(b, np.float32)),
                run_params, got)
        first += n
        mlp = run_params["mlp"]
        for w in (run_params["attn"]["wo"], mlp["w_out"],
                  mlp["shared"]["wo"]):
            assert not np.asarray(w[..., :8], np.float32).any()
        assert np.asarray(mlp["shared"]["wo"][..., 8:], np.float32).all()

    S, rows = 32, 4
    D, V, H, hd = 64, 256, 4, 16
    P = (2 * D * H * hd + 2 * D * 2 * hd + 2 * 3 * D * 32 + D * 8
         + 3 * D * 48)
    fwd = sum(2 * P * S + 4 * H * hd * _pairs(kind, S, 8)
              for kind in cfg.pattern) + 2 * D * V * S
    assert _pairs("local", S, 8) < _pairs("attn", S, 8)
    assert flops.train_step_flops(c, rows, S) == 3.0 * fwd * rows

    mesh = run.make_mesh(cell, jax.devices()[:1])
    fn, params, opt, feed, _ = train_cell.build(cell, seed, mesh)
    with jax.set_mesh(mesh):
        for i in range(2):
            params, opt, loss = fn(params, opt, feed(i))
            assert np.isfinite(float(loss))
    assert all(p.read_bytes() == b for p, b in before.items())
