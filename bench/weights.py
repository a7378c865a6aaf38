"""Weights from ``--seed``, made on the device in one jitted call.

The tree has the layout the program's decoder takes: ``embed``,
``final_norm``, an optional ``lm_head``, and ``runs``, one stacked run per
maximal run of block kinds of ``model_config(c).runs()``.  What each layer
holds comes from the configuration's arch module (``"arch"`` in its file,
see ``bench/configs/decoder_arch.py``): ``layer_specs`` gives its leaves,
``residual_writers`` the leaves that write the residual stream, and
``routing`` the router, if any.  Nothing here names an architecture, so
the plain reference can make any one layer of the same weights again from
the configuration file alone, without the program.  Every leaf of layer
``l`` comes from its own key, so the stacked tree and the layer-by-layer
reference hold the same numbers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import harness

BF16, F32 = jnp.bfloat16, jnp.float32
NORM_SCALE = 0.25   # norm weights w, applied as (1 + w)
# bfloat16 matrices are uniform in +-GAIN/sqrt(fan_in).  At 1 the residual
# stream keeps the input token's embedding and a tied head puts that token
# first by tens of logits at every position, so greedy decoding never comes
# near a tie and no check could see a wrong logit; at 3 the layers
# dominate and the top two logits lie a median 0.15 apart (float32
# reference, 32 layers at Phi-4-mini widths, on the CPU).
GAIN = 3.0
# Top-k routing.  With every matrix random, a token's k-th and (k+1)-th
# router logits lie so close that bfloat16 rounding flips some tokens'
# choice, and a check of the gradient then measures flips, not precision.
# So each vocabulary id's k experts are drawn from the seed and written
# into the first E dims of its embedding row (ROUTE_VALUE after the sqrt(D)
# scale), no layer writes those dims (their columns of every residual
# writer the arch module names are zero), and the router reads dim e for
# expert e at ROUTE_GAIN: the chosen k lead the rest by several logits,
# which no rounding crosses.  The gates among the k stay random and
# smooth.  The work is unchanged: the same shapes, and every expert gets
# k/E of the tokens in expectation.
ROUTE_VALUE = 3.0
ROUTE_GAIN = 6.0


def top_specs(c: dict) -> dict:
    """name -> (shape, dtype, scale); normal for the embedding, uniform in
    [-scale, scale] for the rest."""
    D, V = c["hidden_size"], c["vocab_size"]
    out = {"embed": ((V, D), BF16, 1.0 / D ** 0.5),
           "final_norm": ((D,), F32, NORM_SCALE)}
    if not c["tie_word_embeddings"]:
        out["lm_head"] = ((D, V), BF16, 1.0 / D ** 0.5)
    return out


def layer_specs(c: dict, layer: int) -> dict:
    """path -> (shape of one layer, dtype, scale) of layer ``layer``."""
    return harness.arch_module(c).layer_specs(c, layer)


def _layout(c: dict, layer: int) -> tuple:
    """Layer ``layer``'s leaf specs and residual writers, hashable: layers
    of one layout share their programs."""
    arch = harness.arch_module(c)
    return (tuple(arch.layer_specs(c, layer).items()),
            tuple(arch.residual_writers(c, layer)))


def _draw(key, shape, scale, normal=False):
    if normal:
        return jax.random.normal(key, shape, F32) * scale
    return jax.random.uniform(key, shape, F32, -scale, scale)


def _top_leaf(c, key, name, spec):
    shape, dtype, scale = spec
    x = _draw(jax.random.fold_in(key, hash_name(name)), shape, scale,
              normal=name == "embed")
    route = harness.arch_module(c).routing(c)
    if name == "embed" and route is not None:
        x = _route_rows(key, x, *route[1:])
    return x.astype(dtype)


def _layer_leaf(c, key, path, spec, layer, writers):
    shape, dtype, scale = spec
    k = jax.random.fold_in(jax.random.fold_in(key, hash_name("/".join(path))),
                           layer)
    x = _draw(k, shape, scale)
    route = harness.arch_module(c).routing(c)
    if route is not None:
        router, E, _ = route
        if path in writers:
            x = x.at[..., :E].set(0.0)
        elif path == router:
            if shape[-1] != E:
                raise ValueError(f"router {path} is {shape[-1]} wide, "
                                 f"routing() says {E}")
            x = x.at[:E].set(ROUTE_GAIN * jnp.eye(E, dtype=F32))
    return x.astype(dtype)


def _route_rows(key, emb, E, k):
    """The embedding with each row's first E dims marking its k experts."""
    V, D = emb.shape
    u = jax.random.uniform(jax.random.fold_in(key, hash_name("route")),
                           (V, E))
    rank = jnp.argsort(jnp.argsort(-u, axis=1), axis=1)
    mark = jnp.where(rank < k, ROUTE_VALUE / D ** 0.5, 0.0)
    return emb.at[:, :E].set(mark)


def hash_name(name: str) -> int:
    """A stable 31-bit id of a leaf name (Python's hash is salted)."""
    h = 2166136261
    for ch in name.encode():
        h = ((h ^ ch) * 16777619) & 0xFFFFFFFF
    return h & 0x7FFFFFFF


def nest(flat: dict) -> dict:
    """{(a, b): x} -> {a: {b: x}}."""
    out: dict = {}
    for path, v in flat.items():
        d = out
        for p in path[:-1]:
            d = d.setdefault(p, {})
        d[path[-1]] = v
    return out


def make_params(c: dict, seed: int, out_shardings=None):
    """The whole tree, stacked over layers, in one jitted call."""
    return _params_fn(c, out_shardings)(_seed_words(seed))


def _seed_words(seed: int):
    """A seed of up to 64 bits as two uint32 words, traced so that one
    program serves every seed."""
    return jnp.asarray([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF],
                       jnp.uint32)


def _key_from_words(w):
    k = jax.random.PRNGKey(w[0])
    return jax.random.fold_in(k, w[1])


class Frozen(dict):
    """A configuration that can key a cache or be a static jit argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, repr(v)) for k, v in self.items())))


def _params_fn(c: dict, out_shardings):
    runs, first = [], 0
    for _, n in harness.model_config(c).runs():
        layout = _layout(c, first)
        if any(_layout(c, l) != layout for l in range(first, first + n)):
            raise ValueError(f"layers {first}..{first + n - 1} form one run "
                             "of the program but differ in their leaves")
        runs.append((first, n, layout))
        first += n

    def build(words):
        key = _key_from_words(words)
        tree = {n: _top_leaf(c, key, n, s) for n, s in top_specs(c).items()}
        tree["runs"] = []
        for lo, n, (specs, writers) in runs:
            layers = jnp.arange(lo, lo + n)
            run = {path: jax.vmap(lambda l, p=path, s=spec:
                                  _layer_leaf(c, key, p, s, l, writers))(
                                      layers)
                   for path, spec in specs}
            tree["runs"].append(nest(run))
        return tree

    return jax.jit(build, out_shardings=out_shardings)


@functools.lru_cache(maxsize=None)
def _layer_fn(c: Frozen, layout: tuple):
    specs, writers = layout

    def build(words, layer):
        key = _key_from_words(words)
        return nest({p: _layer_leaf(c, key, p, s, layer, writers)
                      for p, s in specs})
    return jax.jit(build)


@functools.lru_cache(maxsize=None)
def _top_fn(c: Frozen):
    def build(words):
        key = _key_from_words(words)
        return {n: _top_leaf(c, key, n, s) for n, s in top_specs(c).items()}
    return jax.jit(build)


def make_layer(c: dict, seed: int, layer: int) -> dict:
    """Layer ``layer``'s leaves, as the stacked tree holds them."""
    return _layer_fn(Frozen(c), _layout(c, layer))(_seed_words(seed),
                                                   jnp.int32(layer))


def make_top(c: dict, seed: int) -> dict:
    """The embedding, the final norm and the head."""
    return _top_fn(Frozen(c))(_seed_words(seed))
