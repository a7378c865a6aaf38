"""Plain float32 reference of a decoder-only transformer (dense SwiGLU or
top-k MoE, grouped-query attention, rotary positions, RMSNorm), written
from the configuration file alone.  It imports nothing of the program and
makes its own weights again from the seed (``bench/weights.py``), layer by
layer, so that it fits next to nothing on the chip.

Every matrix product runs at ``Precision.HIGHEST``.  ``quant`` computes in
float8 (e4m3) instead: every activation the program would hold in
bfloat16 (the residual stream, q, k, v, the attention output, the MLP's
hidden layer) is rounded to float8 with one scale per row, and every
weight with one scale per output column; products and sums stay float32.
That is the next precision below the bfloat16 the configurations state:
the control that ``correct`` has to reject.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0
Q_BLOCK = 1024      # query rows whose attention scores are made at once


def _fq(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``;
    the gradient passes straight through."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                    1e-30) / F8_MAX
    q = (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    return x + jax.lax.stop_gradient(q - x)


def _mm(a, b, quant):
    """a (..., K) @ b (K, N) in float32; with ``quant`` the weight b is
    rounded to float8 per output column and the product per row."""
    a, b = a.astype(F32), b.astype(F32)
    if quant:
        b = _fq(b, 0)
    return _act(jnp.matmul(a, b, precision=HI), quant)


def _act(x, quant):
    """An activation as the computation holds it: float8 per row."""
    return _fq(x, -1) if quant else x


def rmsnorm(x, w, eps):
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * (1.0 + w.astype(F32))


def rope(x, theta):
    """x (S, H, hd): rotate the two halves of each head."""
    S, _, hd = x.shape
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(S, dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(c, w, x, quant):
    S = x.shape[0]
    H, Hkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    q = rope(_mm(x, w["wq"], quant).reshape(S, H, hd), c["rope_theta"])
    k = rope(_mm(x, w["wk"], quant).reshape(S, Hkv, hd), c["rope_theta"])
    v = _mm(x, w["wv"], quant).reshape(S, Hkv, hd)
    k = jnp.repeat(k, H // Hkv, axis=1)       # query head h reads kv h // G
    v = jnp.repeat(v, H // Hkv, axis=1)

    def rows(q, lo):
        """Queries lo.. against every key, masked causally."""
        s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / math.sqrt(hd)
        causal = (lo + jnp.arange(q.shape[0]))[:, None] \
            >= jnp.arange(S)[None, :]
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    # blocks of queries, each recomputed in the backward pass, so the
    # (heads, S, S) scores never live whole
    o = jnp.concatenate([
        jax.checkpoint(lambda qb, lo=lo: rows(qb, lo))(q[lo:lo + Q_BLOCK])
        for lo in range(0, S, Q_BLOCK)]).reshape(S, H * hd)
    return _mm(_act(o, quant), w["wo"], quant)


def swiglu(x, wi, wg, wo, quant):
    h = _act(jax.nn.silu(_mm(x, wg, quant)) * _mm(x, wi, quant), quant)
    return _mm(h, wo, quant)


MOE_GROUP = 8   # experts computed together, densely over every token


def moe(c, w, x, quant):
    """Top-k routing with the gates renormalised over the chosen k; every
    expert is evaluated on every token and weighted by its gate (zero
    where not chosen)."""
    E, k = c["num_experts"], c["num_experts_per_tok"]
    probs = jax.nn.softmax(jnp.matmul(x, w["router"].astype(F32),
                                      precision=HI), axis=-1)
    top, idx = jax.lax.top_k(probs, k)
    if c["norm_topk_prob"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    gate = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], idx].set(top)          # (T, E)
    y = jnp.zeros_like(x)
    for g0 in range(0, E, MOE_GROUP):
        def group(x, wi, wg, wo, gt):
            h = jax.vmap(lambda a, b, o: swiglu(x, a, b, o, quant))(wi, wg, wo)
            return jnp.einsum("te,etd->td", gt, h, precision=HI)
        sl = slice(g0, g0 + MOE_GROUP)
        y = y + jax.checkpoint(group)(x, w["w_in"][sl], w["w_gate"][sl],
                                      w["w_out"][sl], gate[:, sl])
    return y


def layer(c, w, h, quant=False):
    """One decoder layer on h (S, D) float32."""
    eps = c["rms_norm_eps"]
    x = _act(rmsnorm(h, w["norm1"], eps), quant)
    h = _act(h + attention(c, w["attn"], x, quant), quant)
    x = _act(rmsnorm(h, w["norm2"], eps), quant)
    if c.get("num_experts"):
        return _act(h + moe(c, w["mlp"], x, quant), quant)
    m = w["mlp"]
    return _act(h + swiglu(x, m["wi"], m["wg"], m["wo"], quant), quant)


def embed(c, top, tokens, quant=False):
    return _act(top["embed"].astype(F32)[tokens]
                * math.sqrt(c["hidden_size"]), quant)


def head(c, top):
    return top["embed"].T if c["tie_word_embeddings"] else top["lm_head"]


def logits(c, top, h, quant=False):
    x = _act(rmsnorm(h, top["final_norm"], c["rms_norm_eps"]), quant)
    return _mm(x, head(c, top), quant)


# ---------------------------------------------------------------------- #
# Serving: the gap of each served token below the reference's best
# ---------------------------------------------------------------------- #

PAD = 256


@functools.lru_cache(maxsize=None)
def _jit_layer(quant: bool):
    return jax.jit(lambda c, w, h: layer(c, w, h, quant), static_argnums=0)


ROWS = 64     # served positions are read in blocks of this many


@functools.partial(jax.jit, static_argnums=(0, 5))
def _gaps(c, top, h, start, toks, n_rows, ctrl_h=None):
    """Gaps at rows [start, start + n_rows) of h: of the served tokens,
    and of the tokens the control's float8 logits put first."""
    D = h.shape[1]
    ref = logits(c, top, jax.lax.dynamic_slice(h, (start, 0), (n_rows, D)))
    best = jnp.max(ref, -1)
    out = best - jnp.take_along_axis(ref, toks[:, None], -1)[:, 0]
    if ctrl_h is None:
        return out, out
    ch = jax.lax.dynamic_slice(ctrl_h, (start, 0), (n_rows, D))
    pick = jnp.argmax(logits(c, top, ch, True), -1)
    return out, best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]


def served_gaps(c: dict, seed: int, items: list, control: bool = False):
    """For each (prompt, served tokens): the gap by which each served
    token's reference logit lies below the reference's best at its
    position.  With ``control`` also the gap of the token the float8
    forward puts first there.  Returns (gaps, control gaps), each one
    float array per item."""
    c = W.Frozen(c)
    top = W.make_top(c, seed)
    seqs, hs, cs = [], [], []
    for prompt, served in items:
        full = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
        S = -(-(len(full) + ROWS) // PAD) * PAD
        padded = np.zeros(S, np.int32)
        padded[:len(full)] = full
        seqs.append((len(prompt), np.asarray(served, np.int32)))
        hs.append(embed(c, top, jnp.asarray(padded)))
        cs.append(embed(c, top, jnp.asarray(padded), True) if control
                  else None)
    for i in range(c["num_hidden_layers"]):
        w = W.make_layer(c, seed, i)
        hs = [_jit_layer(False)(c, w, h) for h in hs]
        if control:
            cs = [_jit_layer(True)(c, w, h) for h in cs]
        del w
    out, ctrl = [], []
    for (P, served), h, ch in zip(seqs, hs, cs):
        g_all, c_all = [], []
        for r0 in range(0, len(served), ROWS):
            toks = np.zeros(ROWS, np.int32)
            part = served[r0:r0 + ROWS]
            toks[:len(part)] = part
            g, cg = _gaps(c, top, h, P - 1 + r0, jnp.asarray(toks), ROWS,
                          ch)
            g_all.append(np.asarray(g)[:len(part)])
            c_all.append(np.asarray(cg)[:len(part)])
        out.append(np.concatenate(g_all))
        ctrl.append(np.concatenate(c_all))
    return out, (ctrl if control else None)


# ---------------------------------------------------------------------- #
# Training: three AdamW steps from the seed's weights
# ---------------------------------------------------------------------- #

def _row_loss(c, params, row, quant):
    h = embed(c, params, row[:-1], quant)
    for i in range(c["num_hidden_layers"]):
        w = jax.tree.map(lambda a: a[i], params["runs"][0])
        h = jax.checkpoint(lambda w, h: layer(c, w, h, quant))(w, h)
    lg = logits(c, params, h, quant)
    lse = jax.nn.logsumexp(lg, -1)
    gold = jnp.take_along_axis(lg, row[1:, None], -1)[:, 0]
    return jnp.mean(lse - gold)


@functools.lru_cache(maxsize=None)
def _jit_row_grad(quant: bool):
    """Loss and gradient of one row, the forward on the weights rounded to
    the dtype each leaf is stored in (as the program holds them)."""
    def f(c, w, row):
        def loss(w):
            fwd = jax.tree.map(lambda x, d: x.astype(d).astype(F32), w,
                               _dtypes(c))
            return _row_loss(c, fwd, row, quant)
        return jax.value_and_grad(loss)(w)
    return jax.jit(f, static_argnums=0)


_add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))


@functools.partial(jax.jit, static_argnums=(0,))
def _clip_lr(opt, grads, n_rows, step):
    """The mean gradient over rows clipped by its global norm, and the
    step's learning rate (linear warm-up, then cosine)."""
    lr_peak, _, _, _, _, clip, warm, total = opt
    g = jax.tree.map(lambda x: x / n_rows, grads)
    gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    g = jax.tree.map(lambda x: x * jnp.minimum(1.0, clip / gnorm), g)
    warm_lr = lr_peak * (step + 1) / max(warm, 1)
    prog = jnp.clip((step - warm) / max(total - warm, 1), 0.0, 1.0)
    lr = jnp.where(step < warm, warm_lr,
                   lr_peak * 0.5 * (1.0 + jnp.cos(jnp.pi * prog)))
    return g, lr


def _opt_tuple(job: dict) -> tuple:
    o = job["optimizer"]
    return (float(o["lr"]), float(o["betas"][0]), float(o["betas"][1]),
            float(o["eps"]), float(o["weight_decay"]), float(o["clip_norm"]),
            int(o["warmup_steps"]), int(o["total_steps"]))


def _full_params(c, seed):
    top = W.make_top(c, seed)
    layers = [W.make_layer(c, seed, i) for i in range(c["num_hidden_layers"])]
    run = jax.tree.map(lambda *xs: jnp.stack(xs), *layers)
    return jax.tree.map(lambda x: x.astype(F32), dict(top, runs=[run]))


@functools.partial(jax.jit, static_argnums=(0,))
def _update(opt, m, v, w, g, lr, t):
    _, b1, b2, eps, wd, *_ = opt
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
    w = jax.tree.map(
        lambda m, v, w: w - lr * ((m / (1 - b1 ** t))
                                  / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
                                  + wd * w), m, v, w)
    return m, v, w


def leaf_norms(tree) -> dict:
    """'path' -> float32 norm of each leaf."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): float(jnp.sqrt(jnp.sum(
        jnp.square(x.astype(F32))))) for p, x in flat}


def train_steps(c: dict, job: dict, seed: int, batches: list,
                quant: bool = False, devices=None) -> dict:
    """The reference's three steps over ``batches`` (each (rows, S+1)):
    each step's loss, the first clipped gradient's leaf norms and leaves
    (on the host, by path), and the leaf norms of the weights' change after
    the last step.  The rows are spread over ``devices`` (default: the
    first), one row at a time."""
    c = W.Frozen(c)
    devices = devices or jax.devices()[:1]
    opt = _opt_tuple(job)
    w = _full_params(c, seed)
    m = jax.tree.map(jnp.zeros_like, w)
    v = jax.tree.map(jnp.zeros_like, w)
    losses, first_grad = [], None
    grad = _jit_row_grad(quant)
    for step, rows in enumerate(batches):
        on = [w] + [jax.device_put(w, d) for d in devices[1:]]
        parts, loss = [None] * len(devices), []
        for i, r in enumerate(rows):
            k = i % len(devices)
            l, g = grad(c, on[k], jax.device_put(jnp.asarray(r), devices[k]))
            loss.append(l)
            parts[k] = g if parts[k] is None else _add(parts[k], g)
        total = parts[0]
        for p in parts[1:]:
            if p is not None:
                total = _add(total, jax.device_put(p, devices[0]))
        del on, parts
        g, lr = _clip_lr(opt, total, float(len(rows)), step)
        del total
        if first_grad is None:
            first_grad = leaf_norms(g)
            grad_tree = {jax.tree_util.keystr(p): np.asarray(x)
                         for p, x in jax.tree_util.tree_flatten_with_path(
                             jax.device_get(g))[0]}
        m, v, w = _update(opt, m, v, w, g, lr, step + 1)
        del g
        losses.append(sum(float(x) for x in loss) / len(rows))
    del m, v
    change = leaf_norms(_minus_seed(c, seed, w))
    return {"loss": losses, "grad": first_grad, "change": change,
            "grad_tree": grad_tree}


def _minus_seed(c, seed, w):
    """w minus the seed's weights, leaf by leaf."""
    top = W.make_top(c, seed)
    out = {n: w[n] - top[n].astype(F32) for n in top}
    run = w["runs"][0]
    rows = [jax.tree.map(lambda x, i=i: x[i], run)
            for i in range(c["num_hidden_layers"])]
    diff = [jax.tree.map(lambda a, b: a - b.astype(F32), r,
                         W.make_layer(c, seed, i)) for i, r in enumerate(rows)]
    out["runs"] = [jax.tree.map(lambda *xs: jnp.stack(xs), *diff)]
    return out


def _dtypes(c) -> dict:
    """The dtype each leaf is stored in, as a tree like the weights'."""
    tree = {n: s[1] for n, s in W.top_specs(c).items()}
    tree["runs"] = [W.nest({p: s[1]
                            for p, s in W.layer_specs(c, 0).items()})]
    return tree
