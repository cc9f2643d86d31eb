"""Plain reference for the GPT-2 block family (Cerebras-GPT, GPT-2).

Straightforward `jax.numpy` in float32 with
`jax.default_matmul_precision("highest")`: pre-LN block, learned positions,
full multi-head causal attention, GELU MLP, separate output head, mean
next-token cross-entropy, Adam. No kernels, no cache, no batching tricks.
It imports nothing of the program and makes its own weights from the seed
(`make_init`), in the tree layout the program's entry points take:

    embed [V, D]  pos [P, D]  lnfg lnfb [D]  Wout [D, V]
    blocks: Wq Wk Wv Wo [L, D, D]  W1 [L, D, F] b1 [L, F]  W2 [L, F, D]
            b2 [L, D]  ln1g ln1b ln2g ln2b [L, D]

Departures from the published models are listed in each configuration's
file. `precision` lets the same code stand in the program's place at a lower
precision (the control of the `correct` comparison): "bf16" rounds every
matrix product's operands to bfloat16, "int8w" rounds the weights to int8
with one scale per output channel, "int8" rounds the activations too (one
scale per row), "fp8" rounds both to float8_e4m3fn.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32


def seed_key(seed: int):
    """Any whole number up to 2**63 (the driver's seeds pass 2**31) as the
    two 32-bit words the initialiser takes."""
    import numpy as np
    seed = int(seed)
    return np.array([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF],
                    np.uint32)


def leaf_shapes(s) -> dict:
    d, f, L, v, p = s.n_embd, s.n_inner, s.n_layer, s.vocab_size, \
        s.n_positions
    return {
        "embed": (v, d), "pos": (p, d), "lnfg": (d,), "lnfb": (d,),
        "Wout": (d, v),
        "blocks": {
            "Wq": (L, d, d), "Wk": (L, d, d), "Wv": (L, d, d),
            "Wo": (L, d, d), "W1": (L, d, f), "b1": (L, f),
            "W2": (L, f, d), "b2": (L, d), "ln1g": (L, d), "ln1b": (L, d),
            "ln2g": (L, d), "ln2b": (L, d)}}


def _mix(x):
    """murmur3's 32-bit finaliser: every input bit reaches every output
    bit."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def _normal(shape, salt):
    """Standard normals as a pure function of (element index, salt): two
    hashed uniforms through Box-Muller. The same whatever the sharding, and
    a few elementwise operations to compile (jax.random's threefry took 30 s
    of every set-up at these sizes on the chip)."""
    n = 1
    for k in shape:
        n *= k
    idx = lax.iota(jnp.uint32, n).reshape(shape)
    a = _mix(idx ^ salt)
    b = _mix(a + jnp.uint32(0x9E3779B9))
    u1 = ((a >> 8).astype(F32) + 0.5) * F32(2.0 ** -24)
    u2 = ((b >> 8).astype(F32) + 0.5) * F32(2.0 ** -24)
    return jnp.sqrt(-2.0 * jnp.log(u1)) * jnp.cos(F32(2.0 * jnp.pi) * u2)


def _init_tree(s, seed):
    shapes = leaf_shapes(s)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    base = _mix(seed[0] ^ _mix(seed[1] + jnp.uint32(0x7F4A7C15)))
    out = []
    for i, (path, shape) in enumerate(flat):
        name = path[-1].key
        z = _normal(shape, _mix(base + jnp.uint32(i + 1)))
        if name.startswith("W"):
            out.append(z / jnp.sqrt(F32(shape[-2])))
        elif name.endswith("g"):
            out.append(1.0 + 0.02 * z)
        else:
            out.append(0.02 * z)
    return jax.tree_util.tree_unflatten(treedef, out)


def make_init(s, shardings=None):
    """One jitted initialiser: `seed_key(seed)` -> float32 tree, made on the
    device in the given shardings."""
    return jax.jit(functools.partial(_init_tree, s), out_shardings=shardings)


# ---------------------------------------------------------------------------
# matrix products at a stated precision
# ---------------------------------------------------------------------------

def _fake_int8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(x / scale) * scale


def _fake_fp8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _mm(a, w, precision: str):
    """a [..., K] @ w [K, N] with both operands rounded as `precision`
    says; the product itself is always accumulated in float32."""
    if precision == "bf16":
        a, w = (a.astype(jnp.bfloat16).astype(F32),
                w.astype(jnp.bfloat16).astype(F32))
    elif precision == "int8w":
        w = _fake_int8(w, 0)
    elif precision == "int8":
        a, w = _fake_int8(a, -1), _fake_int8(w, 0)
    elif precision == "fp8":
        a, w = _fake_fp8(a, -1), _fake_fp8(w, 0)
    elif precision != "f32":
        raise ValueError(f"unknown precision {precision!r}")
    return jnp.matmul(a, w, precision=lax.Precision.HIGHEST)


def _low(precision: str):
    """A matrix product whose three forms (forward, gradient of the input,
    gradient of the weight) all round their operands as `precision` says:
    what a training step with lower-precision matrix units computes."""
    @jax.custom_vjp
    def f(a, w):
        return _mm(a, w, precision)

    def fwd(a, w):
        return _mm(a, w, precision), (a, w)

    def bwd(res, g):
        a, w = res
        ga = _mm(g, w.T, precision)
        a2 = a.reshape(-1, a.shape[-1])
        g2 = g.reshape(-1, g.shape[-1])
        gw = _mm(a2.T, g2, precision)
        return ga, gw

    f.defvjp(fwd, bwd)
    return f


def _mm_fn(precision: str):
    if precision == "f32":
        return lambda a, w: _mm(a, w, "f32")
    return _low(precision)


def _ln(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * g + b


def _block(h, p, s, mm):
    b, t, d = h.shape
    x = _ln(h, p["ln1g"], p["ln1b"], s.eps)
    q = mm(x, p["Wq"]).reshape(b, t, s.n_head, s.d_head)
    k = mm(x, p["Wk"]).reshape(b, t, s.n_head, s.d_head)
    v = mm(x, p["Wv"]).reshape(b, t, s.n_head, s.d_head)
    sc = jnp.einsum("bthd,bshd->bhts", q, k,
                    precision=lax.Precision.HIGHEST) / jnp.sqrt(
                        F32(s.d_head))
    live = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    sc = jnp.where(live[None, None], sc, -jnp.inf)
    w = jax.nn.softmax(sc, axis=-1)
    a = jnp.einsum("bhts,bshd->bthd", w, v,
                   precision=lax.Precision.HIGHEST).reshape(b, t, d)
    h = h + mm(a, p["Wo"])
    x = _ln(h, p["ln2g"], p["ln2b"], s.eps)
    z = jax.nn.gelu(mm(x, p["W1"]) + p["b1"], approximate=True)
    return h + mm(z, p["W2"]) + p["b2"]


def hidden(s, params, tokens, precision: str = "f32"):
    """tokens [B, T] -> final-LN hidden states [B, T, D], float32."""
    mm = _mm_fn(precision)
    t = tokens.shape[1]
    h = params["embed"][tokens] + params["pos"][:t][None]

    def body(h, p):
        return _block(h, p, s, mm), None

    h, _ = lax.scan(jax.checkpoint(body, prevent_cse=False), h,
                    params["blocks"])
    return _ln(h, params["lnfg"], params["lnfb"], s.eps)


def logits_at(s, params, tokens, where, precision: str = "f32"):
    """Logits [B, P, V] at the positions `where` [B, P] of `tokens`."""
    h = hidden(s, params, tokens, precision)
    h = jnp.take_along_axis(h, where[..., None], axis=1)
    return _mm_fn(precision)(h, params["Wout"])


def nll_sum(s, params, tokens, targets, precision: str = "f32"):
    """Summed next-token negative log-likelihood of rows [B, T]."""
    h = hidden(s, params, tokens, precision)
    logits = _mm_fn(precision)(h, params["Wout"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, targets[..., None],
                                        axis=-1))


def loss_and_grad(s, params, tokens, targets, rows_per_block: int,
                  precision: str = "f32"):
    """Mean loss over all rows and its gradient, taken in blocks of rows so
    that it fits beside nothing else: the mean of the blocks' sums."""
    b, t = tokens.shape
    nb = b // rows_per_block
    tk = tokens.reshape(nb, rows_per_block, t)
    tg = targets.reshape(nb, rows_per_block, t)
    vg = jax.value_and_grad(
        lambda p, a, c: nll_sum(s, p, a, c, precision))

    def body(acc, xs):
        loss, g = vg(params, *xs)
        return (acc[0] + loss,
                jax.tree_util.tree_map(jnp.add, acc[1], g)), None

    zero = (jnp.zeros((), F32),
            jax.tree_util.tree_map(jnp.zeros_like, params))
    (loss, g), _ = lax.scan(body, zero, (tk, tg))
    n = F32(b * t)
    return loss / n, jax.tree_util.tree_map(lambda x: x / n, g)


def adam(params, g, m, v, step, lr: float, b1: float = 0.9,
         b2: float = 0.999, eps: float = 1e-8):
    """Adam as published (Kingma & Ba), bias-corrected, no weight decay."""
    t = F32(step)

    def upd(p, g_, m_, v_):
        m2 = b1 * m_ + (1 - b1) * g_
        v2 = b2 * v_ + (1 - b2) * g_ * g_
        mhat = m2 / (1 - b1 ** t)
        vhat = v2 / (1 - b2 ** t)
        return p - lr * mhat / (jnp.sqrt(vhat) + eps), m2, v2

    out = jax.tree_util.tree_map(upd, params, g, m, v)
    pick = lambda i: jax.tree_util.tree_map(  # noqa: E731
        lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2)


def leaf_norms(tree):
    """One norm for every unstacked leaf and one for every layer of a
    stacked leaf, as one flat float32 vector in a fixed (sorted) order."""
    out = []
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        x = x.astype(F32)
        stacked = any(getattr(k, "key", None) == "blocks" for k in path)
        if stacked:
            out.append(jnp.sqrt(jnp.sum(jnp.square(x),
                                        axis=tuple(range(1, x.ndim)))))
        else:
            out.append(jnp.sqrt(jnp.sum(jnp.square(x)))[None])
    return jnp.concatenate(out)


def leaf_samples(tree, stride: int = 64):
    """Every `stride`-th element of every leaf (of every layer of a stacked
    leaf), rows in the order of `leaf_norms`: enough of a gradient to read
    its direction, small enough to keep through a window."""
    out = []
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        x = x.astype(F32)
        stacked = any(getattr(k, "key", None) == "blocks" for k in path)
        rows = x.reshape(x.shape[0], -1) if stacked else x.reshape(1, -1)
        out.append(rows[:, ::stride])
    return out


def leaf_names(s) -> list:
    names = []
    shapes = leaf_shapes(s)
    for path, _ in jax.tree_util.tree_flatten_with_path(
            shapes, is_leaf=lambda x: isinstance(x, tuple))[0]:
        name = ".".join(k.key for k in path)
        if path[0].key == "blocks":
            names += [f"{name}[{i}]" for i in range(s.n_layer)]
        else:
            names.append(name)
    return names


def diff_norms(a, b):
    return leaf_norms(jax.tree_util.tree_map(jnp.subtract, a, b))


def make_train_step(s, lr: float, rows_per_block: int,
                    precision: str = "f32", shardings=None,
                    batch_sharding=None):
    """One jitted reference step: (params, m, v, tokens, targets, t) ->
    (params, m, v, loss, leaf norms of the gradient, its leaf samples). State
    is donated so that three steps need one copy of it."""
    def step(params, m, v, tokens, targets, t):
        loss, g = loss_and_grad(s, params, tokens, targets, rows_per_block,
                                precision)
        params, m, v = adam(params, g, m, v, t, lr)
        return params, m, v, loss, leaf_norms(g), leaf_samples(g)

    kw = {}
    if shardings is not None:
        kw = dict(in_shardings=(shardings, shardings, shardings,
                                batch_sharding, batch_sharding, None),
                  out_shardings=(shardings, shardings, shardings, None,
                                 None, None))
    return jax.jit(step, donate_argnums=(0, 1, 2), **kw)
