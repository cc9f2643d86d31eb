"""Plain reference for the Granite 4.0-H block family: a period of Mamba-2
layers with one grouped-query attention layer that has no positions at all,
every layer followed by a dense gated MLP; four scalar multipliers; a head
tied to the embedding.

Straightforward `jax.numpy` in float32 with
`jax.default_matmul_precision("highest")`. No kernels, no cache; the
state-space recurrence runs as it is written, one position after another
(`lax.scan` over time, segments of it checkpointed so that its backward pass
fits, which changes no number), and attention is plain softmax, a query head
at a time. The per-token work (the MLP, the head and the loss) runs in blocks
of tokens under `jax.checkpoint`: beside 12.35 GB of float32 weights,
gradients and Adam moments the whole `[8192, 16384]` panels do not fit one
chip; computing in blocks leaves no mathematics out. It imports nothing of
the program and makes its own weights from the seed (`make_init`), in the
tree layout the program's entry points take; `P` is the number of periods
held, a period's runs of like layers are `r0 .. r{n-1}`, each of `n` layers:

    embed [V, D]  lnfg [D]                       (no Wout: the head is embed^T)
    blocks.r<j>, every layer:  ln1 ln2 [P, n, D]
        W_gu [P, n, D, 2F] (gate | up)  W_down [P, n, F, D]
      a `mamba` layer:  Win [P, n, D, 2 Di + 2 G N] (z | x B C)
        Wdt [P, n, D, H]  conv [P, n, W, Di + 2 G N]  conv_b [P, n, Di + 2 G N]
        A_log dt_bias D [P, n, H]  gnorm [P, n, Di]  Wo [P, n, Di, D]
      an `attention` layer:  Wq [P, n, D, H dh]  Wk Wv [P, n, D, Hkv dh]
        Wo [P, n, H dh, D]

Per layer `h += r mixer(norm(h)); h += r mlp(norm(h))` with
`norm(x) = x / sqrt(mean(x^2) + eps) * (1 + w)`, `w` nought at the start
(the published `w`, 1 at the start, less one: the same function and the same
update); `h0 = embedding_multiplier * embed[tokens]`;
`logits = norm(h) embed^T / logits_scaling`.

`precision` lets the same code stand in the program's place at a lower
precision (the control of the `correct` comparison), as in `gpt2_block.py`;
the step's projection, the recurrence and the attention's products stay
float32, as in the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
HI = lax.Precision.HIGHEST
TOKEN_BLOCK = 2048      # tokens a block of the per-token work


def seed_key(seed: int):
    """Any whole number up to 2**63 (the driver's seeds pass 2**31) as the
    two 32-bit words the initialiser takes."""
    import numpy as np
    seed = int(seed)
    return np.array([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF],
                    np.uint32)


def runs(s):
    """A period's runs of like layers: [(kind, length)]."""
    out = []
    for kind in s.layer_types:
        if out and out[-1][0] == kind:
            out[-1][1] += 1
        else:
            out.append([kind, 1])
    return [(k, n) for k, n in out]


def layer_shapes(s, kind: str, lead: tuple) -> dict:
    d, f = s.hidden_size, s.shared_intermediate_size
    out = {"ln1": (d,), "ln2": (d,), "W_gu": (d, 2 * f), "W_down": (f, d)}
    if kind == "mamba":
        di = s.mamba_n_heads * s.mamba_d_head
        xbc = di + 2 * s.mamba_n_groups * s.mamba_d_state
        out.update({"Win": (d, di + xbc), "Wdt": (d, s.mamba_n_heads),
                    "conv": (s.mamba_d_conv, xbc), "conv_b": (xbc,),
                    "A_log": (s.mamba_n_heads,),
                    "dt_bias": (s.mamba_n_heads,), "D": (s.mamba_n_heads,),
                    "gnorm": (di,), "Wo": (di, d)})
    elif kind == "attention":
        h, hk, dh = s.num_attention_heads, s.num_key_value_heads, s.head_dim
        out.update({"Wq": (d, h * dh), "Wk": (d, hk * dh),
                    "Wv": (d, hk * dh), "Wo": (h * dh, d)})
    else:
        raise ValueError(f"unknown layer kind {kind!r}")
    return {k: lead + v for k, v in out.items()}


def leaf_shapes(s) -> dict:
    return {"embed": (s.vocab_size, s.hidden_size),
            "lnfg": (s.hidden_size,),
            "blocks": {f"r{j}": layer_shapes(s, kind, (s.n_periods, n))
                       for j, (kind, n) in enumerate(runs(s))}}


def _mix(x):
    """murmur3's 32-bit finaliser."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def _uniforms(shape, salt):
    n = 1
    for k in shape:
        n *= k
    idx = lax.iota(jnp.uint32, n).reshape(shape)
    a = _mix(idx ^ salt)
    b = _mix(a + jnp.uint32(0x9E3779B9))
    return (((a >> 8).astype(F32) + 0.5) * F32(2.0 ** -24),
            ((b >> 8).astype(F32) + 0.5) * F32(2.0 ** -24))


def _normal(shape, salt):
    """Standard normals as a pure function of (element index, salt): two
    hashed uniforms through Box-Muller; the same whatever the sharding."""
    u1, u2 = _uniforms(shape, salt)
    return jnp.sqrt(-2.0 * jnp.log(u1)) * jnp.cos(F32(2.0 * jnp.pi) * u2)


def _init_tree(s, seed):
    """Matrices and the taps N(0, 1 / fan_in), the embedding N(0, 0.02^2),
    the norms' w and the convolution's bias nought, D and the gated norm's
    gain 1, A_log = log U(1, 16), dt_bias the inverse softplus of a step
    log-uniform in [0.001, 0.1] (Mamba-2's own initialiser)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        leaf_shapes(s), is_leaf=lambda x: isinstance(x, tuple))
    base = _mix(seed[0] ^ _mix(seed[1] + jnp.uint32(0x7F4A7C15)))
    out = []
    for i, (path, shape) in enumerate(flat):
        name = path[-1].key
        salt = _mix(base + jnp.uint32(i + 1))
        if name.startswith("W") or name == "conv":
            out.append(_normal(shape, salt) / jnp.sqrt(F32(shape[-2])))
        elif name == "embed":
            out.append(0.02 * _normal(shape, salt))
        elif name == "A_log":
            out.append(jnp.log(1.0 + 15.0 * _uniforms(shape, salt)[0]))
        elif name == "dt_bias":
            dt = jnp.exp(_uniforms(shape, salt)[1]
                         * (jnp.log(0.1) - jnp.log(0.001)) + jnp.log(0.001))
            out.append(dt + jnp.log(-jnp.expm1(-dt)))
        elif name in ("D", "gnorm"):
            out.append(jnp.ones(shape, F32))
        else:                               # ln1 ln2 lnfg conv_b
            out.append(jnp.zeros(shape, F32))
    return jax.tree_util.tree_unflatten(treedef, out)


def make_init(s, shardings=None):
    """One jitted initialiser: `seed_key(seed)` -> float32 tree, made on the
    device in the given shardings."""
    return jax.jit(functools.partial(_init_tree, s), out_shardings=shardings)


# ---------------------------------------------------------------------------
# matrix products at a stated precision (as references/gpt2_block.py)
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
    return jnp.matmul(a, w, precision=HI)


def _low(precision: str):
    """A matrix product whose three forms (forward, gradient of the input,
    gradient of the weight) all round their operands as `precision` says."""
    @jax.custom_vjp
    def f(a, w):
        return _mm(a, w, precision)

    def fwd(a, w):
        return _mm(a, w, precision), (a, w)

    def bwd(res, g):
        a, w = res
        ga = _mm(g, w.T, precision)
        gw = _mm(a.reshape(-1, a.shape[-1]).T,
                 g.reshape(-1, g.shape[-1]), precision)
        return ga, gw

    f.defvjp(fwd, bwd)
    return f


def _mm_fn(precision: str):
    if precision == "f32":
        return lambda a, w: _mm(a, w, "f32")
    return _low(precision)


def by_token_blocks(fn, x, *more):
    """`fn(block of x's tokens, block of each of more's, ...)` over blocks
    of `TOKEN_BLOCK` tokens of x [N, ...], each block checkpointed; the
    results stacked back to [N, ...]."""
    n = x.shape[0]
    blk = max(c for c in range(1, min(n, TOKEN_BLOCK) + 1) if n % c == 0)

    def split(a):
        return a.reshape((n // blk, blk) + a.shape[1:])

    out = lax.map(lambda xs: jax.checkpoint(fn)(*xs),
                  tuple(split(a) for a in (x,) + more))
    return jax.tree_util.tree_map(
        lambda o: o.reshape((n,) + o.shape[2:]), out)


# ---------------------------------------------------------------------------
# the layers
# ---------------------------------------------------------------------------

def rms_norm(x, w, eps):
    """x / sqrt(mean(x^2) + eps) * (1 + w)."""
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                         + eps) * (1.0 + w)


def attention(x, p, s, mm):
    """Grouped-query causal attention with no positions, no norms and no
    gate; the scores are scaled by `attention_multiplier`; x [B, T, D]."""
    b, t, _ = x.shape
    h, hk, dh = s.num_attention_heads, s.num_key_value_heads, s.head_dim
    q = mm(x, p["Wq"]).reshape(b, t, h, dh)
    k = mm(x, p["Wk"]).reshape(b, t, hk, dh)
    v = mm(x, p["Wv"]).reshape(b, t, hk, dh)
    live = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]

    def one_head(_, qh_i):
        qh, i = qh_i                         # [B, T, dh], head index
        kh = jnp.take(k, i // (h // hk), axis=2)
        vh = jnp.take(v, i // (h // hk), axis=2)
        sc = jnp.einsum("btd,bsd->bts", qh, kh, precision=HI) \
            * s.attention_multiplier
        w = jax.nn.softmax(jnp.where(live[None], sc, -jnp.inf), axis=-1)
        return None, jnp.einsum("bts,bsd->btd", w, vh, precision=HI)

    _, a = lax.scan(jax.checkpoint(one_head, prevent_cse=False), None,
                    (jnp.moveaxis(q, 2, 0), jnp.arange(h)))
    return mm(jnp.moveaxis(a, 0, 2).reshape(b, t, h * dh), p["Wo"])


def causal_conv(x, w, bias):
    """Depthwise causal convolution with a bias: x [B, T, C], w [W, C];
    y_t = bias + sum_i w[i] x_{t - (W - 1) + i}."""
    width = w.shape[0]
    t = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    return bias + sum(xp[:, i:i + t] * w[i] for i in range(width))


def ssm_recurrence(x, bm, cm, d, la):
    """The state-space recurrence as written, a position at a time. x
    [B, T, H, P], bm and cm [B, T, G, N], the step d and the log decay la
    [B, T, H]. A head's state S is [P, N], from nought:
        S = exp(la_t) S + d_t x_t B_t^T;   y_t = S C_t."""
    b, t, h, p = x.shape
    r = h // bm.shape[2]

    def step(S, xs):
        x_t, b_t, c_t, d_t, l_t = xs
        S = S * jnp.exp(l_t)[..., None, None] \
            + (d_t[..., None] * x_t)[..., :, None] \
            * jnp.repeat(b_t, r, axis=1)[..., None, :]
        return S, jnp.einsum("bhpn,bhn->bhp", S, jnp.repeat(c_t, r, axis=1),
                             precision=HI)

    seg = max(c for c in range(1, min(t, 128) + 1) if t % c == 0)

    def segment(S, xs):
        return lax.scan(step, S, xs)

    xs = tuple(jnp.moveaxis(a, 1, 0).reshape((t // seg, seg) + a.shape[:1]
                                             + a.shape[2:])
               for a in (x, bm, cm, d, la))
    _, y = lax.scan(jax.checkpoint(segment, prevent_cse=False),
                    jnp.zeros((b, h, p, bm.shape[3]), F32), xs)
    return jnp.moveaxis(y.reshape((t,) + y.shape[2:]), 0, 1)


def mamba(x, p, s, mm):
    """x [B, T, D] -> [B, T, D]: Mamba-2 with the gate before the norm."""
    b, t, _ = x.shape
    h, hd, n, g = (s.mamba_n_heads, s.mamba_d_head, s.mamba_d_state,
                   s.mamba_n_groups)
    di = h * hd
    zxbc = mm(x, p["Win"])
    dt = jnp.matmul(x, p["Wdt"], precision=HI)
    z = zxbc[..., :di]
    xbc = jax.nn.silu(causal_conv(zxbc[..., di:], p["conv"], p["conv_b"]))
    xs = xbc[..., :di].reshape(b, t, h, hd)
    step = jax.nn.softplus(dt + p["dt_bias"])
    y = ssm_recurrence(xs, xbc[..., di:di + g * n].reshape(b, t, g, n),
                       xbc[..., di + g * n:].reshape(b, t, g, n), step,
                       -jnp.exp(p["A_log"]) * step)
    y = (y + p["D"][:, None] * xs).reshape(b, t, di) * jax.nn.silu(z)
    y = y.reshape(b, t, g, di // g)
    y = y * lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                      + s.rms_norm_eps)
    return mm(y.reshape(b, t, di) * p["gnorm"], p["Wo"])


def mlp(x, p, s, mm):
    """(silu(g) * u) W_down with [g | u] = x W_gu, by blocks of tokens."""
    f = s.shared_intermediate_size

    def block(xb):
        gu = mm(xb, p["W_gu"])
        return mm(jax.nn.silu(gu[..., :f]) * gu[..., f:], p["W_down"])

    return by_token_blocks(block, x.reshape(-1, x.shape[-1])).reshape(x.shape)


MIXERS = {"mamba": mamba, "attention": attention}


def layer(h, p, s, kind: str, mm):
    r = s.residual_multiplier
    h = h + r * MIXERS[kind](rms_norm(h, p["ln1"], s.rms_norm_eps), p, s, mm)
    return h + r * mlp(rms_norm(h, p["ln2"], s.rms_norm_eps), p, s, mm)


def hidden(s, params, tokens, precision: str = "f32"):
    """tokens [B, T] -> final-norm hidden states [B, T, D], float32."""
    mm = _mm_fn(precision)
    h = s.embedding_multiplier * params["embed"][tokens]

    def period(h, blocks):
        for j, (kind, _) in enumerate(runs(s)):
            one = jax.checkpoint(
                lambda h_, p_, kind=kind: layer(h_, p_, s, kind, mm))
            h, _ = lax.scan(lambda h_, p_, one=one: (one(h_, p_), None), h,
                            blocks[f"r{j}"])
        return h, None

    h, _ = lax.scan(period, h, params["blocks"])
    return rms_norm(h, params["lnfg"], s.rms_norm_eps)


def nll_sum(s, params, tokens, targets, precision: str = "f32"):
    """Summed next-token negative log-likelihood of rows [B, T]; the head
    is the embedding, the logits are divided by `logits_scaling`."""
    h = hidden(s, params, tokens, precision)
    mm = _mm_fn(precision)
    head = params["embed"].T

    def block(hb, tb):
        logp = jax.nn.log_softmax(mm(hb, head) / s.logits_scaling, axis=-1)
        return jnp.take_along_axis(logp, tb[:, None], axis=-1)[:, 0]

    return -jnp.sum(by_token_blocks(block, h.reshape(-1, h.shape[-1]),
                                    targets.reshape(-1)))


def loss_and_grad(s, params, tokens, targets, rows_per_block: int,
                  precision: str = "f32"):
    """Mean loss over all rows and its gradient, taken in blocks of rows so
    that it fits: the mean of the blocks' sums. One block is taken as it
    is: a second copy of the gradient beside the first does not fit."""
    b, t = tokens.shape
    nb = b // rows_per_block
    vg = jax.value_and_grad(
        lambda p, a, c: nll_sum(s, p, a, c, precision))
    n = F32(b * t)
    if nb == 1:
        loss, g = vg(params, tokens, targets)
    else:
        tk = tokens.reshape(nb, rows_per_block, t)
        tg = targets.reshape(nb, rows_per_block, t)

        def body(acc, xs):
            loss, g = vg(params, *xs)
            return (acc[0] + loss,
                    jax.tree_util.tree_map(jnp.add, acc[1], g)), None

        zero = (jnp.zeros((), F32),
                jax.tree_util.tree_map(jnp.zeros_like, params))
        (loss, g), _ = lax.scan(body, zero, (tk, tg))
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


def _stacked(path) -> bool:
    return any(getattr(k, "key", None) == "blocks" for k in path)


def leaf_norms(tree):
    """One norm for every unstacked leaf and one for every layer (period,
    place in its run) of a stacked leaf, as one flat float32 vector in a
    fixed (sorted) order."""
    out = []
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        x = x.astype(F32)
        if _stacked(path):
            out.append(jnp.sqrt(jnp.sum(
                jnp.square(x), axis=tuple(range(2, x.ndim)))).reshape(-1))
        else:
            out.append(jnp.sqrt(jnp.sum(jnp.square(x)))[None])
    return jnp.concatenate(out)


def leaf_samples(tree, stride: int = 64):
    """Every `stride`-th element of every leaf (of every layer of a stacked
    leaf), rows in the order of `leaf_norms`."""
    out = []
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        x = x.astype(F32)
        rows = x.reshape(x.shape[0] * x.shape[1], -1) if _stacked(path) \
            else x.reshape(1, -1)
        out.append(rows[:, ::min(stride, max(1, rows.shape[1] // 8))])
    return out


def leaf_names(s) -> list:
    names = []
    for path, shape in jax.tree_util.tree_flatten_with_path(
            leaf_shapes(s), is_leaf=lambda x: isinstance(x, tuple))[0]:
        name = ".".join(k.key for k in path)
        if path[0].key == "blocks":
            names += [f"{name}[{i}.{k}]" for i in range(shape[0])
                      for k in range(shape[1])]
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
        norms, samples = leaf_norms(g), leaf_samples(g)
        params, m, v = adam(params, g, m, v, t, lr)
        return params, m, v, loss, norms, samples

    kw = {}
    if shardings is not None:
        kw = dict(in_shardings=(shardings, shardings, shardings,
                                batch_sharding, batch_sharding, None),
                  out_shardings=(shardings, shardings, shardings, None,
                                 None, None))
    return jax.jit(step, donate_argnums=(0, 1, 2), **kw)
