"""Plain reference for the Qwen3-Next block family: a period of Gated
DeltaNet layers closed by one gated grouped-query attention layer, each
followed by a top-k mixture of experts with a gated shared expert.

Straightforward `jax.numpy` in float32 with
`jax.default_matmul_precision("highest")`. No kernels, no cache, and the
delta rule runs as the recurrence is written, one position after another
(`lax.scan` over time; segments of it are checkpointed so that its backward
pass fits, which changes no number). It imports nothing of the program and
makes its own weights (`make_init`), in the tree layout the program's entry
points take; `P` is the number of periods held, and a period's layers are
`l0 .. l{n-1}`:

    embed [V, D]  lnfg [D]  Wout [D, V]
    blocks.l<i>, every layer:  ln1 ln2 [P, D]  router [P, D, E]
        We_gu [P, Eh, D, 2F]  We_down [P, Eh, F, D]     (gate | up, fused)
        Ws_gu [P, D, 2Fs]  Ws_down [P, Fs, D]  Ws_gate [P, D, 1]
      a `deltanet` layer:  Wqkvz [P, D, 2 Hk dk + 2 Hv dv]  Wba [P, D, 2 Hv]
        conv [P, W, 2 Hk dk + Hv dv]  A_log dt_bias [P, Hv]  gnorm [P, dv]
        Wo [P, Hv dv, D]
      a `full` layer:  Wq [P, D, 2 H dh] (a head: query | gate)
        Wk Wv [P, D, Hkv dh]  qnorm knorm [P, dh]  Wo [P, H dh, D]

Per layer `h += mixer(norm(h)); h += moe(norm(h))` with
`norm(x) = x / sqrt(mean(x^2) + eps) * (1 + w)`.

The weights are a function of `sizes.weights_key` alone: `seed_key` returns
the same two words whatever the seed. A deployment has one set of weights
and many batches; `--seed` draws the batches (configs/qwen3-next-80b-a3b
.json, `assumed`, says why).

The mixture of experts is told which experts it holds, `[0, Eh)` of `E`:
it routes over all `E`, normalises over all `top_k` chosen, and adds only
what the held experts give. With `sizes.router_trained` false the router's
matrix is frozen: the leaf gets no gradient, everything else its whole one
(a share trained alone: the configuration's file says why). `precision` lets the same code stand in the
program's place at a lower precision (the control of the `correct`
comparison), as in `gpt2_block.py`; the router's scores, the attention's
and the recurrence's products stay float32, as in the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
HI = lax.Precision.HIGHEST


def seed_key(seed: int):
    """The weights do not change with the seed: the initialiser takes its
    key from `sizes.weights_key`, and this is only the argument's shape."""
    import numpy as np
    return np.zeros((2,), np.uint32)


def _moe_shapes(s, p):
    d, e, eh, f, fs = (s.hidden_size, s.num_experts, s.experts_held,
                       s.moe_intermediate_size,
                       s.shared_expert_intermediate_size)
    return {"ln2": (p, d), "router": (p, d, e),
            "We_gu": (p, eh, d, 2 * f), "We_down": (p, eh, f, d),
            "Ws_gu": (p, d, 2 * fs), "Ws_down": (p, fs, d),
            "Ws_gate": (p, d, 1)}


def layer_shapes(s, kind: str, p: int) -> dict:
    d = s.hidden_size
    out = dict(_moe_shapes(s, p), ln1=(p, d))
    if kind == "deltanet":
        kd = s.linear_num_key_heads * s.linear_key_head_dim
        vd = s.linear_num_value_heads * s.linear_value_head_dim
        out.update({
            "Wqkvz": (p, d, 2 * kd + 2 * vd),
            "Wba": (p, d, 2 * s.linear_num_value_heads),
            "conv": (p, s.linear_conv_kernel_dim, 2 * kd + vd),
            "A_log": (p, s.linear_num_value_heads),
            "dt_bias": (p, s.linear_num_value_heads),
            "gnorm": (p, s.linear_value_head_dim), "Wo": (p, vd, d)})
    elif kind == "full":
        h, hk, dh = (s.num_attention_heads, s.num_key_value_heads,
                     s.head_dim)
        out.update({"Wq": (p, d, 2 * h * dh), "Wk": (p, d, hk * dh),
                    "Wv": (p, d, hk * dh), "qnorm": (p, dh),
                    "knorm": (p, dh), "Wo": (p, h * dh, d)})
    else:
        raise ValueError(f"unknown layer kind {kind!r}")
    return out


def leaf_shapes(s) -> dict:
    d, v = s.hidden_size, s.vocab_size
    return {"embed": (v, d), "lnfg": (d,), "Wout": (d, v),
            "blocks": {f"l{i}": layer_shapes(s, kind, s.n_periods)
                       for i, kind in enumerate(s.layer_types)}}


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


def _init_tree(s, _seed):
    shapes = leaf_shapes(s)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    key = int(s.weights_key)
    base = _mix(jnp.uint32(key & 0xFFFFFFFF)
                ^ _mix(jnp.uint32((key >> 32) & 0xFFFFFFFF)
                       + jnp.uint32(0x7F4A7C15)))
    out = []
    for i, (path, shape) in enumerate(flat):
        name = path[-1].key
        salt = _mix(base + jnp.uint32(i + 1))
        z = _normal(shape, salt)
        if name.startswith("W") or name in ("router", "conv"):
            # matrices N(0, 1 / fan_in), the taps N(0, 1 / width)
            out.append(z / jnp.sqrt(F32(shape[-2])))
        elif name == "A_log":             # log of U(1, 16)
            out.append(jnp.log(1.0 + 15.0 * _uniforms(shape, salt)[0]))
        elif name == "dt_bias":
            # the family's own initialiser (fla's GatedDeltaNet, as Mamba-2):
            # the step softplus(dt_bias) log-uniform in [0.001, 0.1], so
            # that a position forgets between 0.1% and 80% of the state
            dt = jnp.exp(_uniforms(shape, salt)[1]
                         * (jnp.log(0.1) - jnp.log(0.001)) + jnp.log(0.001))
            out.append(dt + jnp.log(-jnp.expm1(-dt)))
        elif name == "gnorm":                 # a plain gain: 1 + small
            out.append(1.0 + 0.02 * z)
        else:      # embed, and the (1 + w) norms' w: small around nought
            out.append(0.02 * z)
    return jax.tree_util.tree_unflatten(treedef, out)


def make_init(s, shardings=None):
    """One jitted initialiser: `seed_key(seed)` -> float32 tree, made on the
    device in the given shardings. The argument is not read."""
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


# ---------------------------------------------------------------------------
# the layers
# ---------------------------------------------------------------------------

def rms_norm(x, w, eps):
    """x / sqrt(mean(x^2) + eps) * (1 + w)."""
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                         + eps) * (1.0 + w)


def _l2(x):
    return x * lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + 1e-6)


def rotary(x, theta: float, rot: int):
    """Rotate-half positions on the first `rot` of a head's dimensions;
    x [B, T, H, dh], positions 0 .. T-1."""
    t = x.shape[1]
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=F32) / rot))
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]     # [T, rot/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    xr, rest = x[..., :rot], x[..., rot:]
    half = jnp.concatenate([-xr[..., rot // 2:], xr[..., :rot // 2]], -1)
    return jnp.concatenate([xr * cos + half * sin, rest], -1)


def gated_attention(x, p, s, mm):
    """Grouped-query causal attention with q/k norms, partial rotary
    positions and a sigmoid output gate; x [B, T, D]."""
    b, t, _ = x.shape
    h, hk, dh = s.num_attention_heads, s.num_key_value_heads, s.head_dim
    qg = mm(x, p["Wq"]).reshape(b, t, h, 2 * dh)
    q, gate = qg[..., :dh], qg[..., dh:]
    k = mm(x, p["Wk"]).reshape(b, t, hk, dh)
    v = mm(x, p["Wv"]).reshape(b, t, hk, dh)
    rot = int(dh * s.partial_rotary_factor)
    q = rotary(rms_norm(q, p["qnorm"], s.rms_norm_eps), s.rope_theta, rot)
    k = rotary(rms_norm(k, p["knorm"], s.rms_norm_eps), s.rope_theta, rot)
    live = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]

    def one_head(_, qh_i):
        qh, i = qh_i                         # [B, T, dh], head index
        kh = jnp.take(k, i // (h // hk), axis=2)
        vh = jnp.take(v, i // (h // hk), axis=2)
        sc = jnp.einsum("btd,bsd->bts", qh, kh, precision=HI) * dh ** -0.5
        w = jax.nn.softmax(jnp.where(live[None], sc, -jnp.inf), axis=-1)
        return None, jnp.einsum("bts,bsd->btd", w, vh, precision=HI)

    _, a = lax.scan(jax.checkpoint(one_head, prevent_cse=False), None,
                    (jnp.moveaxis(q, 2, 0), jnp.arange(h)))
    a = jnp.moveaxis(a, 0, 2) * jax.nn.sigmoid(gate)           # [B,T,H,dh]
    return mm(a.reshape(b, t, h * dh), p["Wo"])


def causal_conv(x, w):
    """Depthwise causal convolution, no bias: x [B, T, C], w [W, C];
    y_t = sum_i w[i] x_{t - (W - 1) + i}."""
    width = w.shape[0]
    t = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    return sum(xp[:, i:i + t] * w[i] for i in range(width))


def delta_rule(q, k, v, g, beta):
    """The gated delta rule as written, a position at a time. q, k
    [B, T, H, dk] (normalised, q scaled), v [B, T, H, dv], g (log decay,
    <= 0) and beta [B, T, H]. A head's state S is [dk, dv]:
        S = exp(g_t) S;  u = (v_t - S^T k_t) beta_t;  S = S + k_t u^T;
        o_t = S^T q_t."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]

    def step(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = S * jnp.exp(g_t)[..., None, None]
        u = (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t, precision=HI)) \
            * b_t[..., None]
        S = S + k_t[..., :, None] * u[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t, precision=HI)

    seg = max(c for c in range(1, min(t, 128) + 1) if t % c == 0)

    def segment(S, xs):
        return lax.scan(step, S, xs)

    xs = tuple(jnp.moveaxis(a, 1, 0).reshape((t // seg, seg) + a.shape[:1]
                                             + a.shape[2:])
               for a in (q, k, v, g, beta))
    _, o = lax.scan(jax.checkpoint(segment, prevent_cse=False),
                    jnp.zeros((b, h, dk, dv), F32), xs)
    return jnp.moveaxis(o.reshape((t,) + o.shape[2:]), 0, 1)


def gated_deltanet(x, p, s, mm):
    """x [B, T, D] -> [B, T, D]. `Wqkvz` is laid out by key head: q dk,
    k dk, v r dv, z r dv (r value heads a key head); `Wba` b r, a r."""
    b, t, _ = x.shape
    hk, hv = s.linear_num_key_heads, s.linear_num_value_heads
    dk, dv = s.linear_key_head_dim, s.linear_value_head_dim
    r = hv // hk
    qkvz = mm(x, p["Wqkvz"]).reshape(b, t, hk, 2 * dk + 2 * r * dv)
    ba = mm(x, p["Wba"]).reshape(b, t, hk, 2 * r)
    q, k = qkvz[..., :dk], qkvz[..., dk:2 * dk]
    v = qkvz[..., 2 * dk:2 * dk + r * dv].reshape(b, t, hv, dv)
    z = qkvz[..., 2 * dk + r * dv:].reshape(b, t, hv, dv)
    bb, a = ba[..., :r].reshape(b, t, hv), ba[..., r:].reshape(b, t, hv)
    mixed = jnp.concatenate([q.reshape(b, t, -1), k.reshape(b, t, -1),
                             v.reshape(b, t, -1)], -1)
    mixed = jax.nn.silu(causal_conv(mixed, p["conv"]))
    q = mixed[..., :hk * dk].reshape(b, t, hk, dk)
    k = mixed[..., hk * dk:2 * hk * dk].reshape(b, t, hk, dk)
    v = mixed[..., 2 * hk * dk:].reshape(b, t, hv, dv)
    beta = jax.nn.sigmoid(bb)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])
    q = jnp.repeat(_l2(q) * dk ** -0.5, r, axis=2)
    k = jnp.repeat(_l2(k), r, axis=2)
    o = delta_rule(q, k, v, g, beta)
    o = o * lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                      + s.rms_norm_eps) * p["gnorm"] * jax.nn.silu(z)
    return mm(o.reshape(b, t, hv * dv), p["Wo"])


def route(x, router, top_k: int):
    """Softmax over all experts in float32, the top_k, their weights
    divided by their sum: (experts [N, k], weights [N, k])."""
    prob = jax.nn.softmax(jnp.matmul(x, router, precision=HI), axis=-1)
    w, idx = lax.top_k(prob, top_k)
    return idx, w / jnp.sum(w, -1, keepdims=True)


def _swiglu(x, w_gu, w_down, mm):
    gu = mm(x, w_gu)
    f = gu.shape[-1] // 2
    return mm(jax.nn.silu(gu[..., :f]) * gu[..., f:], w_down)


def moe(x, p, s, mm, first: int = 0):
    """x [B, T, D]. The routed part over the experts held, ids
    [first, first + Eh), every one of them over every row with the
    router's weight or nought; plus the gated shared expert."""
    b, t, d = x.shape
    xf = x.reshape(b * t, d)
    router = p["router"]
    if not s.router_trained:        # frozen: the leaf's gradient only
        router = lax.stop_gradient(router)
    idx, w = route(xf, router, s.num_experts_per_tok)
    eh = p["We_gu"].shape[0]

    def one_expert(acc, xs):
        w_gu, w_down, e = xs
        we = jnp.sum(jnp.where(idx == e, w, 0.0), -1)            # [N]
        return acc + we[:, None] * _swiglu(xf, w_gu, w_down, mm), None

    y, _ = lax.scan(jax.checkpoint(one_expert, prevent_cse=False),
                    jnp.zeros_like(xf),
                    (p["We_gu"], p["We_down"], first + jnp.arange(eh)))
    return (y + shared_expert(xf, p, mm)).reshape(b, t, d)


def shared_expert(xf, p, mm):
    return jax.nn.sigmoid(jnp.matmul(xf, p["Ws_gate"], precision=HI)) \
        * _swiglu(xf, p["Ws_gu"], p["Ws_down"], mm)


MIXERS = {"deltanet": gated_deltanet, "full": gated_attention}


def layer(h, p, s, kind: str, mm):
    h = h + MIXERS[kind](rms_norm(h, p["ln1"], s.rms_norm_eps), p, s, mm)
    return h + moe(rms_norm(h, p["ln2"], s.rms_norm_eps), p, s, mm)


def hidden(s, params, tokens, precision: str = "f32"):
    """tokens [B, T] -> final-norm hidden states [B, T, D], float32."""
    mm = _mm_fn(precision)
    h = params["embed"][tokens]

    def period(h, blocks):
        for i, kind in enumerate(s.layer_types):
            # (the barrier against merging a layer's recomputation with
            # its forward stays on: the layers of a period share a body)
            h = jax.checkpoint(
                lambda h_, p_, kind=kind: layer(h_, p_, s, kind, mm))(
                    h, blocks[f"l{i}"])
        return h, None

    h, _ = lax.scan(period, h, params["blocks"])
    return rms_norm(h, params["lnfg"], s.rms_norm_eps)


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
    that it fits: the mean of the blocks' sums."""
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


def _stacked(path) -> bool:
    return any(getattr(k, "key", None) == "blocks" for k in path)


def leaf_norms(tree):
    """One norm for every unstacked leaf and one for every period of a
    stacked leaf, as one flat float32 vector in a fixed (sorted) order."""
    out = []
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        x = x.astype(F32)
        if _stacked(path):
            out.append(jnp.sqrt(jnp.sum(jnp.square(x),
                                        axis=tuple(range(1, x.ndim)))))
        else:
            out.append(jnp.sqrt(jnp.sum(jnp.square(x)))[None])
    return jnp.concatenate(out)


def leaf_samples(tree, stride: int = 64):
    """Every `stride`-th element of every leaf (of every period of a
    stacked leaf), rows in the order of `leaf_norms`."""
    out = []
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        x = x.astype(F32)
        rows = x.reshape(x.shape[0], -1) if _stacked(path) \
            else x.reshape(1, -1)
        out.append(rows[:, ::min(stride, max(1, rows.shape[1] // 8))])
    return out


def leaf_names(s) -> list:
    names = []
    for path, _ in jax.tree_util.tree_flatten_with_path(
            leaf_shapes(s), is_leaf=lambda x: isinstance(x, tuple))[0]:
        name = ".".join(k.key for k in path)
        if path[0].key == "blocks":
            names += [f"{name}[{i}]" for i in range(s.n_periods)]
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
