"""Plain reference for the LFM2 MoE block family (`lfm2_moe`): gated short
convolutions beside grouped-query attention, leading layers with a dense
SwiGLU, then layers of sigmoid-routed top-k experts with no shared expert;
RMSNorm, rotary positions, a head tied to the embedding.

Straightforward `jax.numpy` in float32 with
`jax.default_matmul_precision("highest")`. No kernels, no cache; a layer
runs a block of rows at a time, attention a head and a block of queries at
a time, the dense MLP and the head a block of positions at a time, so that
no more than a block's float32 intermediates are ever held, which changes
no number. It imports nothing of the program and makes its own weights
(`make_init`), in the tree layout the program's entry points take; `P` is
the number of periods held after the leading layers, a period's runs of
like layers are `r0 .. r{n-1}`, each of `n` layers:

    embed [V, D]  lnfg [D]                       (no Wout: the head is embed^T)
    a `conv` mixer:  Win [D, 3 D] (B | C | x)  conv [L, D]  Wo [D, D]
    a `full_attention` mixer:  Wq [D, H dh]  Wk Wv [D, Hkv dh]  Wo [H dh, D]
        qnorm knorm [dh]
    lead.l<i>, a leading layer:  ln1 ln2 [D], its mixer,
        W_gu [D, 2 F]  W_down [F, D]                    (gate | up, fused)
    blocks.r<j>, every leaf with leading [P, n]:  ln1 ln2, the run's mixer,
        router [D, E]  router_bias [E]  We_gu [Eh, D, 2 Fe]
        We_down [Eh, Fe, D]

Per layer `h += mixer(norm(h)); h += mlp(norm(h))` with
`norm(x) = x / sqrt(mean(x^2) + eps) * (1 + w)` (a published gain is stored
less one); the final norm is the published `embedding_norm`;
`logits = norm(h) embed^T`.

The gated short convolution, x a layer's normed input:
    [B | C | x~] = x Win;  u = B * x~
    v_t = sum_i conv[i] * u_{t - (L - 1) + i}  (u before 0 is nought; the
        published Conv1d with padding L - 1, its first T outputs; no bias)
    out = (C * v) Wo;  no activation anywhere
Attention: q = norm(x Wq) and k = norm(x Wk) over each head's dh, both
rotated (rotate-half, all of dh), v = x Wv; query head i reads key/value head
i // (H / Hkv); causal softmax(q k^T dh^-1/2) v, the heads side by side, Wo.
The router: `s = sigmoid(x W_r)` over all `E` experts; the `top_k` by `s + b`
(`b` the expert bias, a buffer: it enters the choice and not the weight, and
no gradient reaches it); weights `s / (sum of the chosen s + 1e-20) *
routed_scaling_factor`. The layer is told which experts it holds,
`[first, first + Eh)` of `E`: it routes over all `E`, normalises over all
`top_k` chosen, and adds only what the held experts give. With
`sizes.router_trained` false the router's matrix is frozen: the leaf gets no
gradient, everything else its whole one.

The weights are a function of `sizes.weights_key` alone: `seed_key` returns
the same two words whatever the seed (configs/lfm2-24b-a2b.json, `assumed`,
says why). `precision` lets the same code stand in the program's place at a
lower precision (the control of the `correct` comparison), as in
`gpt2_block.py`; the router's scores, the convolution and the attention's
products stay float32, as in the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
HI = lax.Precision.HIGHEST
QUERY_BLOCK = 1024


def seed_key(seed: int):
    """The weights do not change with the seed: the initialiser takes its
    key from `sizes.weights_key`, and this is only the argument's shape."""
    import numpy as np
    return np.zeros((2,), np.uint32)


def runs(kinds):
    """Runs of like layers: [(kind, length)]."""
    out = []
    for kind in kinds:
        if out and out[-1][0] == kind:
            out[-1][1] += 1
        else:
            out.append([kind, 1])
    return [(k, n) for k, n in out]


def mixer_shapes(s, kind: str) -> dict:
    d = s.hidden_size
    if kind == "conv":
        return {"Win": (d, 3 * d), "conv": (s.conv_L_cache, d), "Wo": (d, d)}
    if kind == "full_attention":
        h, hk, dh = s.num_attention_heads, s.num_key_value_heads, s.head_dim
        return {"Wq": (d, h * dh), "Wk": (d, hk * dh), "Wv": (d, hk * dh),
                "Wo": (h * dh, d), "qnorm": (dh,), "knorm": (dh,)}
    raise ValueError(f"unknown layer type {kind!r}")


def layer_shapes(s, kind: str, mlp: str, lead: tuple = ()) -> dict:
    """One layer's leaves: the mixer `kind`, the MLP `mlp` (`dense` or
    `moe`), `lead` its leading axes (`(P, n)` for a stacked run)."""
    d = s.hidden_size
    out = dict(mixer_shapes(s, kind), ln1=(d,), ln2=(d,))
    if mlp == "dense":
        f = s.intermediate_size
        out.update(W_gu=(d, 2 * f), W_down=(f, d))
    elif mlp == "moe":
        e, eh, fe = s.router_width, s.experts_held, s.moe_intermediate_size
        out.update(router=(d, e), router_bias=(e,), We_gu=(eh, d, 2 * fe),
                   We_down=(eh, fe, d))
    else:
        raise ValueError(f"unknown mlp {mlp!r}")
    return {k: lead + v for k, v in out.items()}


def leaf_shapes(s) -> dict:
    out = {"embed": (s.vocab_size, s.hidden_size), "lnfg": (s.hidden_size,),
           "blocks": {f"r{j}": layer_shapes(s, kind, "moe", (s.n_periods, n))
                      for j, (kind, n) in enumerate(runs(s.period))}}
    if s.lead_types:
        out["lead"] = {f"l{i}": layer_shapes(s, kind, "dense")
                       for i, kind in enumerate(s.lead_types)}
    return out


def _mix(x):
    """murmur3's 32-bit finaliser."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def _normal(shape, salt):
    """Standard normals as a pure function of (element index, salt): two
    hashed uniforms through Box-Muller; the same whatever the sharding."""
    n = 1
    for k in shape:
        n *= k
    idx = lax.iota(jnp.uint32, n).reshape(shape)
    a = _mix(idx ^ salt)
    b = _mix(a + jnp.uint32(0x9E3779B9))
    u1 = ((a >> 8).astype(F32) + 0.5) * F32(2.0 ** -24)
    u2 = ((b >> 8).astype(F32) + 0.5) * F32(2.0 ** -24)
    return jnp.sqrt(-2.0 * jnp.log(u1)) * jnp.cos(F32(2.0 * jnp.pi) * u2)


def _init_tree(s, _seed):
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        leaf_shapes(s), is_leaf=lambda x: isinstance(x, tuple))
    key = int(s.weights_key)
    base = _mix(jnp.uint32(key & 0xFFFFFFFF)
                ^ _mix(jnp.uint32((key >> 32) & 0xFFFFFFFF)
                       + jnp.uint32(0x7F4A7C15)))
    out = []
    for i, (path, shape) in enumerate(flat):
        name = path[-1].key
        z = _normal(shape, _mix(base + jnp.uint32(i + 1)))
        if name.startswith("W") or name in ("router", "conv"):
            out.append(z / jnp.sqrt(F32(shape[-2])))   # N(0, 1 / fan_in)
        elif name == "router_bias":
            # not nought: the choice (s + b) and the weight (s) differ
            out.append(0.01 * z)
        else:   # embed and the (1 + w) norms' w: small around 0
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


def rope(x, theta: float):
    """Rotary positions 0 .. T-1 on all of x's last axis, rotate-half
    pairing (dimension j with j + half); x [B, T, H, dh]."""
    t, dr = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dr, 2, dtype=F32) / dr))
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]      # [T, dr/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    half = jnp.concatenate([-x[..., dr // 2:], x[..., :dr // 2]], -1)
    return x * cos + half * sin


def _block(t: int) -> int:
    """Positions a block: the largest divisor of `t` up to QUERY_BLOCK."""
    return max(c for c in range(1, min(t, QUERY_BLOCK) + 1) if t % c == 0)


def _split(x, blk: int):
    """[B, T, ...] -> [T / blk, B, blk, ...]."""
    b, t = x.shape[:2]
    return jnp.moveaxis(x.reshape((b, t // blk, blk) + x.shape[2:]), 1, 0)


def causal_attention(q, k, v, scale: float):
    """q, k, v [B, T, H, dh] -> [B, T, H, dh]: a head and a block of
    queries at a time, each checkpointed."""
    b, t = q.shape[:2]
    blk = _block(t)
    cols = jnp.arange(t)

    def one_head(_, qkv):
        qh, kh, vh = qkv                                      # [B, T, d]

        def one_block(_, qs):
            qb, start = qs                                    # [B, blk, d]
            sc = jnp.einsum("bqd,bsd->bqs", qb, kh, precision=HI) * scale
            live = (start + jnp.arange(blk))[:, None] >= cols[None, :]
            w = jax.nn.softmax(jnp.where(live[None], sc, -jnp.inf), axis=-1)
            return None, jnp.einsum("bqs,bsd->bqd", w, vh, precision=HI)

        _, o = lax.scan(jax.checkpoint(one_block, prevent_cse=False), None,
                        (_split(qh, blk), jnp.arange(0, t, blk)))
        return None, jnp.moveaxis(o, 0, 1).reshape(b, t, -1)

    _, a = lax.scan(jax.checkpoint(one_head, prevent_cse=False), None,
                    tuple(jnp.moveaxis(x, 2, 0) for x in (q, k, v)))
    return jnp.moveaxis(a, 0, 2)


def attention(x, p, s, mm):
    """Grouped-query attention with q/k norms and rotary positions on the
    whole head; x [B, T, D]."""
    b, t, _ = x.shape
    h, hk, dh = s.num_attention_heads, s.num_key_value_heads, s.head_dim
    q = rms_norm(mm(x, p["Wq"]).reshape(b, t, h, dh), p["qnorm"], s.norm_eps)
    k = rms_norm(mm(x, p["Wk"]).reshape(b, t, hk, dh), p["knorm"],
                 s.norm_eps)
    v = mm(x, p["Wv"]).reshape(b, t, hk, dh)
    q, k = rope(q, s.rope_theta), rope(k, s.rope_theta)
    a = causal_attention(q, jnp.repeat(k, h // hk, axis=2),
                         jnp.repeat(v, h // hk, axis=2), dh ** -0.5)
    return mm(a.reshape(b, t, h * dh), p["Wo"])


def causal_conv(x, w):
    """Depthwise causal convolution: x [B, T, C], w [L, C];
    y_t = sum_i w[i] x_{t - (L - 1) + i}."""
    width, t = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    return sum(xp[:, i:i + t] * w[i] for i in range(width))


def short_conv(x, p, s, mm):
    """The gated short convolution; x [B, T, D]."""
    d = s.hidden_size
    bcx = mm(x, p["Win"])
    u = bcx[..., :d] * bcx[..., 2 * d:]
    v = causal_conv(u, p["conv"])
    return mm(bcx[..., d:2 * d] * v, p["Wo"])


MIXERS = {"conv": short_conv, "full_attention": attention}


def route(x, router, bias, top_k: int, scale: float):
    """Sigmoid scores over all experts, the top_k by score plus bias,
    weights the scores themselves over their sum, times `scale`:
    (experts [N, k], weights [N, k])."""
    score = jax.nn.sigmoid(jnp.matmul(x, router, precision=HI))
    _, idx = lax.top_k(score + bias, top_k)
    w = jnp.take_along_axis(score, idx, axis=-1)
    return idx, w / (jnp.sum(w, -1, keepdims=True) + 1e-20) * scale


def _swiglu(x, w_gu, w_down, mm):
    gu = mm(x, w_gu)
    f = gu.shape[-1] // 2
    return mm(jax.nn.silu(gu[..., :f]) * gu[..., f:], w_down)


def dense_mlp(x, p, mm):
    """The leading layers' SwiGLU over x [B, T, D], a block of positions
    at a time, each checkpointed: a row's [T, 2 F] float32 is never held."""
    b, t, d = x.shape
    y = lax.map(jax.checkpoint(
        lambda xb: _swiglu(xb, p["W_gu"], p["W_down"], mm)),
        _split(x, _block(t)))
    return jnp.moveaxis(y, 0, 1).reshape(b, t, d)


def moe(x, p, s, mm, first: int = 0):
    """The routed part over the experts held, ids [first, first + Eh):
    every one of them over every row with the router's weight or nought.
    No shared expert."""
    b, t, d = x.shape
    xf = x.reshape(b * t, d)
    router = p["router"]
    if not s.router_trained:        # frozen: the leaf's gradient only
        router = lax.stop_gradient(router)
    idx, w = route(xf, router, lax.stop_gradient(p["router_bias"]),
                   s.num_experts_per_tok, s.routed_scaling_factor)
    eh = p["We_gu"].shape[0]

    def one_expert(acc, xs):
        w_gu, w_down, e = xs
        we = jnp.sum(jnp.where(idx == e, w, 0.0), -1)            # [N]
        return acc + we[:, None] * _swiglu(xf, w_gu, w_down, mm), None

    y, _ = lax.scan(jax.checkpoint(one_expert, prevent_cse=False),
                    jnp.zeros_like(xf),
                    (p["We_gu"], p["We_down"], first + jnp.arange(eh)))
    return y.reshape(b, t, d)


def layer(h, p, s, kind: str, mlp: str, mm, first: int = 0):
    h = h + MIXERS[kind](rms_norm(h, p["ln1"], s.norm_eps), p, s, mm)
    x = rms_norm(h, p["ln2"], s.norm_eps)
    if mlp == "dense":
        return h + dense_mlp(x, p, mm)
    return h + moe(x, p, s, mm, first)


def _by_rows(fn, h, p, rows_per_block=None):
    """`fn(h, p)`, a layer over h [B, T, D], a block of rows at a time,
    each keeping only its input for the backward pass. A layer's rows do
    not see one another, so this changes no number; the gradient of `p` is
    summed over the blocks a layer at a time, and only one layer's rows of
    a block are ever held."""
    b = h.shape[0]
    rpb = min(rows_per_block or b, b)
    kept = jax.checkpoint(fn)
    out = lax.map(lambda hb: kept(hb, p),
                  h.reshape((b // rpb, rpb) + h.shape[1:]))
    return out.reshape(h.shape)


def hidden(s, params, tokens, precision: str = "f32", first: int = 0,
           rows_per_block=None):
    """tokens [B, T] -> final-norm hidden states [B, T, D], float32."""
    mm = _mm_fn(precision)
    h = params["embed"][tokens]
    for i, kind in enumerate(s.lead_types):
        h = _by_rows(lambda h_, p_, kind=kind: layer(h_, p_, s, kind,
                                                     "dense", mm),
                     h, params["lead"][f"l{i}"], rows_per_block)

    def period(h, blocks):
        for j, (kind, _) in enumerate(runs(s.period)):
            def one(h_, p_, kind=kind):
                return _by_rows(
                    lambda a, b: layer(a, b, s, kind, "moe", mm, first),
                    h_, p_, rows_per_block), None
            h, _ = lax.scan(one, h, blocks[f"r{j}"])
        return h, None

    h, _ = lax.scan(period, h, params["blocks"])
    return rms_norm(h, params["lnfg"], s.norm_eps)


def nll_sum(s, params, tokens, targets, precision: str = "f32",
            first: int = 0, rows_per_block=None):
    """Summed next-token negative log-likelihood of rows [B, T] under the
    tied head, a block of positions at a time (each checkpointed), so that
    the float32 logits of a whole row are never held."""
    mm = _mm_fn(precision)
    hf = hidden(s, params, tokens, precision, first, rows_per_block)
    head = params["embed"].T

    def one_block(acc, xs):
        hb, tb = xs                                  # [B, blk, D], [B, blk]
        logp = jax.nn.log_softmax(mm(hb, head), axis=-1)
        got = jnp.take_along_axis(logp, tb[..., None], axis=-1)[..., 0]
        return acc - jnp.sum(got), None

    blk = _block(hf.shape[1])
    total, _ = lax.scan(jax.checkpoint(one_block, prevent_cse=False),
                        jnp.zeros((), F32),
                        (_split(hf, blk), _split(targets, blk)))
    return total


def loss(s, params, tokens, targets, precision: str = "f32", first: int = 0,
         rows_per_block=None):
    """The mean over B x T."""
    return nll_sum(s, params, tokens, targets, precision, first,
                   rows_per_block) / tokens.size


def loss_and_grad(s, params, tokens, targets, rows_per_block: int,
                  precision: str = "f32"):
    """Mean loss over all rows and its gradient, each layer taken in blocks
    of `rows_per_block` rows so that it fits (`_by_rows`): one gradient
    tree, not a second one to sum the blocks' into."""
    return jax.value_and_grad(
        lambda p: loss(s, p, tokens, targets, precision, 0,
                       rows_per_block))(params)


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
    """Every `stride`-th element of every leaf's last axis (of every layer
    of a stacked leaf; at least eight a row of it), rows in the order of
    `leaf_norms`. The stride is taken before the layers are flattened: a
    flattened `[P, n, 8, 2048, 3072]` stack strided took the chip's
    compiler 111 s, this 9.6 s (for a described v5e); where the last axis is
    a multiple of the stride the elements are the same."""
    out = []
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        rows = x.shape[0] * x.shape[1] if _stacked(path) else 1
        k = min(stride, max(1, x.shape[-1] // 8))
        out.append(x[..., ::k].astype(F32).reshape(rows, -1))
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
        value, g = loss_and_grad(s, params, tokens, targets, rows_per_block,
                                 precision)
        norms, samples = leaf_norms(g), leaf_samples(g)
        params, m, v = adam(params, g, m, v, t, lr)
        return params, m, v, value, norms, samples

    kw = {}
    if shardings is not None:
        kw = dict(in_shardings=(shardings, shardings, shardings,
                                batch_sharding, batch_sharding, None),
                  out_shardings=(shardings, shardings, shardings, None,
                                 None, None))
    return jax.jit(step, donate_argnums=(0, 1, 2), **kw)
