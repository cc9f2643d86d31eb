"""Plain reference for the GLM-4.7-Flash block family (`glm4_moe_lite`):
multi-head latent attention in every layer, leading dense layers, then
layers of sigmoid-routed top-k experts with an ungated shared expert, and a
multi-token-prediction module whose loss joins the main one.

Straightforward `jax.numpy` in float32 with
`jax.default_matmul_precision("highest")`. No kernels, no cache; a layer
runs a block of rows at a time, attention a head and a block of queries at
a time, the dense MLP and the head a block of positions at a time, so that
no more than a block's float32 intermediates are ever held, which changes
no number. It imports nothing of
the program and makes its own weights (`make_init`), in the tree layout the
program's entry points take; `P` is the number of expert layers held after
the `first_k_dense_replace` leading ones:

    embed [V, D]  lnfg [D]  Wout [D, V]
    a mixer:  Wq_a [D, rq]  q_a_norm [rq]  Wq_b [rq, H (dn + dr)]
        Wkv_a [D, rkv + dr]  kv_a_norm [rkv]  Wkv_b [rkv, H (dn + dv)]
        Wo [H dv, D]
    lead.l<i>, a leading layer:  ln1 ln2 [D], the mixer,
        W_gu [D, 2 F]  W_down [F, D]                    (gate | up, fused)
    blocks.l0, the expert layers, every leaf with a leading [P]:
        ln1 ln2, the mixer, router [D, E]  router_bias [E]
        We_gu [Eh, D, 2 Fe]  We_down [Eh, Fe, D]  Ws_gu [D, 2 Fs]
        Ws_down [Fs, D]
    mtp:  enorm hnorm norm [D]  eh_proj [2 D, D]  layer: an expert layer's
        leaves without the [P]

Per layer `h += mla(norm(h)); h += mlp(norm(h))` with
`norm(x) = x / sqrt(mean(x^2) + eps) * (1 + w)` (a published gain is stored
less one).

Latent attention, x a layer's normed input:
    c_q = norm(x Wq_a);  q = (c_q Wq_b) a head [q_nope dn | q_rope dr]
    [c_kv rkv | k_r dr] = x Wkv_a;  c_kv = norm(c_kv)
    (c_kv Wkv_b) a head [k_nope dn | v dv];  k = [k_nope | rope(k_r)], the
    one k_r for every head;  q_rope = rope(q_rope)
    out = causal softmax(q k^T (dn + dr)^-1/2) v, the heads side by side, Wo
The router (`noaux_tc`, one group): `s = sigmoid(x W_r)` over all `E`
experts; the `top_k` by `s + b` (the correction bias `b` a buffer: it enters
the choice and not the weight, and no gradient reaches it); weights
`s / (sum of the chosen s + 1e-20) * routed_scaling_factor`. The layer is
told which experts it holds, `[first, first + Eh)` of `E`: it routes over
all `E`, normalises over all `top_k` chosen, and adds only what the held
experts give, plus the shared expert, ungated. With `sizes.router_trained`
false the router's matrix is frozen: the leaf gets no gradient, everything
else its whole one.

Multi-token prediction (depth 1): with `hf` the main model's final-normed
hidden states, position i of a row gives
    u_i = [norm(embed[t_{i+1}]; enorm) ; norm(hf_i; hnorm)] eh_proj
through one expert layer (causal) and `norm(.; mtp.norm)`, the same `Wout`,
against `t_{i+2}`. A row's last position has no `t_{i+2}`: the layer runs
over all T positions (causal, so the others see nothing of the last) and
the loss leaves the last out. The loss is the main head's mean over the T
positions plus `mtp_loss_weight` times this mean over the T - 1.

The weights are a function of `sizes.weights_key` alone: `seed_key` returns
the same two words whatever the seed (configs/glm-4.7-flash.json, `assumed`,
says why). `precision` lets the same code stand in the program's place at a
lower precision (the control of the `correct` comparison), as in
`gpt2_block.py`; the router's scores and the attention's products stay
float32, as in the program.
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


def mixer_shapes(s, lead: tuple = ()) -> dict:
    d, h = s.hidden_size, s.num_attention_heads
    dn, dr, dv = s.qk_nope_head_dim, s.qk_rope_head_dim, s.v_head_dim
    rq, rkv = s.q_lora_rank, s.kv_lora_rank
    shapes = {"Wq_a": (d, rq), "q_a_norm": (rq,), "Wq_b": (rq, h * (dn + dr)),
              "Wkv_a": (d, rkv + dr), "kv_a_norm": (rkv,),
              "Wkv_b": (rkv, h * (dn + dv)), "Wo": (h * dv, d)}
    return {k: lead + v for k, v in shapes.items()}


def layer_shapes(s, kind: str, lead: tuple = ()) -> dict:
    """One layer's leaves, `kind` `dense` or `moe`, `lead` its leading axes
    (`(P,)` for the stacked expert layers)."""
    d = s.hidden_size
    out = dict(mixer_shapes(s, lead), ln1=lead + (d,), ln2=lead + (d,))
    if kind == "dense":
        f = s.intermediate_size
        out.update(W_gu=lead + (d, 2 * f), W_down=lead + (f, d))
    elif kind == "moe":
        e, eh, fe = s.n_routed_experts, s.experts_held, s.moe_intermediate_size
        fs = fe * s.n_shared_experts
        out.update(router=lead + (d, e), router_bias=lead + (e,),
                   We_gu=lead + (eh, d, 2 * fe), We_down=lead + (eh, fe, d),
                   Ws_gu=lead + (d, 2 * fs), Ws_down=lead + (fs, d))
    else:
        raise ValueError(f"unknown layer kind {kind!r}")
    return out


def leaf_shapes(s) -> dict:
    d, v = s.hidden_size, s.vocab_size
    out = {"embed": (v, d), "lnfg": (d,), "Wout": (d, v),
           "blocks": {"l0": layer_shapes(s, "moe", (s.n_expert_layers,))}}
    if s.first_k_dense_replace:
        out["lead"] = {f"l{i}": layer_shapes(s, "dense")
                       for i in range(s.first_k_dense_replace)}
    if s.num_nextn_predict_layers:
        out["mtp"] = {"enorm": (d,), "hnorm": (d,), "eh_proj": (2 * d, d),
                      "layer": layer_shapes(s, "moe"), "norm": (d,)}
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
        if name.startswith("W") or name in ("router", "eh_proj"):
            out.append(z / jnp.sqrt(F32(shape[-2])))   # N(0, 1 / fan_in)
        elif name == "router_bias":
            # not nought: the choice (s + b) and the weight (s) differ
            out.append(0.01 * z)
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


def rope(x, theta: float):
    """Rotary positions 0 .. T-1 on all of x's last axis, rotate-half
    pairing (dimension j with j + half); x [B, T, H, dr]."""
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
    """q, k [B, T, H, dqk], v [B, T, H, dv] -> [B, T, H, dv]: a head and a
    block of queries at a time, each checkpointed."""
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


def mla(x, p, s, mm):
    """Multi-head latent attention; x [B, T, D]."""
    b, t, _ = x.shape
    h = s.num_attention_heads
    dn, dr, dv = s.qk_nope_head_dim, s.qk_rope_head_dim, s.v_head_dim
    rkv = s.kv_lora_rank
    c_q = rms_norm(mm(x, p["Wq_a"]), p["q_a_norm"], s.rms_norm_eps)
    q = mm(c_q, p["Wq_b"]).reshape(b, t, h, dn + dr)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], s.rope_theta)], -1)
    ckv = mm(x, p["Wkv_a"])
    c_kv = rms_norm(ckv[..., :rkv], p["kv_a_norm"], s.rms_norm_eps)
    k_r = rope(ckv[..., rkv:].reshape(b, t, 1, dr), s.rope_theta)
    kv = mm(c_kv, p["Wkv_b"]).reshape(b, t, h, dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.repeat(k_r, h, axis=2)], -1)
    a = causal_attention(q, k, kv[..., dn:], (dn + dr) ** -0.5)
    return mm(a.reshape(b, t, h * dv), p["Wo"])


def route(x, router, bias, top_k: int, scale: float):
    """`noaux_tc` with one group: sigmoid scores over all experts, the
    top_k by score plus bias, weights the scores themselves over their sum,
    times `scale`: (experts [N, k], weights [N, k])."""
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


def routed(xf, p, s, mm, first: int = 0):
    """The routed part over the experts held, ids [first, first + Eh):
    every one of them over every row with the router's weight or nought."""
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
    return y


def shared_expert(xf, p, mm):
    return _swiglu(xf, p["Ws_gu"], p["Ws_down"], mm)


def moe(x, p, s, mm, first: int = 0):
    b, t, d = x.shape
    xf = x.reshape(b * t, d)
    return (routed(xf, p, s, mm, first)
            + shared_expert(xf, p, mm)).reshape(b, t, d)


def layer(h, p, s, kind: str, mm, first: int = 0):
    h = h + mla(rms_norm(h, p["ln1"], s.rms_norm_eps), p, s, mm)
    x = rms_norm(h, p["ln2"], s.rms_norm_eps)
    if kind == "dense":
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
    for i in range(s.first_k_dense_replace):
        h = _by_rows(lambda h_, p_: layer(h_, p_, s, "dense", mm), h,
                     params["lead"][f"l{i}"], rows_per_block)

    def one(h, p):
        return _by_rows(lambda h_, p_: layer(h_, p_, s, "moe", mm, first),
                        h, p, rows_per_block), None

    h, _ = lax.scan(one, h, params["blocks"]["l0"])
    return rms_norm(h, params["lnfg"], s.rms_norm_eps)


def _nll(h, wout, targets, keep, mm):
    """Summed negative log-likelihood of `targets` [B, T] where `keep`
    [T] is 1, a block of positions at a time (each checkpointed), so that
    the float32 logits of a whole row are never held."""
    blk = _block(h.shape[1])

    def one_block(acc, xs):
        hb, tb, kb = xs                        # [B, blk, D], [B, blk], [blk]
        logp = jax.nn.log_softmax(mm(hb, wout), axis=-1)
        got = jnp.take_along_axis(logp, tb[..., None], axis=-1)[..., 0]
        return acc - jnp.sum(got * kb), None

    total, _ = lax.scan(jax.checkpoint(one_block, prevent_cse=False),
                        jnp.zeros((), F32),
                        (_split(h, blk), _split(targets, blk),
                         keep.reshape(-1, blk)))
    return total


def nll_sums(s, params, tokens, targets, precision: str = "f32",
             first: int = 0, rows_per_block=None):
    """(the main head's summed negative log-likelihood over rows [B, T],
    the multi-token-prediction head's over their first T - 1 positions,
    nought without the module)."""
    mm = _mm_fn(precision)
    t = tokens.shape[1]
    hf = hidden(s, params, tokens, precision, first, rows_per_block)
    main = _nll(hf, params["Wout"], targets, jnp.ones((t,), F32), mm)
    if not s.num_nextn_predict_layers:
        return main, jnp.zeros((), F32)
    m = params["mtp"]
    e = rms_norm(params["embed"][targets], m["enorm"], s.rms_norm_eps)
    g = rms_norm(hf, m["hnorm"], s.rms_norm_eps)
    u = mm(jnp.concatenate([e, g], -1), m["eh_proj"])
    u = _by_rows(lambda u_, p_: layer(u_, p_, s, "moe", mm, first), u,
                 m["layer"], rows_per_block)
    u = rms_norm(u, m["norm"], s.rms_norm_eps)
    # position i against token i + 2, which is targets[i + 1]; the last
    # position's stand-in is never counted
    after = jnp.concatenate([targets[:, 1:], targets[:, :1]], axis=1)
    return main, _nll(u, params["Wout"], after,
                      (jnp.arange(t) < t - 1).astype(F32), mm)


def loss(s, params, tokens, targets, precision: str = "f32",
         first: int = 0, rows_per_block=None):
    """The main head's mean over B x T plus `mtp_loss_weight` times the
    module's mean over B x (T - 1)."""
    b, t = tokens.shape
    main, mtp = nll_sums(s, params, tokens, targets, precision, first,
                         rows_per_block)
    return main / (b * t) + s.mtp_loss_weight * mtp / (b * max(t - 1, 1))


def loss_and_grad(s, params, tokens, targets, rows_per_block: int,
                  precision: str = "f32"):
    """Mean loss over all rows and its gradient, each layer taken in blocks
    of `rows_per_block` rows so that it fits (`_by_rows`): one gradient
    tree, not a second one to sum the blocks' into (at 16 bytes a parameter
    held, three trees of state and two of gradients leave a chip no room
    for a row)."""
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
    """One norm for every unstacked leaf and one for every layer of a
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
    """Every `stride`-th element of every leaf (of every layer of a
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
            names += [f"{name}[{i}]" for i in range(s.n_expert_layers)]
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
        params, m, v = adam(params, g, m, v, t, lr)
        return params, m, v, value, leaf_norms(g), leaf_samples(g)

    kw = {}
    if shardings is not None:
        kw = dict(in_shardings=(shardings, shardings, shardings,
                                batch_sharding, batch_sharding, None),
                  out_shardings=(shardings, shardings, shardings, None,
                                 None, None))
    return jax.jit(step, donate_argnums=(0, 1, 2), **kw)
