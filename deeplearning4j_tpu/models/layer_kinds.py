"""Layer kinds for a `TransformerConfig` with `layer_types`: a period of
unlike layers where the GPT-2 family has one block.

A layer is ``h += r mixer(norm(h)); h += r mlp(norm(h))`` with
``norm(x) = x / sqrt(mean(x^2) + eps) * (1 + w)`` in float32 and
``r = cfg.residual_scale``. Mixers:

- ``"full"`` and ``"attention"`` (two families' names for one mixer,
  `grouped_query_attention`): a KV head serves ``n_heads / n_kv_heads``
  query heads; the config says which parts it has. With `attn_gate`,
  ``Wq`` gives a head its query and its output gate side by side and the
  output is ``Wo(attn * sigmoid(gate))``; with `qk_norm`, q and k are
  RMS-normed over the head; rotary positions (rotate-half) turn the first
  `rotary_fraction` of the head (0: no positions at all); the scores are
  scaled by `attn_scale` (0: ``d_head ** -0.5``).
- ``"mamba2"``: Mamba-2. ``Win`` gives ``z | x B C`` and ``Wdt`` the step
  (in float32: it feeds softplus and exp); ``x B C`` through a causal
  depthwise convolution with a bias and SiLU; the state-space-dual scan
  (ops/mamba2_ssd.py) with ``d = softplus(dt + dt_bias)`` and decay
  ``exp(-exp(A_log) d)``, plus ``D x``; ``Wo(rms(y * silu(z)) * gnorm)``,
  the norm over a group's channels.
- ``"deltanet"``: Gated DeltaNet. ``Wqkvz`` (laid out by key head: q, k,
  v of its value heads, z of its value heads) and ``Wba``; q, k, v through
  a causal depthwise convolution and SiLU; the gated delta rule
  (ops/gated_delta.py) over L2-normalised q and k;
  ``Wo(norm(o) * gnorm * silu(z))``.

A layer's second half is what `cfg.mlp_kind` names: ``"swiglu"``, one dense
gated MLP of width `dense_d_ff` (``W_gu`` gate | up, ``W_down``), or
``"moe"``, a top-k mixture of experts with a gated shared
expert (`moe_topk`). That layer is told which experts it holds
(`cfg.experts_held`, ids from `first`): it routes over all
`cfg.n_experts`, normalises over all `moe_top_k` chosen and adds only what
the held experts give, without drops: every routed row is computed
(ops/grouped_matmul.py). On one chip that is an expert-parallel rank
without its exchange; the absent experts' part is left out.

Parameter leaves are stacked over PERIODS: ``blocks["l<i>"][name]`` is
``[P, ...]`` for position ``i`` of the period, and the step scans periods.
With `cfg.stack_runs` a period's runs of like layers are stacked too:
``blocks["r<j>"][name]`` is ``[P, n, ...]`` for the ``n`` layers of run
``j``, scanned, so a run is traced and compiled once (`block_keys`). With
`cfg.tie_head` there is no ``Wout``.
These kinds run on the training path (models/transformer.py's forward and
parallel/megatron.py's step on data / pipe axes); serving refuses them.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

Array = jax.Array
F32 = jnp.float32
KINDS = ("deltanet", "full", "mamba2", "attention")
_HI = lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def layer_shapes(cfg, kind: str) -> Dict[str, tuple]:
    """Leaf shapes of one layer of `kind`, without the period axis."""
    d, f, fs = cfg.d_model, cfg.moe_d_ff, cfg.shared_d_ff
    if cfg.mlp_kind == "swiglu":
        out = {"ln1": (d,), "ln2": (d,), "W_gu": (d, 2 * cfg.dense_d_ff),
               "W_down": (cfg.dense_d_ff, d)}
    elif cfg.mlp_kind == "moe":
        out = {"ln1": (d,), "ln2": (d,), "router": (d, cfg.n_experts),
               "We_gu": (cfg.experts_held, d, 2 * f),
               "We_down": (cfg.experts_held, f, d),
               "Ws_gu": (d, 2 * fs), "Ws_down": (fs, d), "Ws_gate": (d, 1)}
    else:
        raise ValueError(f"unknown mlp_kind {cfg.mlp_kind!r}: expected "
                         "'moe' or 'swiglu'")
    if kind == "deltanet":
        kd = cfg.gdn_key_heads * cfg.gdn_key_dim
        vd = cfg.gdn_value_heads * cfg.gdn_value_dim
        out.update(Wqkvz=(d, 2 * kd + 2 * vd),
                   Wba=(d, 2 * cfg.gdn_value_heads),
                   conv=(cfg.gdn_conv_width, 2 * kd + vd),
                   A_log=(cfg.gdn_value_heads,),
                   dt_bias=(cfg.gdn_value_heads,),
                   gnorm=(cfg.gdn_value_dim,), Wo=(vd, d))
    elif kind in ("full", "attention"):
        h, hk, dh = cfg.n_heads, cfg.kv_heads, cfg.d_head
        out.update(Wq=(d, (2 if cfg.attn_gate else 1) * h * dh),
                   Wk=(d, hk * dh), Wv=(d, hk * dh), Wo=(h * dh, d))
        if cfg.qk_norm:
            out.update(qnorm=(dh,), knorm=(dh,))
    elif kind == "mamba2":
        di = cfg.ssm_heads * cfg.ssm_head_dim
        xbc = di + 2 * cfg.ssm_groups * cfg.ssm_state
        out.update(Win=(d, di + xbc), Wdt=(d, cfg.ssm_heads),
                   conv=(cfg.ssm_conv_width, xbc), conv_b=(xbc,),
                   A_log=(cfg.ssm_heads,), dt_bias=(cfg.ssm_heads,),
                   D=(cfg.ssm_heads,), gnorm=(di,), Wo=(di, d))
    else:
        raise ValueError(f"unknown layer kind {kind!r}: expected one of "
                         f"{KINDS}")
    return out


def n_periods(cfg) -> int:
    if cfg.n_layers % len(cfg.layer_types):
        raise ValueError(f"n_layers {cfg.n_layers} is not whole periods of "
                         f"layer_types {cfg.layer_types}")
    return cfg.n_layers // len(cfg.layer_types)


def block_keys(cfg):
    """(key, kind, leading axes beside the period's) of `blocks`' entries:
    a layer each, `l<i>`, or with `cfg.stack_runs` a run of like layers
    each, `r<j>` with its length."""
    if not cfg.stack_runs:
        return [(f"l{i}", kind, ()) for i, kind in enumerate(cfg.layer_types)]
    runs = []
    for kind in cfg.layer_types:
        if runs and runs[-1][0] == kind:
            runs[-1][1] += 1
        else:
            runs.append([kind, 1])
    return [(f"r{j}", kind, (n,)) for j, (kind, n) in enumerate(runs)]


def init_params(cfg, key: Array) -> Dict[str, Any]:
    """embed, lnfg, Wout (unless tied) and `block_keys`' entries stacked
    over periods: matrices
    N(0, 1/fan_in), norms' w nought, plain gains 1, A_log log U(1, 16),
    dt_bias the inverse softplus of a step log-uniform in [0.001, 0.1]
    (the family's initialiser: a position forgets 0.1% to 80% of the
    state; with dt_bias 1 it forgets nearly all of it, the output is one
    position's term, and its norm turns on the sign of k . q)."""
    p = n_periods(cfg)
    blocks = {}
    for i, (block, kind, lead) in enumerate(block_keys(cfg)):
        leaves = {}
        for j, (name, shape) in enumerate(sorted(
                layer_shapes(cfg, kind).items())):
            k = jax.random.fold_in(jax.random.fold_in(key, i), j)
            full = (p,) + lead + shape
            if name.startswith("W") or name in ("router", "conv"):
                leaves[name] = (jax.random.normal(k, full, F32)
                                / jnp.sqrt(F32(shape[-2])))
            elif name == "A_log":
                leaves[name] = jnp.log(jax.random.uniform(
                    k, full, F32, 1.0, 16.0))
            elif name == "dt_bias":     # softplus^-1 of log U(.001, .1)
                dt = jnp.exp(jax.random.uniform(
                    k, full, F32, jnp.log(0.001), jnp.log(0.1)))
                leaves[name] = dt + jnp.log(-jnp.expm1(-dt))
            elif name in ("gnorm", "D"):
                leaves[name] = jnp.ones(full, F32)
            else:
                leaves[name] = jnp.zeros(full, F32)
        blocks[block] = leaves
    ke, ko = jax.random.split(jax.random.fold_in(key, 1 << 20))
    d, v = cfg.d_model, cfg.vocab_size
    out = {"embed": jax.random.normal(ke, (v, d), F32) * 0.02,
           "blocks": blocks, "lnfg": jnp.zeros((d,), F32)}
    if not cfg.tie_head:
        out["Wout"] = jax.random.normal(ko, (d, v), F32) / jnp.sqrt(F32(d))
    return out


# ---------------------------------------------------------------------------
# pieces
# ---------------------------------------------------------------------------

def rms_norm(x: Array, w: Array, eps: float) -> Array:
    """x / sqrt(mean(x^2) + eps) * (1 + w), computed in float32."""
    xf = x.astype(F32)
    y = xf * lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
    return (y * (1.0 + w.astype(F32))).astype(x.dtype)


def rotary(x: Array, theta: float, rot: int) -> Array:
    """Rotate-half positions 0 .. T-1 on the first `rot` of a head's
    dimensions, in float32; x [B, T, H, dh]."""
    t = x.shape[1]
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=F32) / rot))
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    xr = x[..., :rot].astype(F32)
    half = jnp.concatenate([-xr[..., rot // 2:], xr[..., :rot // 2]], -1)
    return jnp.concatenate([(xr * cos + half * sin).astype(x.dtype),
                            x[..., rot:]], -1)


def _mm(x: Array, w: Array) -> Array:
    return jnp.matmul(x, w.astype(x.dtype))


def grouped_query_attention(x: Array, p: Dict[str, Array], cfg) -> Array:
    """The one attention mixer; `cfg.attn_gate`, `cfg.qk_norm`,
    `cfg.rotary_fraction` and `cfg.attn_scale` say which parts it has."""
    from deeplearning4j_tpu.nn.layers.attention import dot_product_attention
    b, t, _ = x.shape
    h, hk, dh = cfg.n_heads, cfg.kv_heads, cfg.d_head
    if cfg.attn_gate:
        qg = _mm(x, p["Wq"]).reshape(b, t, h, 2 * dh)
        q, gate = qg[..., :dh], qg[..., dh:]
    else:
        q = _mm(x, p["Wq"]).reshape(b, t, h, dh)
    k = _mm(x, p["Wk"]).reshape(b, t, hk, dh)
    v = _mm(x, p["Wv"]).reshape(b, t, hk, dh)
    rot = int(dh * cfg.rotary_fraction)
    if cfg.qk_norm:
        q = rms_norm(q, p["qnorm"], cfg.eps)
        k = rms_norm(k, p["knorm"], cfg.eps)
    if rot:
        q, k = (rotary(q, cfg.rope_theta, rot),
                rotary(k, cfg.rope_theta, rot))
    a = dot_product_attention(q, k, v, causal=True,
                              scale=cfg.attn_scale or None)  # [B, T, H, dh]
    if cfg.attn_gate:
        a = a * jax.nn.sigmoid(gate.astype(F32)).astype(a.dtype)
    return _mm(a.reshape(b, t, h * dh), p["Wo"])


def causal_conv(x: Array, w: Array, bias: Array = None) -> Array:
    """Depthwise causal convolution: x [B, T, C], w [W, C], bias [C] or
    none. The taps are summed in float32 (a tap's gradient is a sum over
    every position of the batch), the result is in x's dtype."""
    width, t = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0))).astype(F32)
    y = sum(xp[:, i:i + t] * w[i] for i in range(width))
    if bias is not None:
        y = y + bias
    return y.astype(x.dtype)


def _l2(x: Array) -> Array:
    xf = x.astype(F32)
    return xf * lax.rsqrt(jnp.sum(jnp.square(xf), -1, keepdims=True) + 1e-6)


def gated_deltanet(x: Array, p: Dict[str, Array], cfg) -> Array:
    from deeplearning4j_tpu.ops.gated_delta import gated_delta_rule
    b, t, _ = x.shape
    hk, hv = cfg.gdn_key_heads, cfg.gdn_value_heads
    dk, dv = cfg.gdn_key_dim, cfg.gdn_value_dim
    r = hv // hk
    qkvz = _mm(x, p["Wqkvz"]).reshape(b, t, hk, 2 * dk + 2 * r * dv)
    # the gates' two thin columns in float32: they feed exp and sigmoid
    ba = jnp.matmul(x.astype(F32), p["Wba"],
                    precision=_HI).reshape(b, t, hk, 2 * r)
    z = qkvz[..., 2 * dk + r * dv:].reshape(b, t, hv, dv)
    mixed = jnp.concatenate(
        [qkvz[..., :dk].reshape(b, t, hk * dk),
         qkvz[..., dk:2 * dk].reshape(b, t, hk * dk),
         qkvz[..., 2 * dk:2 * dk + r * dv].reshape(b, t, hv * dv)], -1)
    mixed = jax.nn.silu(causal_conv(mixed, p["conv"]))
    q = mixed[..., :hk * dk].reshape(b, t, hk, dk)
    k = mixed[..., hk * dk:2 * hk * dk].reshape(b, t, hk, dk)
    v = mixed[..., 2 * hk * dk:].reshape(b, t, hv, dv)
    beta = jax.nn.sigmoid(ba[..., :r].reshape(b, t, hv))
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(
        ba[..., r:].reshape(b, t, hv) + p["dt_bias"])
    q = (_l2(q) * dk ** -0.5).astype(x.dtype)
    k = _l2(k).astype(x.dtype)
    o = gated_delta_rule(q, k, v, g, beta)
    of = o.astype(F32)
    of = of * lax.rsqrt(jnp.mean(jnp.square(of), -1, keepdims=True)
                        + cfg.eps) * p["gnorm"]
    o = (of * jax.nn.silu(z.astype(F32))).astype(x.dtype)
    return _mm(o.reshape(b, t, hv * dv), p["Wo"])


def mamba2(x: Array, p: Dict[str, Array], cfg) -> Array:
    from deeplearning4j_tpu.ops.mamba2_ssd import ssd_scan
    b, t, _ = x.shape
    h, hd, n, g = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state,
                   cfg.ssm_groups)
    di = h * hd
    zxbc = _mm(x, p["Win"])
    # the step's thin column in float32: it feeds softplus and exp
    dt = jnp.matmul(x.astype(F32), p["Wdt"], precision=_HI)
    z = zxbc[..., :di]
    with jax.named_scope("mamba.conv"):
        xbc = jax.nn.silu(causal_conv(zxbc[..., di:], p["conv"],
                                      p["conv_b"]))
    xs = xbc[..., :di].reshape(b, t, h, hd)
    step = jax.nn.softplus(dt + p["dt_bias"])
    with jax.named_scope("mamba.ssd"):
        y = ssd_scan(xs, xbc[..., di:di + g * n].reshape(b, t, g, n),
                     xbc[..., di + g * n:].reshape(b, t, g, n), step,
                     -jnp.exp(p["A_log"]) * step)
    yf = y.astype(F32) + p["D"][:, None] * xs.astype(F32)
    yf = (yf.reshape(b, t, di) * jax.nn.silu(z.astype(F32))).reshape(
        b, t, g, di // g)
    yf = yf * lax.rsqrt(jnp.mean(jnp.square(yf), -1, keepdims=True)
                        + cfg.eps)
    return _mm((yf.reshape(b, t, di) * p["gnorm"]).astype(x.dtype), p["Wo"])


def _swiglu(x: Array, w_gu: Array, w_down: Array) -> Array:
    gu = _mm(x, w_gu)
    f = gu.shape[-1] // 2
    return _mm(jax.nn.silu(gu[..., :f]) * gu[..., f:], w_down)


def route(x: Array, router: Array, top_k: int):
    """Softmax over all experts in float32, the top_k, their weights
    divided by their sum: (experts [N, k] int32, weights [N, k] f32)."""
    prob = jax.nn.softmax(jnp.matmul(x.astype(F32), router, precision=_HI),
                          axis=-1)
    w, idx = lax.top_k(prob, top_k)
    return idx.astype(jnp.int32), w / jnp.sum(w, -1, keepdims=True)


def moe_topk(x: Array, p: Dict[str, Array], cfg, first: int = 0) -> Array:
    """x [B, T, D]: the held experts' part of the routed sum, dropless,
    plus the gated shared expert."""
    from deeplearning4j_tpu.observability.metrics import default_registry
    from deeplearning4j_tpu.observability.tracing import mark
    from deeplearning4j_tpu.ops import grouped_matmul as gm

    b, t, d = x.shape
    n, k, held = b * t, cfg.moe_top_k, cfg.experts_held
    xf = x.reshape(n, d)
    mark("moe.share", held=held, of=cfg.n_experts, top_k=k, tokens=n,
         buffer_rows=gm.capacity_rows(n * k, held, cfg.n_experts),
         worst_rows=gm.buffer_rows(n * k, held),
         capacity_factor=gm.CAPACITY_FACTOR,
         overflow_counted=gm.counts_overflow())
    registry = default_registry()
    registry.counter(
        "moe_calls", "top-k mixture-of-experts layers traced").inc()
    registry.counter(
        "moe_overflow", "executions of a dropless layer's overflow "
        "path: more live rows than its capacity, every one computed")
    with jax.named_scope("moe.route"):
        router = p["router"]
        if not cfg.train_router:        # frozen: the leaf's gradient only
            router = lax.stop_gradient(router)
        idx, w = route(xf, router, k)
        plan = gm.plan_groups(idx, first, held, cfg.n_experts)
    y = gm.dropless_experts(xf, w, p["We_gu"], p["We_down"], plan)
    with jax.named_scope("moe.shared"):
        gate = jax.nn.sigmoid(jnp.matmul(xf.astype(F32), p["Ws_gate"],
                                         precision=_HI))
        y = y + gate.astype(x.dtype) * _swiglu(xf, p["Ws_gu"], p["Ws_down"])
    return y.reshape(b, t, d)


MIXERS = {"deltanet": ("deltanet", gated_deltanet),
          "full": ("attn", grouped_query_attention),
          "attention": ("attn", grouped_query_attention),
          "mamba2": ("mamba", mamba2)}


def _joins(y: Array, cfg) -> Array:
    """What a mixer or an MLP adds to the stream."""
    return y if cfg.residual_scale == 1.0 else y * cfg.residual_scale


def layer_forward(h: Array, p: Dict[str, Array], cfg, kind: str) -> Array:
    scope, mixer = MIXERS[kind]
    with jax.named_scope(scope):
        h = h + _joins(mixer(rms_norm(h, p["ln1"], cfg.eps), p, cfg), cfg)
    with jax.named_scope("mlp"):
        x = rms_norm(h, p["ln2"], cfg.eps)
        if cfg.mlp_kind == "swiglu":
            return h + _joins(_swiglu(x, p["W_gu"], p["W_down"]), cfg)
        return h + _joins(moe_topk(x, p, cfg), cfg)


def _own_slice(p: Dict[str, Array], h: Array) -> Dict[str, Array]:
    """A scanned layer's matrices made to depend on the loop's carry (plus
    nought times one of its elements, which no finite value changes).
    XLA's TPU pipeline otherwise moves the cast to the activation dtype
    above the slice and out of the loop, through `optimization_barrier`
    too: a bfloat16 copy of every stacked layer that lives from the
    forward loop to the backward one, 1.87 GiB of a step's 16.52 at
    Granite's sizes (perfbench/compile_check.py: 14.64 GiB with this).
    The sum fuses into the cast."""
    zero = lax.stop_gradient(h[0, 0, 0] * 0).astype(F32)
    return {k: (w + zero if w.ndim >= 2 else w) for k, w in p.items()}


def periods_forward(h: Array, blocks: Dict[str, Dict[str, Array]],
                    cfg) -> Array:
    """Every period held, scanned: `blocks["l<i>"][name]` is `[P, ...]`.
    The single-device forward and the parallel step's stage both are
    this."""
    def period(h, p):
        return period_forward(h, p, cfg), None
    return lax.scan(period, h, blocks)[0]


def period_forward(h: Array, blocks: Dict[str, Dict[str, Array]],
                   cfg) -> Array:
    """One period's layers in turn; `blocks`' entries without the period
    axis, a run of like layers scanned where they are stacked
    (`block_keys`). With `cfg.remat` each layer keeps only its input."""
    for key, kind, lead in block_keys(cfg):
        fn = lambda h_, p_, kind=kind, own=bool(lead): layer_forward(  # noqa: E731,E501
            h_, _own_slice(p_, h_) if own else p_, cfg, kind)
        if cfg.remat:
            # prevent_cse stays on: a period's layers share one scan body
            # (one iteration where one period is held), and without the
            # barrier XLA merges a layer's recomputation with its forward
            # and keeps every layer's activations
            fn = jax.checkpoint(fn)
        if lead:
            h = lax.scan(lambda h_, p_, fn=fn: (fn(h_, p_), None), h,
                         blocks[key])[0]
        else:
            h = fn(h, blocks[key])
    return h
