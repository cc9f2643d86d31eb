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
- ``"mla"``: latent attention (`latent_attention`). Queries and
  keys/values go through low-rank latents, an RMSNorm on each:
  ``q = rms(x Wq_a) Wq_b`` a head ``[q_nope | q_rope]``;
  ``x Wkv_a = [c_kv | k_r]``, ``rms(c_kv) Wkv_b`` a head ``[k_nope | v]``.
  Rotary positions turn ``q_rope`` and the one ``k_r`` that every head
  shares; a head's key is ``[k_nope | k_r]``, its value has a width of
  its own; no q/k norms, no gate. K and V are formed whole (the
  "expanded" form) and go through the same attention as the other kinds.
- ``"shortconv"``: the gated short convolution (LFM2's). ``Win`` gives
  ``B | C | x~``; ``B * x~`` through a depthwise causal convolution of
  `cfg.shortconv_width` taps, no bias, no activation; ``Wo(C * conv)``.
  The gates and the convolution are the scope ``shortconv.conv``.
- ``"deltanet"``: Gated DeltaNet. ``Wqkvz`` (laid out by key head: q, k,
  v of its value heads, z of its value heads) and ``Wba``; q, k, v through
  a causal depthwise convolution and SiLU; the gated delta rule
  (ops/gated_delta.py) over L2-normalised q and k;
  ``Wo(norm(o) * gnorm * silu(z))``.

A layer's second half is what `cfg.mlp_kind` names: ``"swiglu"``, one dense
gated MLP of width `dense_d_ff` (``W_gu`` gate | up, ``W_down``), or
``"moe"``, a top-k mixture of experts with a shared expert of
`cfg.shared_d_ff` (none where it is 0), gated or (`cfg.shared_gate` false)
not (`moe_topk`); `cfg.router_scoring` says how the router scores
(`route`). That layer is told which experts it holds
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

Outside the periods: `cfg.lead_dense_layers` layers before the first one
(``lead["l<i>"][name]``, no period axis: the mixer `cfg.lead_layer_types`
names, or the period's first, and a dense SwiGLU whatever `mlp_kind` says;
`lead_forward`), and with `cfg.mtp_layers` a multi-token-prediction module
after the final norm (``mtp``: ``enorm``, ``hnorm``, ``eh_proj``, one
``layer`` of the period's last kind, ``norm``; `mtp_hidden`), whose loss
models/transformer.py adds.
These kinds run on the training path (models/transformer.py's forward and
parallel/megatron.py's step on data / pipe axes); serving refuses them.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.models.remat import remat_layer
from deeplearning4j_tpu.ops.causal_conv import causal_conv, lanes

Array = jax.Array
F32 = jnp.float32
KINDS = ("deltanet", "full", "mamba2", "attention", "mla", "shortconv")
_HI = lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def layer_shapes(cfg, kind: str, mlp: str = None) -> Dict[str, tuple]:
    """Leaf shapes of one layer of `kind`, without the period axis; its
    second half is `mlp`, or what `cfg.mlp_kind` names."""
    d, f, fs = cfg.d_model, cfg.moe_d_ff, cfg.shared_d_ff
    mlp = mlp or cfg.mlp_kind
    if mlp == "swiglu":
        out = {"ln1": (d,), "ln2": (d,), "W_gu": (d, 2 * cfg.dense_d_ff),
               "W_down": (cfg.dense_d_ff, d)}
    elif mlp == "moe":
        out = {"ln1": (d,), "ln2": (d,), "router": (d, cfg.n_experts),
               "We_gu": (cfg.experts_held, d, 2 * f),
               "We_down": (cfg.experts_held, f, d)}
        if fs:
            out.update(Ws_gu=(d, 2 * fs), Ws_down=(fs, d))
            if cfg.shared_gate:
                out["Ws_gate"] = (d, 1)
        if cfg.router_scoring == "sigmoid":
            out["router_bias"] = (cfg.n_experts,)
    else:
        raise ValueError(f"unknown mlp_kind {mlp!r}: expected "
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
    elif kind == "mla":
        h, rq, rkv = cfg.n_heads, cfg.q_lora_rank, cfg.kv_lora_rank
        if not (rq and rkv and cfg.qk_rope_dim and cfg.v_head_dim):
            raise ValueError(
                "layer kind 'mla' needs TransformerConfig.q_lora_rank, "
                "kv_lora_rank, qk_rope_dim and v_head_dim")
        out.update(Wq_a=(d, rq), q_a_norm=(rq,),
                   Wq_b=(rq, h * (cfg.qk_nope_dim + cfg.qk_rope_dim)),
                   Wkv_a=(d, rkv + cfg.qk_rope_dim), kv_a_norm=(rkv,),
                   Wkv_b=(rkv, h * (cfg.qk_nope_dim + cfg.v_head_dim)),
                   Wo=(h * cfg.v_head_dim, d))
    elif kind == "shortconv":
        out.update(Win=(d, 3 * d), conv=(cfg.shortconv_width, d), Wo=(d, d))
    else:
        raise ValueError(f"unknown layer kind {kind!r}: expected one of "
                         f"{KINDS}")
    return out


def n_periods(cfg) -> int:
    n = cfg.n_layers - cfg.lead_dense_layers
    if n % len(cfg.layer_types):
        raise ValueError(f"n_layers {cfg.n_layers} less lead_dense_layers "
                         f"{cfg.lead_dense_layers} is not whole periods of "
                         f"layer_types {cfg.layer_types}")
    return n // len(cfg.layer_types)


def lead_kinds(cfg) -> tuple:
    """The leading layers' mixers: `cfg.lead_layer_types`, one a layer, or
    the period's first for each."""
    kinds = cfg.lead_layer_types or (cfg.layer_types[0],) * \
        cfg.lead_dense_layers
    if len(kinds) != cfg.lead_dense_layers:
        raise ValueError(f"TransformerConfig.lead_layer_types={kinds} names "
                         f"{len(kinds)} mixers for lead_dense_layers="
                         f"{cfg.lead_dense_layers}")
    return tuple(kinds)


def lead_shapes(cfg) -> Dict[str, Dict[str, tuple]]:
    """`lead`'s entries, a leading layer each: its mixer (`lead_kinds`)
    and a dense SwiGLU."""
    return {f"l{i}": layer_shapes(cfg, kind, "swiglu")
            for i, kind in enumerate(lead_kinds(cfg))}


def mtp_shapes(cfg) -> Dict[str, Any]:
    """`mtp`'s entries: the norms of the two inputs, the projection of
    the pair, one layer of the period's last kind, the head's norm."""
    if cfg.mtp_layers != 1:
        raise ValueError(f"TransformerConfig.mtp_layers={cfg.mtp_layers}: "
                         "one multi-token-prediction module is there")
    d = cfg.d_model
    return {"enorm": (d,), "hnorm": (d,), "eh_proj": (2 * d, d),
            "layer": layer_shapes(cfg, cfg.layer_types[-1]), "norm": (d,)}


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
    """embed, lnfg, Wout (unless tied), `block_keys`' entries stacked
    over periods and, where the config has them, `lead` and `mtp`: matrices
    N(0, 1/fan_in), norms' w nought, plain gains 1, A_log log U(1, 16),
    dt_bias the inverse softplus of a step log-uniform in [0.001, 0.1]
    (the family's initialiser: a position forgets 0.1% to 80% of the
    state; with dt_bias 1 it forgets nearly all of it, the output is one
    position's term, and its norm turns on the sign of k . q)."""
    p = n_periods(cfg)

    def leaves(shapes, key, lead=()):
        out = {}
        for j, (name, shape) in enumerate(sorted(shapes.items())):
            k = jax.random.fold_in(key, j)
            if isinstance(shape, dict):
                out[name] = leaves(shape, k, lead)
                continue
            full = lead + shape
            if name.startswith("W") or name in ("router", "conv",
                                                  "eh_proj"):
                out[name] = (jax.random.normal(k, full, F32)
                             / jnp.sqrt(F32(shape[-2])))
            elif name == "A_log":
                out[name] = jnp.log(jax.random.uniform(
                    k, full, F32, 1.0, 16.0))
            elif name == "dt_bias":     # softplus^-1 of log U(.001, .1)
                dt = jnp.exp(jax.random.uniform(
                    k, full, F32, jnp.log(0.001), jnp.log(0.1)))
                out[name] = dt + jnp.log(-jnp.expm1(-dt))
            elif name in ("gnorm", "D"):
                out[name] = jnp.ones(full, F32)
            else:
                out[name] = jnp.zeros(full, F32)
        return out

    blocks = {block: leaves(layer_shapes(cfg, kind),
                            jax.random.fold_in(key, i), (p,) + lead)
              for i, (block, kind, lead) in enumerate(block_keys(cfg))}
    ke, ko = jax.random.split(jax.random.fold_in(key, 1 << 20))
    d, v = cfg.d_model, cfg.vocab_size
    out = {"embed": jax.random.normal(ke, (v, d), F32) * 0.02,
           "blocks": blocks, "lnfg": jnp.zeros((d,), F32)}
    if not cfg.tie_head:
        out["Wout"] = jax.random.normal(ko, (d, v), F32) / jnp.sqrt(F32(d))
    if cfg.lead_dense_layers:
        out["lead"] = leaves(lead_shapes(cfg),
                             jax.random.fold_in(key, 1 << 21))
    if cfg.mtp_layers:
        out["mtp"] = leaves(mtp_shapes(cfg),
                            jax.random.fold_in(key, 1 << 22))
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


def latent_attention(x: Array, p: Dict[str, Array], cfg) -> Array:
    """Multi-head latent attention in its expanded form: every head's
    key and value are formed from the latent and attended as any other
    head's. The rotary key is one for all heads (its gradient the sum
    over them); the score scale is `cfg.attn_scale`, or the key's whole
    width to the power -1/2."""
    from deeplearning4j_tpu.nn.layers.attention import dot_product_attention
    from deeplearning4j_tpu.observability.metrics import default_registry
    from deeplearning4j_tpu.observability.tracing import mark
    b, t, _ = x.shape
    h, dn, dr, dv = (cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim,
                     cfg.v_head_dim)
    r, qk = cfg.kv_lora_rank, dn + dr
    # trace time: elements a token that attention reads of K and V, as
    # formed here and as the latent holds them
    mark("mla.layout", form="expanded", heads=h, qk_dim=qk, v_dim=dv,
         kv_rank=r, rope_dim=dr, kv_expanded_elems=h * (qk + dv),
         kv_latent_elems=r + dr)
    default_registry().counter(
        "mla_calls", "latent-attention mixers traced, by the form K and V "
        "take", labelnames=("form",)).labels("expanded").inc()
    with jax.named_scope("mla.q"):
        c_q = rms_norm(_mm(x, p["Wq_a"]), p["q_a_norm"], cfg.eps)
        q = _mm(c_q, p["Wq_b"]).reshape(b, t, h, qk)
    with jax.named_scope("mla.kv"):
        c_kv = _mm(x, p["Wkv_a"])
        k_r = c_kv[..., r:].reshape(b, t, 1, dr)
        kv = _mm(rms_norm(c_kv[..., :r], p["kv_a_norm"], cfg.eps),
                 p["Wkv_b"]).reshape(b, t, h, dn + dv)
    with jax.named_scope("mla.rope"):
        q = jnp.concatenate(
            [q[..., :dn], rotary(q[..., dn:], cfg.rope_theta, dr)], -1)
        k_r = rotary(k_r, cfg.rope_theta, dr)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_r, (b, t, h, dr))], -1)
    v = kv[..., dn:]
    # the kernels take one width: noughts behind the narrower of key and
    # value change neither a score nor the columns of the output kept. No
    # cell takes either branch (the published key and value are both 256
    # wide); tests/test_glm4_moe_lite.py's unequal widths do, and the
    # branches go when that test does
    pad = lambda a, w: jnp.pad(a, ((0, 0),) * 3 + ((0, w - a.shape[-1]),))  # noqa: E731,E501
    if qk < dv:
        q, k = pad(q, dv), pad(k, dv)
    elif dv < qk:
        v = pad(v, qk)
    a = dot_product_attention(q, k, v, causal=True,
                              scale=cfg.attn_scale or qk ** -0.5)
    return _mm(a[..., :dv].reshape(b, t, h * dv), p["Wo"])


def short_conv(x: Array, p: Dict[str, Array], cfg) -> Array:
    """The gated short convolution: ``[B | C | x~] = x Win``, ``B * x~``
    through the depthwise causal convolution (`causal_conv`: the first tap
    multiplies the oldest position), gated by ``C``, then ``Wo``."""
    from deeplearning4j_tpu.observability.metrics import default_registry
    from deeplearning4j_tpu.observability.tracing import mark
    d = x.shape[-1]
    # trace time: the convolution's shape and the two gates around it
    mark("shortconv.layout", channels=d, taps=cfg.shortconv_width, gates=2)
    default_registry().counter(
        "shortconv_calls", "gated short-convolution mixers traced").inc()
    bcx = _mm(x, p["Win"])
    with jax.named_scope("shortconv.conv"):
        y = causal_conv(lanes(bcx, (2 * d, 3 * d)), p["conv"],
                        pre=lanes(bcx, (0, d)), post=lanes(bcx, (d, 2 * d)))
    return _mm(y, p["Wo"])


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
    # q of every key head, then k, then v, read where the projection
    # left them (a key head's row is q | k | v | z)
    row = 2 * dk + 2 * r * dv
    heads = range(0, hk * row, row)
    mixed = causal_conv(
        lanes(qkvz.reshape(b, t, hk * row),
              *[(h, h + dk) for h in heads],
              *[(h + dk, h + 2 * dk) for h in heads],
              *[(h + 2 * dk, h + 2 * dk + r * dv) for h in heads]),
        p["conv"], act="silu")
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
        xbc = causal_conv(lanes(zxbc, (di, zxbc.shape[-1])), p["conv"],
                          p["conv_b"], act="silu")
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


def route(x: Array, router: Array, top_k: int, scoring: str = "softmax",
          bias: Array = None, scale: float = 1.0):
    """(experts [N, k] int32, weights [N, k] f32), scored in float32.
    `scoring` "softmax": softmax over all experts, the top_k, their
    weights divided by their sum; it takes no `bias`. "sigmoid": an
    expert's score is ``s = sigmoid(x . router)``, the top_k are chosen by
    ``s + bias`` (`bias` [E], required), and their weights are ``s``
    divided by its sum over the chosen: the bias enters the choice and not
    the weight. Either way times `scale`."""
    if scoring not in ("softmax", "sigmoid"):
        raise ValueError(f"unknown router_scoring {scoring!r}: expected "
                         "'softmax' or 'sigmoid'")
    if (bias is not None) != (scoring == "sigmoid"):
        raise ValueError(
            f"router_scoring {scoring!r} and the leaf 'router_bias' "
            f"({'present' if bias is not None else 'missing'}) disagree: "
            "the sigmoid router has the correction bias, the softmax one "
            "has none")
    logits = jnp.matmul(x.astype(F32), router, precision=_HI)
    if scoring == "softmax":
        w, idx = lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
        w = w / jnp.sum(w, -1, keepdims=True)
    else:
        s = jax.nn.sigmoid(logits)
        idx = lax.top_k(s + bias, top_k)[1]
        w = jnp.take_along_axis(s, idx, -1)
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w if scale == 1.0 else w * scale


def moe_topk(x: Array, p: Dict[str, Array], cfg, first: int = 0) -> Array:
    """x [B, T, D]: the held experts' part of the routed sum, dropless,
    plus the shared expert where there is one (`cfg.shared_d_ff`), gated or
    (`cfg.shared_gate` false) not."""
    from deeplearning4j_tpu.observability.metrics import default_registry
    from deeplearning4j_tpu.observability.tracing import mark
    from deeplearning4j_tpu.ops import grouped_matmul as gm

    b, t, d = x.shape
    n, k, held = b * t, cfg.moe_top_k, cfg.experts_held
    xf = x.reshape(n, d)
    mark("moe.share", held=held, of=cfg.n_experts, top_k=k, tokens=n,
         buffer_rows=gm.capacity_rows(n * k, held, cfg.n_experts),
         worst_rows=gm.buffer_rows(n * k, held),
         capacity_factor=gm.CAPACITY_FACTOR, scoring=cfg.router_scoring)
    default_registry().counter(
        "moe_calls", "top-k mixture-of-experts layers traced").inc()
    with jax.named_scope("moe.route"):
        router = p["router"]
        if not cfg.train_router:        # frozen: the leaf's gradient only
            router = lax.stop_gradient(router)
        idx, w = route(xf, router, k, cfg.router_scoring,
                       p.get("router_bias"), cfg.routed_scale)
        plan = gm.plan_groups(idx, first, held, cfg.n_experts)
    y = gm.dropless_experts(xf, w, p["We_gu"], p["We_down"], plan)
    if not cfg.shared_d_ff:
        return y.reshape(b, t, d)
    with jax.named_scope("moe.shared"):
        shared = _swiglu(xf, p["Ws_gu"], p["Ws_down"])
        if cfg.shared_gate:
            gate = jax.nn.sigmoid(jnp.matmul(xf.astype(F32), p["Ws_gate"],
                                             precision=_HI))
            shared = gate.astype(x.dtype) * shared
        y = y + shared
    return y.reshape(b, t, d)


MIXERS = {"deltanet": ("deltanet", gated_deltanet),
          "full": ("attn", grouped_query_attention),
          "attention": ("attn", grouped_query_attention),
          "mamba2": ("mamba", mamba2),
          "mla": ("mla", latent_attention),
          "shortconv": ("shortconv", short_conv)}


def _joins(y: Array, cfg) -> Array:
    """What a mixer or an MLP adds to the stream."""
    return y if cfg.residual_scale == 1.0 else y * cfg.residual_scale


def layer_forward(h: Array, p: Dict[str, Array], cfg, kind: str,
                  mlp: str = None) -> Array:
    scope, mixer = MIXERS[kind]
    with jax.named_scope(scope):
        h = h + _joins(mixer(rms_norm(h, p["ln1"], cfg.eps), p, cfg), cfg)
    with jax.named_scope("mlp"):
        x = rms_norm(h, p["ln2"], cfg.eps)
        if (mlp or cfg.mlp_kind) == "swiglu":
            return h + _joins(_swiglu(x, p["W_gu"], p["W_down"]), cfg)
        return h + _joins(moe_topk(x, p, cfg), cfg)


def _one_layer(cfg, kind: str, mlp: str = None):
    """`layer_forward` of (h, p); with `cfg.remat` it keeps its input and
    its attention kernel's results (models/remat.py)."""
    return remat_layer(lambda h, p: layer_forward(h, p, cfg, kind, mlp),
                       cfg, site="layer_kinds.one_layer")


def lead_forward(h: Array, lead: Dict[str, Dict[str, Array]], cfg) -> Array:
    """The `cfg.lead_dense_layers` layers before the first period, each
    rematerialised alone like a period's."""
    with jax.named_scope("lead"):
        for i, kind in enumerate(lead_kinds(cfg)):
            h = _one_layer(cfg, kind, "swiglu")(h, lead[f"l{i}"])
    return h


def mtp_hidden(hf: Array, e_next: Array, p: Dict[str, Any], cfg) -> Array:
    """The multi-token-prediction module up to its head's norm: `hf`
    [B, T, D] the main model's final-normed hidden states, `e_next` the
    embedding of each position's next token; position i's output predicts
    token i + 2. ``norm(layer([enorm(e_next) ; hnorm(hf)] eh_proj))``,
    the embedding first."""
    from deeplearning4j_tpu.observability.tracing import mark
    mark("mtp.share", depth=cfg.mtp_layers, weight=cfg.mtp_loss_weight)
    u = jnp.concatenate([rms_norm(e_next, p["enorm"], cfg.eps),
                         rms_norm(hf, p["hnorm"], cfg.eps)], -1)
    u = _one_layer(cfg, cfg.layer_types[-1])(_mm(u, p["eh_proj"]),
                                              p["layer"])
    return rms_norm(u, p["norm"], cfg.eps)


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
    # several periods held: a period's matrices are slices of a scanned
    # stack, as a run's are (`_own_slice`)
    own = jax.tree_util.tree_leaves(blocks)[0].shape[0] > 1

    def period(h, p):
        return period_forward(h, p, cfg, own), None
    with jax.named_scope("layers"):
        return lax.scan(period, h, blocks)[0]


def period_forward(h: Array, blocks: Dict[str, Dict[str, Array]],
                   cfg, sliced: bool = False) -> Array:
    """One period's layers in turn; `blocks`' entries without the period
    axis, a run of like layers scanned where they are stacked
    (`block_keys`). With `cfg.remat` each layer keeps its input and its
    attention kernel's results (models/remat.py).
    `sliced`: the entries are slices of a scanned stack of periods."""
    for key, kind, lead in block_keys(cfg):
        fn = lambda h_, p_, kind=kind, own=sliced or bool(lead): layer_forward(  # noqa: E731,E501
            h_, _own_slice(p_, h_) if own else p_, cfg, kind)
        # prevent_cse stays on: a period's layers share one scan body (one
        # iteration where one period is held), and without the barrier XLA
        # merges a layer's recomputation with its forward and keeps every
        # layer's activations
        fn = remat_layer(fn, cfg, site="layer_kinds.period")
        if lead:
            with jax.named_scope("layers"):
                h = lax.scan(lambda h_, p_, fn=fn: (fn(h_, p_), None), h,
                             blocks[key])[0]
        else:
            h = fn(h, blocks[key])
    return h
