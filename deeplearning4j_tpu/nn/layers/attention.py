"""Attention layers: LayerNormalization, MultiHeadAttention, TransformerBlock.

NET-NEW capability: the reference has no attention anywhere (SURVEY.md §5.7 —
its only long-sequence mechanism is truncated BPTT), but the task requires
long-context sequence/context parallelism, which needs attention. These
layers are designed for sharding from the start:

- head dim is a real axis ([B, T, H, Dh]) so tensor parallelism shards H
  over the 'model' mesh axis with zero layout churn;
- the functional core (`dot_product_attention`) takes explicit query/key
  position offsets so sequence-parallel callers (ring attention,
  parallel/ring.py) can apply causal masks on global positions while holding
  only a local block;
- matmuls are laid out [*, T, Dh] x [*, Dh, S] — MXU-shaped, bfloat16-safe
  (softmax accumulates in >=f32; f64 inputs keep f64).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.activations import get_activation
from deeplearning4j_tpu.nn.conf import inputs as it
from deeplearning4j_tpu.nn.conf.serde import register
from deeplearning4j_tpu.nn.layers.base import BaseLayer, Layer, apply_dropout
from deeplearning4j_tpu.nn.weights import init_weights

Array = jax.Array

NEG_INF = -1e30


def dot_product_attention(q: Array, k: Array, v: Array, *,
                          causal: bool = False,
                          mask: Optional[Array] = None,
                          q_offset=0, kv_offset=0,
                          scale: Optional[float] = None) -> Array:
    """Scaled dot-product attention.

    q: [B, T, H, Dh]; k, v: [B, S, H, Dh] -> [B, T, H, Dh]. k and v may
    hold fewer heads (grouped-query attention): KV head j serves query
    heads [j * H / Hkv, (j + 1) * H / Hkv).
    ``mask``: optional [B, S] {0,1} key-validity mask.
    ``q_offset``/``kv_offset``: global positions of q[0] / k[0] — causal
    masking compares global positions, enabling blockwise/ring callers.
    Scores and softmax accumulate in at least float32 (f64 inputs keep
    f64 — the gradient-check suites run whole nets in float64).
    """
    dh = q.shape[-1]
    # dh is static — python math keeps scale concrete under jit (the
    # pallas dispatch below needs a weak-typed float)
    scale = (dh ** -0.5) if scale is None else scale
    # Pallas fast path (ops/flash_attention.py) — the cuDNN-helper
    # pattern: kernel when eligible, this jnp path as the fallback.
    # Offsets must be concrete (custom_vjp statics); traced offsets
    # (shard_map ring callers) take the fallback.
    if isinstance(q_offset, int) and isinstance(kv_offset, int) \
            and isinstance(scale, (int, float)):
        from deeplearning4j_tpu.ops.flash_attention import (
            flash_attention, flash_attention_available)
        if flash_attention_available(q, k, mask):
            return flash_attention(q, k, v, causal=causal,
                                   q_offset=q_offset, kv_offset=kv_offset,
                                   scale=float(scale))
    # [B, H, T, S] — accumulate in >=f32 (f64 inputs keep f64: the
    # gradient-check suites run the whole net in float64)
    acc = jnp.promote_types(q.dtype, jnp.float32)
    if k.shape[2] != q.shape[2]:
        k = jnp.repeat(k, q.shape[2] // k.shape[2], axis=2)
        v = jnp.repeat(v, q.shape[2] // v.shape[2], axis=2)
    scores = jnp.einsum("bthd,bshd->bhts", q, k,
                        preferred_element_type=acc) * scale
    if causal:
        qpos = q_offset + jnp.arange(q.shape[1])
        kpos = kv_offset + jnp.arange(k.shape[1])
        cm = qpos[:, None] >= kpos[None, :]  # [T, S]
        scores = jnp.where(cm[None, None], scores, NEG_INF)
    if mask is not None:
        scores = jnp.where(mask[:, None, None, :].astype(bool), scores,
                           NEG_INF)
    weights = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhts,bshd->bthd", weights.astype(v.dtype), v)
    return out


def _ln_dtype(dtype):
    return jnp.promote_types(dtype, jnp.float32)


def layer_norm(x: Array, gamma: Array, beta: Array,
               eps: float = 1e-5) -> Array:
    xf = x.astype(_ln_dtype(x.dtype))
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * gamma + beta).astype(x.dtype)


@register
@dataclass
class LayerNormalization(Layer):
    """Per-feature layer norm over the last axis (net-new vs the reference,
    which only has BatchNormalization)."""
    n_out: Optional[int] = None
    eps: float = 1e-5
    _family: str = "ff"

    @property
    def family(self):
        return self._family

    @property
    def input_family(self):
        return self._family

    def weight_param_keys(self):
        return ()

    def update_input_type(self, input_type):
        if isinstance(input_type, it.InputTypeRecurrent):
            self._family = "rnn"
            self.n_out = self.n_out or input_type.size
        elif isinstance(input_type, it.InputTypeFeedForward):
            self._family = "ff"
            self.n_out = self.n_out or input_type.size
        else:
            raise ValueError("LayerNormalization needs ff/rnn input")
        return input_type

    def init_params(self, key, dtype=jnp.float32):
        return {"gamma": jnp.ones((self.n_out,), _ln_dtype(dtype)),
                "beta": jnp.zeros((self.n_out,), _ln_dtype(dtype))}

    def apply(self, params, state, x, *, train=False, key=None, mask=None):
        return layer_norm(x, params["gamma"], params["beta"], self.eps), state


@register
@dataclass
class MultiHeadAttention(BaseLayer):
    """Self-attention over [B, T, D] -> [B, T, D]."""
    n_in: Optional[int] = None
    n_out: Optional[int] = None
    n_heads: int = 4
    causal: bool = False
    attn_dropout: float = 0.0

    @property
    def family(self):
        return "rnn"

    def update_input_type(self, input_type):
        if not isinstance(input_type, it.InputTypeRecurrent):
            raise ValueError("MultiHeadAttention needs recurrent input")
        if self.n_in is None:
            self.n_in = input_type.size
        if self.n_out is None:
            self.n_out = self.n_in
        if self.n_out % self.n_heads:
            raise ValueError(f"n_out {self.n_out} not divisible by n_heads "
                             f"{self.n_heads}")
        return it.InputType.recurrent(self.n_out,
                                      input_type.time_series_length)

    def init_params(self, key, dtype=jnp.float32):
        kq, kk, kv, ko = jax.random.split(key, 4)
        d, o = self.n_in, self.n_out
        scheme = self.weight_init or "xavier"

        def w(k, shape, fi, fo):
            return init_weights(k, shape, fi, fo, scheme, self.dist, dtype)

        return {"Wq": w(kq, (d, o), d, o), "Wk": w(kk, (d, o), d, o),
                "Wv": w(kv, (d, o), d, o), "Wo": w(ko, (o, o), o, o),
                "bq": jnp.zeros((o,), dtype), "bk": jnp.zeros((o,), dtype),
                "bv": jnp.zeros((o,), dtype), "bo": jnp.zeros((o,), dtype)}

    def weight_param_keys(self):
        return ("Wq", "Wk", "Wv", "Wo")

    def _heads(self, x, w, b):
        y = jnp.matmul(x, w.astype(x.dtype)) + b.astype(x.dtype)
        b_, t = y.shape[0], y.shape[1]
        return y.reshape(b_, t, self.n_heads, self.n_out // self.n_heads)

    def apply(self, params, state, x, *, train=False, key=None, mask=None):
        q = self._heads(x, params["Wq"], params["bq"])
        k = self._heads(x, params["Wk"], params["bk"])
        v = self._heads(x, params["Wv"], params["bv"])
        out = dot_product_attention(q, k, v, causal=self.causal, mask=mask)
        b_, t = out.shape[0], out.shape[1]
        out = out.reshape(b_, t, self.n_out)
        out = jnp.matmul(out, params["Wo"].astype(x.dtype)) \
            + params["bo"].astype(x.dtype)
        if train and self.attn_dropout > 0 and key is not None:
            out = apply_dropout(out, self.attn_dropout, key)
        return out, state


@register
@dataclass
class TransformerBlock(BaseLayer):
    """Pre-LN transformer block: x + MHA(LN(x)); x + MLP(LN(x)).

    One config class rather than a vertex subgraph: the block is the unit of
    pipeline parallelism (stacked blocks shard over the 'pipe' axis) and of
    `lax.scan` over depth, so it must be a single traced function.
    """
    n_in: Optional[int] = None
    n_heads: int = 4
    mlp_ratio: int = 4
    causal: bool = True
    eps: float = 1e-5

    @property
    def family(self):
        return "rnn"

    def update_input_type(self, input_type):
        if not isinstance(input_type, it.InputTypeRecurrent):
            raise ValueError("TransformerBlock needs recurrent input")
        if self.n_in is None:
            self.n_in = input_type.size
        if self.n_in % self.n_heads:
            raise ValueError("n_in not divisible by n_heads")
        return input_type

    @property
    def n_out(self):
        return self.n_in

    def init_params(self, key, dtype=jnp.float32):
        d = self.n_in
        f = d * self.mlp_ratio
        ks = jax.random.split(key, 6)
        scheme = self.weight_init or "xavier"

        def w(k, shape, fi, fo):
            return init_weights(k, shape, fi, fo, scheme, self.dist, dtype)

        return {
            "Wq": w(ks[0], (d, d), d, d), "Wk": w(ks[1], (d, d), d, d),
            "Wv": w(ks[2], (d, d), d, d), "Wo": w(ks[3], (d, d), d, d),
            "W1": w(ks[4], (d, f), d, f), "W2": w(ks[5], (f, d), f, d),
            "b1": jnp.zeros((f,), dtype), "b2": jnp.zeros((d,), dtype),
            # LN params stay >=f32 (bf16 LN scales lose precision); f64
            # nets keep f64 so gradient checks see full precision
            "ln1g": jnp.ones((d,), _ln_dtype(dtype)),
            "ln1b": jnp.zeros((d,), _ln_dtype(dtype)),
            "ln2g": jnp.ones((d,), _ln_dtype(dtype)),
            "ln2b": jnp.zeros((d,), _ln_dtype(dtype)),
        }

    def weight_param_keys(self):
        return ("Wq", "Wk", "Wv", "Wo", "W1", "W2")

    def apply(self, params, state, x, *, train=False, key=None, mask=None):
        d = self.n_in
        h = layer_norm(x, params["ln1g"], params["ln1b"], self.eps)

        def heads(y):
            b_, t = y.shape[0], y.shape[1]
            return y.reshape(b_, t, self.n_heads, d // self.n_heads)

        q = heads(jnp.matmul(h, params["Wq"].astype(h.dtype)))
        k = heads(jnp.matmul(h, params["Wk"].astype(h.dtype)))
        v = heads(jnp.matmul(h, params["Wv"].astype(h.dtype)))
        a = dot_product_attention(q, k, v, causal=self.causal, mask=mask)
        b_, t = a.shape[0], a.shape[1]
        x = x + jnp.matmul(a.reshape(b_, t, d),
                           params["Wo"].astype(x.dtype))
        h = layer_norm(x, params["ln2g"], params["ln2b"], self.eps)
        h = get_activation("gelu")(jnp.matmul(h, params["W1"].astype(h.dtype))
                                   + params["b1"].astype(h.dtype))
        x = x + jnp.matmul(h, params["W2"].astype(x.dtype)) \
            + params["b2"].astype(x.dtype)
        return x, state
