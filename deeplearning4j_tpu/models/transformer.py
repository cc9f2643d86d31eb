"""TransformerLM — the flagship TPU-native model family.

NET-NEW vs the reference (it has no attention, SURVEY.md §5.7); this is the
model the long-context and multi-dimensional parallelism requirements hang
off. Design:

- Pure-functional: `init_params` -> pytree, `forward(params, tokens)` ->
  logits, `loss(params, tokens, targets)` -> scalar. The MLN/CG class API
  wraps models like this; the flagship stays functional so the parallel
  train step (parallel/megatron.py) can shard it axis-by-axis.
- Block parameters are STACKED over depth (leading [L] axis) and applied
  with `lax.scan` — one compiled block body regardless of depth, and the
  natural layout for pipeline parallelism (reshape [L] -> [S, L/S], shard
  the stage axis over 'pipe').
- Head axis is explicit; attention runs through the same
  `dot_product_attention` core as the DSL layer, so ring attention drops in
  by replacing that one call.
- Weights stay float32 at rest; activations can run bfloat16 (`dtype`),
  accumulating in f32 on the MXU. For SERVING, `quant/model.py`
  quantizes the tree to int8 (per-output-channel scales); every weight
  use here goes through `.astype(activation_dtype)`, which doubles as
  the on-the-fly dequantization when the leaf is a
  `quant.core.QuantizedTensor` — a quantized tree is a drop-in
  `params` argument for forward/forward_hidden/decode/generate.
"""
from __future__ import annotations

import functools as _ft

from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.models.remat import remat_layer
from deeplearning4j_tpu.nn.layers.attention import (dot_product_attention,
                                                    layer_norm)

Array = jax.Array


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 256
    d_model: int = 128
    n_heads: int = 4
    n_layers: int = 4
    max_len: int = 256
    mlp_ratio: int = 4
    dtype: str = "float32"          # activation dtype ('bfloat16' on TPU)
    n_experts: int = 0              # >0 switches the MLP to MoE every block
    capacity_factor: float = 1.25
    eps: float = 1e-5
    # rematerialize each block on the backward pass (jax.checkpoint):
    # activations are NOT kept through the scan, trading recompute FLOPs
    # for HBM — the long-context lever when T*L activations outgrow HBM
    remat: bool = False
    # what the checkpoint keeps when remat=True (models/remat.py builds it
    # at every site, the parallel step's too):
    #   'full'  — the block's input and its attention kernel's output and
    #             log-sum-exp (the flash kernel names them): H*Dh / D of
    #             the input's bytes more a layer, B x T x H*Dh
    #             activations, plus a float32 or two a row and head. The
    #             backward recomputes everything XLA emits (norms,
    #             projections, q/k/v, the MLP) and does not run the Pallas
    #             forward kernel again
    #   'dots'  — the same and, by
    #             jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    #             the matmul outputs: only elementwise tails recomputed
    #   'mlp'   — checkpoint ONLY the MLP (its [B,T,4D] intermediate is
    #             the memory hog; its recompute is cheap MXU work) and
    #             keep every attention residual. The measured throughput
    #             sweet spot when activations fit (BASELINE.md r3); the
    #             single-device and FSDP steps only: the parallel step and
    #             the typed layers refuse it by name
    remat_policy: str = "full"
    # sequence-parallel attention strategy when the mesh's 'seq' axis > 1:
    # 'ring' (parallel/ring.py: K/V ppermute ring) or 'ulysses'
    # (parallel/ulysses.py: all_to_all head resharding; needs
    # n_heads/tp % sp == 0)
    seq_impl: str = "ring"
    # vocab chunk size for the streaming cross-entropy (0 = dense path).
    # At real-LM vocabularies the [B, T, V] f32 logits of the dense
    # loss are the memory wall (4.3 GB at V=32k/B=16/T=2048, and the
    # dense backward holds logits + log_softmax residuals — ~3x that);
    # with xent_chunk=C the loss scans V/C output-projection panels
    # with an online logsumexp and never materializes more than
    # [B*T, C] — see chunked_cross_entropy
    xent_chunk: int = 0
    # KV-cache at-rest dtype (None = the activation dtype). bf16 caches
    # under f32 activations halve decode-cache HBM on their own; the
    # quantized serving path (quant/kv.py) goes further with int8 rows
    # + per-row scales. Cache writes cast on store; attention reads
    # promote back through the usual matmul dtype rules.
    cache_dtype: Optional[str] = None
    # --- a period of unlike layers (models/layer_kinds.py) -------------
    # the kinds of one period's layers, in order ("deltanet", "full",
    # "mamba2", "attention", "mla"); () is the one GPT-2 block above. With
    # layer_types the model has RMSNorm (1 + w), no learned positions,
    # and after every mixer the MLP that `mlp_kind` names. Training path
    # only: the serving engine refuses a config that sets them.
    layer_types: Tuple[str, ...] = ()
    # True: a period's runs of like layers are stacked (`blocks.r<j>`
    # leaves `[P, n, ...]`) and scanned, one traced body a run; False:
    # `blocks.l<i>`, one body a layer
    stack_runs: bool = False
    n_kv_heads: int = 0             # 0: as many as n_heads
    head_dim: int = 0               # 0: d_model // n_heads
    rotary_fraction: float = 0.0    # part of a head that rotary turns
    rope_theta: float = 10000.0
    # the attention mixer's parts: a sigmoid output gate beside the query,
    # RMS norms of q and k over the head, the score scale (0: d_head^-0.5)
    attn_gate: bool = True
    qk_norm: bool = True
    attn_scale: float = 0.0
    # Mamba-2 (ops/mamba2_ssd.py): heads, their size, the state's size,
    # groups sharing B and C, the causal convolution's width
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_conv_width: int = 4
    # a typed layer's second half: "moe" (top-k experts and a gated shared
    # expert) or "swiglu" (one dense gated MLP of width dense_d_ff)
    mlp_kind: str = "moe"
    dense_d_ff: int = 0
    # multipliers: the embedding's rows by embed_scale, a mixer's and an
    # MLP's output by residual_scale before it joins the stream, the
    # logits divided by logits_scale
    embed_scale: float = 1.0
    residual_scale: float = 1.0
    logits_scale: float = 1.0
    # True: no Wout; the head is the embedding transposed, whose gradient
    # is the sum of the gather's and the head's (typed layers only)
    tie_head: bool = False
    gdn_key_heads: int = 0          # Gated DeltaNet: key / value heads,
    gdn_value_heads: int = 0        # their sizes and the causal
    gdn_key_dim: int = 0            # convolution's width
    gdn_value_dim: int = 0
    gdn_conv_width: int = 4
    # top-k MoE of the typed layers: n_experts is the router's width,
    # experts_held how many of them ([0, experts_held)) live here
    experts_held: int = 0
    moe_top_k: int = 1
    moe_d_ff: int = 0
    shared_d_ff: int = 0
    # False: the router's matrix is frozen (it gets no gradient, so Adam
    # leaves it where it is), as a fine-tune that keeps the routing does.
    # The hidden state still gets its gradient through the routing
    # weights: the mathematics of every other leaf is whole
    train_router: bool = True
    # how the router scores: "softmax" (over all experts, then the top_k)
    # or "sigmoid" (an expert's score its own; a bias a layer, a buffer
    # that no gradient reaches, enters the choice and not the weight);
    # the chosen experts' normalised weights times routed_scale; False:
    # the shared expert joins ungated (no Ws_gate)
    router_scoring: str = "softmax"
    routed_scale: float = 1.0
    shared_gate: bool = True
    # latent attention ("mla"): queries and keys/values through low-rank
    # latents with an RMSNorm each; a head's key is its own qk_nope_dim
    # and qk_rope_dim rotary dimensions that all heads share
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    # layers before the first period, counted in n_layers, outside the
    # scan: the period's first mixer and a dense SwiGLU of dense_d_ff
    lead_dense_layers: int = 0
    # multi-token prediction: one further layer fed by the final hidden
    # state and the next token's embedding; its loss, through the same
    # embedding and head, joins the main one times mtp_loss_weight
    mtp_layers: int = 0
    mtp_loss_weight: float = 0.0

    @property
    def d_head(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def d_ff(self) -> int:
        return self.d_model * self.mlp_ratio

    def activation_dtype(self):
        return {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
                "float64": jnp.float64}[self.dtype]

    def cache_jnp_dtype(self):
        """KV-cache storage dtype: `cache_dtype` when set, else the
        activation dtype (the pre-quantization default)."""
        if not self.cache_dtype:
            return self.activation_dtype()
        return {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
                "float64": jnp.float64}[self.cache_dtype]


def _winit(key, shape, fan_in):
    return (jax.random.normal(key, shape, jnp.float32)
            / jnp.sqrt(jnp.asarray(fan_in, jnp.float32)))


def init_params(cfg: TransformerConfig, key: Array) -> Dict[str, Any]:
    if cfg.layer_types:
        from deeplearning4j_tpu.models import layer_kinds
        return layer_kinds.init_params(cfg, key)
    d, f, L, v = cfg.d_model, cfg.d_ff, cfg.n_layers, cfg.vocab_size
    ks = jax.random.split(key, 12)

    def stack(k, shape, fan_in):
        keys = jax.random.split(k, L)
        return jnp.stack([_winit(keys[i], shape, fan_in) for i in range(L)])

    blocks: Dict[str, Array] = {
        "Wq": stack(ks[0], (d, d), d), "Wk": stack(ks[1], (d, d), d),
        "Wv": stack(ks[2], (d, d), d), "Wo": stack(ks[3], (d, d), d),
        "ln1g": jnp.ones((L, d)), "ln1b": jnp.zeros((L, d)),
        "ln2g": jnp.ones((L, d)), "ln2b": jnp.zeros((L, d)),
    }
    if cfg.n_experts > 0:
        e = cfg.n_experts
        ek = jax.random.split(ks[4], L)
        blocks["router"] = stack(ks[5], (d, e), d)
        blocks["We1"] = jnp.stack([
            jnp.stack([_winit(jax.random.fold_in(ek[i], j), (d, f), d)
                       for j in range(e)]) for i in range(L)])  # [L, E, D, F]
        blocks["We2"] = jnp.stack([
            jnp.stack([_winit(jax.random.fold_in(ek[i], e + j), (f, d), f)
                       for j in range(e)]) for i in range(L)])  # [L, E, F, D]
    else:
        blocks["W1"] = stack(ks[6], (d, f), d)
        blocks["b1"] = jnp.zeros((L, f))
        blocks["W2"] = stack(ks[7], (f, d), f)
        blocks["b2"] = jnp.zeros((L, d))
    return {
        "embed": jax.random.normal(ks[8], (v, d), jnp.float32) * 0.02,
        "pos": jax.random.normal(ks[9], (cfg.max_len, d), jnp.float32) * 0.02,
        "blocks": blocks,
        "lnfg": jnp.ones((d,)), "lnfb": jnp.zeros((d,)),
        "Wout": _winit(ks[10], (d, v), d),
    }


# ---------------------------------------------------------------------------
# block body — shared by the single-device forward and the parallel step
# ---------------------------------------------------------------------------

def dense_mlp(h: Array, p: Dict[str, Array]) -> Array:
    z = jnp.matmul(h, p["W1"].astype(h.dtype)) + p["b1"].astype(h.dtype)
    z = jax.nn.gelu(z)
    return jnp.matmul(z, p["W2"].astype(h.dtype)) + p["b2"].astype(h.dtype)


def moe_mlp(h: Array, p: Dict[str, Array], cfg: TransformerConfig) -> Array:
    """Top-1-routed mixture of experts (GShard-style dispatch/combine
    einsums; expert-parallel variant lives in parallel/megatron.py)."""
    b, t, d = h.shape
    x = h.reshape(b * t, d)
    n, e = x.shape[0], cfg.n_experts
    logits = jnp.matmul(x.astype(jnp.float32), p["router"])
    gates = jax.nn.softmax(logits, axis=-1)            # [N, E]
    expert = jnp.argmax(gates, axis=-1)                # [N]
    prob = jnp.take_along_axis(gates, expert[:, None], 1)[:, 0]
    cap = max(1, int(cfg.capacity_factor * n / e))
    onehot = jax.nn.one_hot(expert, e, dtype=jnp.float32)       # [N, E]
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1.0             # [N, E]
    keep = (pos >= 0) & (pos < cap)
    posc = jnp.clip(pos, 0, cap - 1).astype(jnp.int32)
    disp = (jax.nn.one_hot(posc, cap, dtype=jnp.float32)
            * keep[..., None].astype(jnp.float32)
            * onehot[..., None])                                 # [N, E, C]
    xin = jnp.einsum("nec,nd->ecd", disp, x.astype(jnp.float32))
    # .astype(f32) is a no-op on the float tree and the on-the-fly
    # dequantization on a quantized one (quant/core.QuantizedTensor)
    z = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", xin,
                               p["We1"].astype(jnp.float32)))
    out = jnp.einsum("ecf,efd->ecd", z,
                     p["We2"].astype(jnp.float32))               # [E, C, D]
    comb = disp * prob[:, None, None]
    y = jnp.einsum("nec,ecd->nd", comb, out)
    return y.astype(h.dtype).reshape(b, t, d)


def block_forward(h: Array, p: Dict[str, Array], cfg: TransformerConfig,
                  mask: Optional[Array] = None, return_kv: bool = False,
                  remat_mlp: bool = False):
    """One pre-LN transformer block on [B, T, D] (full, unsharded).
    ``return_kv`` additionally returns the block's K/V heads — the
    batched cache-prefill path for decoding. ``remat_mlp`` checkpoints
    just the MLP branch (the remat_policy='mlp' mode: the [B,T,4D]
    intermediate is recomputed in backward, attention residuals are
    kept)."""
    d = cfg.d_model

    def heads(y):
        return y.reshape(y.shape[0], y.shape[1], cfg.n_heads, cfg.d_head)

    with jax.named_scope("attn"):
        x = layer_norm(h, p["ln1g"], p["ln1b"], cfg.eps)
        q = heads(jnp.matmul(x, p["Wq"].astype(x.dtype)))
        k = heads(jnp.matmul(x, p["Wk"].astype(x.dtype)))
        v = heads(jnp.matmul(x, p["Wv"].astype(x.dtype)))
        a = dot_product_attention(q, k, v, causal=True, mask=mask)
        h = h + jnp.matmul(a.reshape(a.shape[0], a.shape[1], d),
                           p["Wo"].astype(h.dtype))
    if cfg.n_experts > 0:
        mlp = lambda xx, pp: moe_mlp(xx, pp, cfg)  # noqa: E731
    else:
        mlp = dense_mlp
    if remat_mlp:
        mlp = jax.checkpoint(mlp, prevent_cse=False)
    with jax.named_scope("mlp"):
        x = layer_norm(h, p["ln2g"], p["ln2b"], cfg.eps)
        h = h + mlp(x, p)
    if return_kv:
        return h, (k, v)
    return h


def forward_hidden(cfg: TransformerConfig, params: Dict[str, Any],
                   tokens: Array) -> Array:
    """tokens [B, T] int32 -> final-LN hidden states [B, T, D] (the
    pre-output-projection activations; loss_fn consumes these directly
    so the chunked cross-entropy can fuse the D->V projection into its
    vocab-panel scan)."""
    dt = cfg.activation_dtype()
    t = tokens.shape[1]
    if cfg.layer_types:
        return _forward_hidden_typed(cfg, params, tokens)
    with jax.named_scope("embed"):
        h = (params["embed"].astype(dt)[tokens]
             + params["pos"].astype(dt)[:t][None])

    if cfg.remat and cfg.remat_policy not in ("full", "dots", "mlp"):
        raise ValueError(f"unknown remat_policy {cfg.remat_policy!r}: "
                         "expected 'full', 'dots' or 'mlp'")
    remat_mlp = cfg.remat and cfg.remat_policy == "mlp"

    def block(h, p):
        return block_forward(h, p, cfg, remat_mlp=remat_mlp)

    if not remat_mlp:
        # prevent_cse=False: under lax.scan the loop structure already
        # prevents the CSE the default barrier guards against
        block = remat_layer(block, cfg, site="transformer.forward_hidden",
                            prevent_cse=False)
    h, _ = lax.scan(lambda h, p: (block(h, p), None), h, params["blocks"])
    return final_norm(cfg, params, h)


def final_norm(cfg: TransformerConfig, params: Dict[str, Any],
               h: Array) -> Array:
    """The norm before the head: RMSNorm `(1 + w)` with `layer_types`,
    else the GPT-2 block's LayerNorm."""
    if cfg.layer_types:
        from deeplearning4j_tpu.models.layer_kinds import rms_norm
        return rms_norm(h, params["lnfg"], cfg.eps)
    return layer_norm(h, params["lnfg"], params["lnfb"], cfg.eps)


def head_matrix(cfg: TransformerConfig, params: Dict[str, Any]) -> Array:
    """The output projection `[D, V]`: `Wout`, or with `cfg.tie_head` the
    embedding transposed."""
    return params["embed"].T if cfg.tie_head else params["Wout"]


def scaled_logits(cfg: TransformerConfig, logits: Array) -> Array:
    """float32 logits over `cfg.logits_scale`."""
    logits = logits.astype(jnp.float32)
    if cfg.logits_scale != 1.0:
        logits = logits / cfg.logits_scale
    return logits


def embed_tokens(cfg: TransformerConfig, params: Dict[str, Any],
                 tokens: Array) -> Array:
    h = params["embed"].astype(cfg.activation_dtype())[tokens]
    if cfg.embed_scale != 1.0:
        h = h * cfg.embed_scale
    return h


def _forward_hidden_typed(cfg: TransformerConfig, params: Dict[str, Any],
                          tokens: Array) -> Array:
    """forward_hidden for a config with `layer_types`: no positions are
    added (the full layers rotate or go without, the others recur), the
    scan is over periods, remat is a layer's (models/remat.py)."""
    from deeplearning4j_tpu.models import layer_kinds
    with jax.named_scope("embed"):
        h = embed_tokens(cfg, params, tokens)
    if cfg.lead_dense_layers:
        h = layer_kinds.lead_forward(h, params["lead"], cfg)
    h = layer_kinds.periods_forward(h, params["blocks"], cfg)
    return final_norm(cfg, params, h)


def forward(cfg: TransformerConfig, params: Dict[str, Any],
            tokens: Array) -> Array:
    """tokens [B, T] int32 -> logits [B, T, V]."""
    h = forward_hidden(cfg, params, tokens)
    logits = jnp.matmul(h, head_matrix(cfg, params).astype(h.dtype))
    if cfg.logits_scale != 1.0:
        logits = scaled_logits(cfg, logits).astype(h.dtype)
    return logits


# ---------------------------------------------------------------------------
# KV-cache decoding — the rnnTimeStep analog for the flagship family
# (reference capability: MultiLayerNetwork.rnnTimeStep:2234 streams RNN
# state; here the streamed state is the per-layer KV cache, static-shaped
# for XLA: one compiled step regardless of position)
# ---------------------------------------------------------------------------

def slot_cache_shape(cfg: TransformerConfig, num_slots: int,
                     max_len: Optional[int] = None
                     ) -> Tuple[int, int, int, int]:
    """Canonical slot-pool KV-cache geometry [L, num_slots, S, D] —
    init_cache's batch axis generalized to a PERSISTENT slot axis:
    continuous batching (serving/engine.py, parallel/serving.py
    init_slot_state) keeps one such buffer pair resident on device
    across decode chunks, admitting requests into and freeing slot
    rows while the buffer never changes shape — no reallocation, no
    recompile. Heads stay FLATTENED (D = H*Dh) for the same tiling
    reasons as init_cache (the serving mesh additionally shards the
    slot axis over 'data' and D over 'model')."""
    return (cfg.n_layers, num_slots, max_len or cfg.max_len,
            cfg.d_model)


def page_pool_shape(cfg: TransformerConfig, num_pages: int,
                    page_size: int) -> Tuple[int, int, int, int]:
    """Canonical PAGED KV-pool geometry [L, num_pages, page_size, D]:
    slot_cache_shape's per-slot [S] budget rows refactored into a
    shared pool of page_size-token pages addressed through per-slot
    block tables (parallel/serving.py paged section). Heads stay
    flattened (D = H*Dh) for the same tiling reasons; physical page 0
    is the reserved scratch page masked writes are routed to."""
    return (cfg.n_layers, num_pages, page_size, cfg.d_model)


def init_cache(cfg: TransformerConfig, batch: int,
               max_len: Optional[int] = None,
               cache_dtype=None) -> Tuple[Array, Array]:
    """Stacked per-layer KV caches [L, B, S, D] (k, v) — heads kept
    FLATTENED in the cache (D = H*Dh): the minor-most dims are then
    (S-tile, D=lane-full), a clean 2D tiling for the per-position
    dynamic_update_slice; views reshape to heads at the attention.

    ``cache_dtype`` (a jnp dtype) overrides `cfg.cache_dtype` for this
    allocation — the explicit passthrough for bf16 caches under f32
    activations (writes cast on store via `.astype(cache.dtype)`, the
    attention promotes reads back)."""
    shape = slot_cache_shape(cfg, batch, max_len)
    dt = cache_dtype if cache_dtype is not None else cfg.cache_jnp_dtype()
    return jnp.zeros(shape, dt), jnp.zeros(shape, dt)


def _block_decode(h: Array, p: Dict[str, Array], ck_all: Array,
                  cv_all: Array, layer: int, pos: Array,
                  cfg: TransformerConfig) -> Tuple[Array, Array, Array]:
    """One block, one new position: h [B, 1, D]; stacked caches
    [L, B, S, D] (heads FLATTENED — see init_cache). The new K/V row
    is written in place at (layer, :, pos) — a [1, B, 1, D] update,
    NOT a rewrite of the layer's cache (the carry through the sampling
    scan aliases the buffer, so per-step HBM write traffic is one
    position per layer; restacking whole caches through a layer scan
    was the decode bandwidth bottleneck, and the old per-head 5-D
    layout hit a 369 ms/step XLA tiling pathology at
    (S=2048, B=64/96) — BASELINE.md round-3 notes)."""
    d = cfg.d_model
    x = layer_norm(h, p["ln1g"], p["ln1b"], cfg.eps)

    def heads(y):
        return y.reshape(y.shape[0], 1, cfg.n_heads, cfg.d_head)

    q = heads(jnp.matmul(x, p["Wq"].astype(x.dtype)))
    k = jnp.matmul(x, p["Wk"].astype(x.dtype))        # [B, 1, D] flat
    v = jnp.matmul(x, p["Wv"].astype(x.dtype))
    z = jnp.asarray(0, pos.dtype)
    lz = jnp.asarray(layer, pos.dtype)
    ck_all = jax.lax.dynamic_update_slice(
        ck_all, k[None].astype(ck_all.dtype), (lz, z, pos, z))
    cv_all = jax.lax.dynamic_update_slice(
        cv_all, v[None].astype(cv_all.dtype), (lz, z, pos, z))
    # the single query attends the filled cache prefix 0..pos through
    # the decode-attention dispatcher (ops/flash_decode.py): on TPU the
    # split-K Pallas kernel reads only ceil((pos+1)/block) of the cache
    # from HBM per step (the round-3 jnp path read all of max_len every
    # step — the 5x-off-roofline finding, VERDICT r3 #2); elsewhere the
    # jnp reference path with identical masking semantics
    from deeplearning4j_tpu.ops.flash_decode import decode_attention
    a = decode_attention(q[:, 0], ck_all, cv_all, pos,
                         n_heads=cfg.n_heads, layer=layer)  # [B, H, Dh]
    h = h + jnp.matmul(a.reshape(a.shape[0], 1, d),
                       p["Wo"].astype(h.dtype))
    x = layer_norm(h, p["ln2g"], p["ln2b"], cfg.eps)
    if cfg.n_experts > 0:
        h = h + moe_mlp(x, p, cfg)
    else:
        h = h + dense_mlp(x, p)
    return h, ck_all, cv_all


def _decode_step_impl(cfg: TransformerConfig, params: Dict[str, Any],
                      token: Array, caches: Tuple[Array, Array],
                      pos: Array) -> Tuple[Array, Tuple[Array, Array]]:
    dt = cfg.activation_dtype()
    # embed + positional row at pos
    emb = params["embed"].astype(dt)[token]                      # [B, D]
    posv = jax.lax.dynamic_slice_in_dim(params["pos"], pos, 1,
                                        axis=0).astype(dt)       # [1, D]
    h = (emb + posv)[:, None, :]                                 # [B, 1, D]
    ck_all, cv_all = caches
    for layer in range(cfg.n_layers):
        p_l = {k: v[layer] for k, v in params["blocks"].items()}
        h, ck_all, cv_all = _block_decode(h, p_l, ck_all, cv_all, layer,
                                          pos, cfg)
    h = layer_norm(h, params["lnfg"], params["lnfb"], cfg.eps)
    logits = jnp.matmul(h[:, 0], params["Wout"].astype(h.dtype))
    return logits, (ck_all, cv_all)


@_ft.lru_cache(maxsize=64)
def _decode_step_jit(cfg: TransformerConfig, donate: bool = True):
    kwargs = {"donate_argnums": (2,)} if donate else {}
    return jax.jit(_ft.partial(_decode_step_impl, cfg), **kwargs)


def decode_step(cfg: TransformerConfig, params: Dict[str, Any],
                token: Array, caches: Tuple[Array, Array], pos: Array,
                donate: bool = True
                ) -> Tuple[Array, Tuple[Array, Array]]:
    """token [B] int32 at position ``pos`` -> (logits [B, V], caches).

    The layer loop is unrolled (static layer indices) so cache updates
    stay single-position dynamic_update_slices on the stacked buffers —
    and by default the step runs JITTED with the caches DONATED, so
    eager callers (the rnnTimeStep-style streaming loop) get in-place
    cache updates rather than 2L whole-cache copies. Donation
    INVALIDATES the passed-in cache buffers: pass the returned caches
    to the next call and never reuse the old ones. Branching decode
    (several continuations from one prefill cache) must call with
    ``donate=False``, which keeps the input caches intact at the cost
    of a cache copy per step."""
    return _decode_step_jit(cfg, donate)(params, jnp.asarray(token),
                                         caches,
                                         jnp.asarray(pos, jnp.int32))


def prefill(cfg: TransformerConfig, params: Dict[str, Any],
            prompt: Array) -> Tuple[Array, Tuple[Array, Array]]:
    """ONE batched pass over the prompt: last-position logits + filled
    KV caches (O(T0^2) parallel work instead of T0 sequential decode
    steps)."""
    dt = cfg.activation_dtype()
    b, t0 = prompt.shape
    h = (params["embed"].astype(dt)[prompt]
         + params["pos"].astype(dt)[:t0][None])

    def body(h, p):
        return block_forward(h, p, cfg, return_kv=True)

    h, (ks, vs) = lax.scan(body, h, params["blocks"])  # [L, B, T0, H, Dh]
    ck, cv = init_cache(cfg, b)
    lf = (cfg.n_layers, b, t0, cfg.d_model)            # flatten heads
    ck = ck.at[:, :, :t0].set(ks.reshape(lf).astype(ck.dtype))
    cv = cv.at[:, :, :t0].set(vs.reshape(lf).astype(cv.dtype))
    h = layer_norm(h, params["lnfg"], params["lnfb"], cfg.eps)
    last_logits = jnp.matmul(h[:, -1], params["Wout"].astype(h.dtype))
    return last_logits, (ck, cv)


def _filter_logits(logits: Array, top_k: int, top_p: float) -> Array:
    """Standard LM sampling filters on [B, V] f32 logits: keep the
    top_k highest-scoring tokens (0 = off) and/or the smallest prefix
    of the probability-sorted vocab whose cumulative mass reaches
    top_p (1.0 = off; the top-1 token always survives). Filtered
    entries drop to -inf before the categorical draw. ONE descending
    sort serves both filters (this runs inside every decode step of
    the sampling scan — a second full-vocab sort there is pure waste)."""
    v = logits.shape[-1]
    use_k = bool(top_k) and top_k < v
    use_p = top_p < 1.0
    if not (use_k or use_p):
        return logits
    sorted_l = jnp.sort(logits, axis=-1)[:, ::-1]           # desc
    if use_k:
        kth = sorted_l[:, top_k - 1][:, None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if use_p:
        if use_k:   # mask the same tail in the sorted view
            idx = jnp.arange(v)[None, :]
            sorted_l = jnp.where(idx >= top_k, -jnp.inf, sorted_l)
        probs = jax.nn.softmax(sorted_l, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # number of kept tokens = first index where cum >= top_p, +1
        keep_n = jnp.sum((cum - probs) < top_p, axis=-1,
                         keepdims=True)                     # >= 1
        cutoff = jnp.take_along_axis(sorted_l, keep_n - 1, axis=-1)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return logits


def sample_at_positions(logits: Array, posidx: Array, key,
                        temperature: float, top_k: int,
                        top_p: float) -> Array:
    """POSITION-KEYED sampling on [N, V] logits: row i draws from
    ``fold_in(key, posidx[i])`` after the standard temperature /
    top-k / top-p filters (greedy ignores the key entirely). The token
    at sequence index j is a deterministic function of (key, j, the
    logits at j) — independent of batch/slot placement, chunk
    boundaries, or HOW MANY positions are scored per call — which is
    what makes retries, solo isolation, preempt-resume, and
    speculative verify-then-commit reproduce continuations exactly:
    the serving decode paths (parallel/serving._sample_slots) and the
    speculative verify pass (which scores K+1 positions at once and
    must emit the very tokens sequential decode would) all sample
    through this one function."""
    if temperature <= 0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    filt = _filter_logits(logits.astype(jnp.float32) / temperature,
                          top_k, top_p)
    keys = jax.vmap(lambda j: jax.random.fold_in(key, j))(
        posidx.astype(jnp.int32))
    return jax.vmap(jax.random.categorical)(keys, filt) \
        .astype(jnp.int32)


@_ft.lru_cache(maxsize=64)
def _generate_jit(cfg: TransformerConfig, max_new_tokens: int,
                  temperature: float, top_k: int = 0,
                  top_p: float = 1.0):
    """One compiled prefill+sample program per (cfg, length, temp,
    top_k, top_p) — jax.jit caches by function identity, so the
    closure must be reused across generate() calls."""

    def run(params, prompt, key):
        last_logits, caches = prefill(cfg, params, prompt)
        pos = jnp.asarray(prompt.shape[1], jnp.int32)

        def sample(carry, i):
            caches, pos, logits = carry
            if temperature <= 0:
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            else:
                # per-step key FOLDED inside the body rather than a
                # pre-split key array scanned as xs: greedy then
                # traces zero threefry work and the scan xs stay a
                # plain int32 arange
                filt = _filter_logits(
                    logits.astype(jnp.float32) / temperature,
                    top_k, top_p)
                tok = jax.random.categorical(
                    jax.random.fold_in(key, i), filt, axis=-1
                ).astype(jnp.int32)
            new_logits, caches = _decode_step_impl(cfg, params, tok,
                                                   caches, pos)
            return (caches, pos + 1, new_logits), tok

        _, toks = lax.scan(sample, (caches, pos, last_logits),
                           jnp.arange(max_new_tokens, dtype=jnp.int32))
        return jnp.concatenate([prompt, jnp.swapaxes(toks, 0, 1)], axis=1)

    return jax.jit(run)


def generate(cfg: TransformerConfig, params: Dict[str, Any], prompt: Array,
             max_new_tokens: int, key: Array,
             temperature: float = 1.0, top_k: int = 0,
             top_p: float = 1.0) -> Array:
    """Autoregressive sampling with a KV cache, ONE compiled program:
    batched prefill fills the cache, then the sampling loop scans
    max_new_tokens cached decode steps. temperature<=0 means greedy
    argmax; top_k>0 keeps only the k most likely tokens and
    top_p<1.0 applies nucleus filtering (both composable, applied
    after temperature). Returns [B, T0 + max_new_tokens]."""
    prompt = jnp.asarray(prompt, jnp.int32)
    total = prompt.shape[1] + max_new_tokens
    if total > cfg.max_len:
        raise ValueError(f"generation length {total} exceeds "
                         f"max_len={cfg.max_len}")
    if not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    if top_k < 0:
        raise ValueError(f"top_k must be >= 0, got {top_k}")
    run = _generate_jit(cfg, int(max_new_tokens), float(temperature),
                        int(top_k), float(top_p))
    return run(params, prompt, key)


def chunked_cross_entropy(h: Array, wout: Array, targets: Array,
                          chunk: int) -> Array:
    """Streaming softmax cross-entropy: mean NLL of ``targets`` under
    ``softmax(h @ wout)`` WITHOUT materializing the [B, T, V] logits.

    The vocab axis is split into V/chunk panels and scanned with an
    online logsumexp (running max ``m``, rescaled sum ``s`` — the same
    streaming-softmax recurrence flash attention uses along T, applied
    along V), picking up the target logit from whichever panel contains
    it. Live memory is one [B*T, chunk] f32 panel; the scan body is
    jax.checkpoint'ed so reverse-mode recomputes each panel instead of
    saving all of them (which would rebuild the full logits tensor as
    residuals). Role analog: the reference's output-layer score path
    (BaseOutputLayer.java computeScore) materializes full preOutput —
    affordable at its vocabularies, not at a 32k-vocab LM batch.
    """
    d, v = wout.shape
    if v % chunk != 0:
        raise ValueError(f"vocab {v} not divisible by xent_chunk {chunk}")
    n_chunks = v // chunk
    x = h.reshape(-1, d)
    y = targets.reshape(-1).astype(jnp.int32)
    n = x.shape[0]
    # [D, V] -> [nC, D, C] panel stack (panel i holds cols [i*C, (i+1)*C))
    wc = jnp.moveaxis(wout.reshape(d, n_chunks, chunk), 1, 0)

    def body(carry, inp):
        m, s, tl = carry
        w_i, c0 = inp
        # match the dense path's arithmetic: matmul in the activation
        # dtype (bf16 on TPU, f32 accumulation on the MXU), then f32
        logits = jnp.matmul(x, w_i.astype(x.dtype)).astype(jnp.float32)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        s = (s * jnp.exp(m - m_new)
             + jnp.sum(jnp.exp(logits - m_new[:, None]), axis=-1))
        local = y - c0
        hit = (local >= 0) & (local < chunk)
        g = jnp.take_along_axis(
            logits, jnp.clip(local, 0, chunk - 1)[:, None], axis=1)[:, 0]
        return (m_new, s, jnp.where(hit, g, tl)), None

    init = (jnp.full((n,), -jnp.inf, jnp.float32),
            jnp.zeros((n,), jnp.float32), jnp.zeros((n,), jnp.float32))
    offsets = (jnp.arange(n_chunks, dtype=jnp.int32) * chunk)
    (m, s, tl), _ = lax.scan(
        jax.checkpoint(body, prevent_cse=False), init, (wc, offsets))
    return jnp.mean(m + jnp.log(s) - tl)


def scaled_hidden(cfg: TransformerConfig, h: Array) -> Array:
    """The chunked loss never holds the logits, so there the final
    hidden state is divided by `cfg.logits_scale` instead."""
    return h if cfg.logits_scale == 1.0 else h / cfg.logits_scale


def nll_sum(cfg: TransformerConfig, wout: Array, hf: Array,
            targets: Array) -> Array:
    """Summed negative log-likelihood of `targets` [B, T] under the head
    `wout` [D, V] on normed hidden states `hf` [B, T, D]: the dense
    float32 logits, or with `cfg.xent_chunk` the streaming vocab-panel
    scan (`chunked_cross_entropy`), which never holds them."""
    if cfg.xent_chunk > 0 and cfg.vocab_size > cfg.xent_chunk:
        return chunked_cross_entropy(scaled_hidden(cfg, hf), wout, targets,
                                     cfg.xent_chunk) * targets.size
    logits = jnp.matmul(hf, wout.astype(hf.dtype))
    logp = jax.nn.log_softmax(scaled_logits(cfg, logits), axis=-1)
    return -jnp.sum(jnp.take_along_axis(
        logp, targets[..., None].astype(jnp.int32), axis=-1))


def head_loss_sum(cfg: TransformerConfig, params: Dict[str, Any],
                  hf: Array, targets: Array) -> Array:
    """What a block of rows adds to the loss's sum, from its final-normed
    hidden states `hf` [B, T, D]: the loss is this over every row's
    B x T. The single-device `loss_fn` and the parallel step's
    (parallel/megatron.py) both are this. With `cfg.mtp_layers` the
    multi-token-prediction module's term is in it: position i, fed
    ``hf[i]`` and the embedding of ``targets[i]`` (token i + 1), predicts
    ``targets[i + 1]`` through the same embedding and head; a row's last
    position has no such term, and the mean over the T - 1 that have
    joins times `cfg.mtp_loss_weight`."""
    wout = head_matrix(cfg, params)
    with jax.named_scope("head_loss"):
        total = nll_sum(cfg, wout, hf, targets)
    if cfg.mtp_layers:
        from deeplearning4j_tpu.models.layer_kinds import mtp_hidden
        t = targets.shape[1]
        with jax.named_scope("mtp"):
            u = mtp_hidden(hf, embed_tokens(cfg, params, targets),
                           params["mtp"], cfg)
        with jax.named_scope("mtp.head_loss"):
            total = total + (cfg.mtp_loss_weight * t / (t - 1)) * nll_sum(
                cfg, wout, u[:, :-1], targets[:, 1:])
    return total


def loss_fn(cfg: TransformerConfig, params: Dict[str, Any], tokens: Array,
            targets: Array) -> Array:
    h = forward_hidden(cfg, params, tokens)
    return head_loss_sum(cfg, params, h, targets) / targets.size


class TransformerLM:
    """Thin stateful wrapper matching the framework's model surface
    (init/fit-style usage goes through parallel/megatron.py's train step or
    a user loop; this class covers single-chip use and the graft entry)."""

    def __init__(self, cfg: TransformerConfig, seed: int = 0):
        self.cfg = cfg
        self.params = init_params(cfg, jax.random.PRNGKey(seed))
        self._fwd = jax.jit(lambda p, t: forward(cfg, p, t))

    def logits(self, tokens) -> Array:
        return self._fwd(self.params, jnp.asarray(tokens))

    def loss(self, tokens, targets) -> float:
        return float(loss_fn(self.cfg, self.params, jnp.asarray(tokens),
                             jnp.asarray(targets)))

    def generate(self, prompt, max_new_tokens: int, *,
                 temperature: float = 1.0, top_k: int = 0,
                 top_p: float = 1.0, seed: int = 0) -> Array:
        """KV-cached autoregressive sampling (the rnnTimeStep-streaming
        analog for this family); greedy / temperature / top-k /
        nucleus — see models.transformer.generate."""
        return generate(self.cfg, self.params, prompt, max_new_tokens,
                        jax.random.PRNGKey(seed), temperature,
                        top_k=top_k, top_p=top_p)
