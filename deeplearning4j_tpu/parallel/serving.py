"""Tensor+data-parallel KV-cache generation — sharded serving.

NET-NEW vs the reference (its serving story is single-process
`MultiLayerNetwork.output`/`rnnTimeStep`; SURVEY §5.7-5.8): the flagship
transformer's autoregressive decode runs SPMD over a `('data',
'model')` mesh. Megatron-style tensor parallelism splits the attention
heads and MLP hidden dim over 'model' (reusing parallel/megatron.py's
param_specs/shard_params layout, pipe=1), the batch splits over 'data',
and each device holds only its head-shard of the KV cache —
[L, B/dp, S, D/tp] in the flattened-head layout models/transformer.py
uses (round-3 decode tiling fix). Per decode step the only collective
is the attention/MLP output psum over 'model' (g-sync), after which
every model-rank holds identical full logits and samples the same
token from the same per-step key — no gather of the cache, ever.

MoE configs (n_experts > 0) serve via EXPERT-TENSOR parallelism
(VERDICT r3 #4): every rank holds all experts, but each expert's FFN
hidden dim is sharded over 'model' exactly like the dense MLP — the
right layout for serving-scale expert counts, where routing all-to-all
over a dedicated expert axis would add a collective per layer per
token for no memory win. Routing is computed per data shard, but the
capacity DROP decision is made against the GLOBAL token order (an
all_gather of per-expert counts over 'data' supplies each rank's
prefix offsets), so a token is dropped on the mesh iff single-chip
moe_mlp would drop it — without that, capacity binds differently at
B/dp tokens per rank and greedy decode diverges from the single-chip
reference.

Sampling carries the full single-chip surface — temperature, top-k,
nucleus (top-p) — via the SAME `_filter_logits` the single-chip scan
uses (r4 gap: serving silently sampled raw logits, VERDICT r4 weak #5).
On a TP-only mesh (dp=1) the per-step key derivation matches
single-chip `generate` exactly, so sampled decode is token-for-token
equivalent too, not just greedy; with dp>1 each data rank folds its
rank index into the key (equal prompts on different ranks must not
sample identical continuations). Equivalence tests:
tests/test_parallel_serving.py — greedy (dense AND MoE) + sampled
top-k/top-p.

CONTINUOUS BATCHING (ISSUE-4): `make_parallel_generate` fuses prefill
and the whole decode budget into one program — right for one batch run
to completion, wrong for mixed, streaming traffic (the engine would
re-run prefill over the grown sequence every chunk). The split surface
below serves the slotted engine instead:

- `init_slot_state(cfg, mesh, num_slots)` — a PERSISTENT pool of
  `num_slots` KV-cache rows ([L, Ns, S, D] sharded batch-over-'data',
  flattened heads over-'model') plus per-slot `pos`/`tok` vectors,
  resident on device across chunk calls.
- `make_continuous_prefill(cfg, mesh, bucket_len, num_slots, ...)` —
  one FIXED-SHAPE program per (bucket_len, num_slots) that prefills
  any subset of slots (`plen > 0` marks admissions) from prompts
  right-padded to the bucket, writes their cache rows, and samples
  each admitted slot's first token. Mixed prompt lengths share the
  program: causal attention means padded positions never influence
  valid ones, the last-token logits are gathered at `plen-1` per row,
  and (for MoE) padded tokens are masked out of expert dispatch.
- `make_continuous_decode(cfg, mesh, chunk, num_slots, ...)` — one
  fixed-shape program per (chunk, num_slots) advancing every active
  slot `chunk` tokens: per-slot cache-row writes at each slot's own
  `pos`, attention masked to each slot's filled prefix, slots
  deactivating themselves when their remaining-token budget hits 0
  (no wasted writes for finished slots). `active`/`rem` are data, not
  shapes — steady-state mixed traffic triggers ZERO recompiles.

Sampling key schedule for the split path: the token generated at
sequence index j uses fold_in(root_key, j) (per-slot vmapped), so a
retried, solo-isolated, or preempted-and-resumed request reproduces
its continuation exactly — the schedule depends on absolute position
only, never on slot placement or chunk boundaries. (This differs from
the fused path's chunk-shaped schedule; greedy decode is identical.)

CHUNKED PREFILL (ISSUE-10): `make_continuous_prefill` runs a whole
admission's prompt as ONE fused pass, so a single long prompt freezes
every co-resident decoding slot for the full prefill — the TPOT-p99
stall the engine's token-budget scheduler exists to bound.
`make_chunked_prefill` (contiguous pool) and
`make_paged_chunked_prefill` (paged pool) instead advance any subset
of MID-PREFILL slots by up to `chunk_len` prompt tokens per call:
ONE fixed-shape program per (chunk_len, num_slots[, page geometry])
whose per-slot resume position (`start`), valid-token count (`clen`,
partial chunks allowed — the scheduler spends its budget to the
token), and final-chunk flag (`last`) are all runtime data. Each call
writes the chunk's K/V rows at absolute positions start+t and attends
two pieces — the already-written cached prefix masked to s < start,
plus causal float self-attention within the chunk — which is exactly
the paged prefix-hit resume path generalized to ARBITRARY chunk
boundaries (start no longer has to be a prefix-cache page boundary).
When `last` is set the call samples the slot's first generated token
at sequence index start+clen through the same position-keyed schedule
one-shot prefill uses, so chunked prefill is TOKEN-EXACT vs one-shot:
chunk 1's causal self-attention reproduces the one-shot math for its
positions, and every later chunk reads back the identical cached rows
chunk k-1 wrote (float KV bit-for-bit; int8 KV re-reads the prefix
through its quantization exactly as decode does — the same envelope
the paged prefix-hit path documents). tests/test_serving_chunked.py
holds the float/int8, fresh/prefix-hit, greedy/sampled proofs.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                   _filter_logits,
                                                   sample_at_positions)
from deeplearning4j_tpu.nn.layers.attention import (dot_product_attention,
                                                    layer_norm)
from deeplearning4j_tpu.parallel.megatron import (_g_sync, param_specs,
                                                  shard_params)

Array = jax.Array


def _local_moe_mlp(x2, p, cfg: TransformerConfig, dp: int, valid=None):
    """Top-1 MoE on this data shard's tokens x2 [N_loc, D] with
    model-sharded expert FFNs (We1 [E, D, F/tp], We2 [E, F/tp, D]) —
    returns the PARTIAL output (caller psums over 'model').

    Mirrors models/transformer.moe_mlp token for token: the capacity
    cap uses the GLOBAL token count (dp * N_loc) and the keep decision
    uses each token's GLOBAL dispatch position — local cumsum plus a
    prefix of lower ranks' per-expert counts (all_gather over 'data').
    Local buffer slots then only need to be collision-free, so kept
    tokens re-rank locally; dispatch/combine read the same slots, so
    the combined output is exactly the single-chip one for every kept
    token and 0 for dropped ones.

    ``valid`` ([N_loc] bool, continuous-batching bucket prefill): pad
    tokens are masked out of dispatch so they can never claim expert
    capacity from real tokens. The cap itself stays computed from the
    PADDED token count (it sizes static buffers), so a bucket-padded
    MoE prefill can drop fewer tokens than an exact-length run —
    documented divergence, docs/serving.md."""
    n_loc = x2.shape[0]
    e = cfg.n_experts
    logits = jnp.matmul(x2.astype(jnp.float32), p["router"])
    gates = jax.nn.softmax(logits, axis=-1)
    expert = jnp.argmax(gates, axis=-1)
    prob = jnp.take_along_axis(gates, expert[:, None], 1)[:, 0]
    cap = max(1, int(cfg.capacity_factor * n_loc * dp / e))
    onehot = jax.nn.one_hot(expert, e, dtype=jnp.float32)       # [N, E]
    if valid is not None:
        onehot = onehot * valid.astype(jnp.float32)[:, None]
    counts = jnp.sum(onehot, axis=0)                            # [E]
    all_counts = lax.all_gather(counts, "data")                 # [dp, E]
    r = lax.axis_index("data")
    prefix = jnp.sum(
        jnp.where(jnp.arange(dp)[:, None] < r, all_counts, 0.0),
        axis=0)                                                 # [E]
    pos_g = (jnp.cumsum(onehot, axis=0) + prefix[None, :]) * onehot \
        - 1.0
    keep = (pos_g >= 0) & (pos_g < cap)
    keep_oh = onehot * keep.astype(jnp.float32)
    cap_loc = max(1, min(cap, n_loc))
    pos_l = jnp.cumsum(keep_oh, axis=0) * keep_oh - 1.0
    posc = jnp.clip(pos_l, 0, cap_loc - 1).astype(jnp.int32)
    disp = (jax.nn.one_hot(posc, cap_loc, dtype=jnp.float32)
            * keep_oh[..., None])                               # [N,E,C]
    xin = jnp.einsum("nec,nd->ecd", disp, x2.astype(jnp.float32))
    # .astype(f32): identity on float trees, on-the-fly dequantization
    # on quantized ones (quant/core.QuantizedTensor)
    z = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", xin,
                               p["We1"].astype(jnp.float32)))
    out = jnp.einsum("ecf,efd->ecd", z,
                     p["We2"].astype(jnp.float32))  # partial over tp
    comb = disp * prob[:, None, None]
    return jnp.einsum("nec,ecd->nd", comb, out).astype(x2.dtype)


def _local_mlp(h, x, p, cfg: TransformerConfig, dp: int, g_model,
               valid=None):
    """Shared MLP tail for prefill/decode blocks: dense TP or MoE
    expert-tensor-parallel, partial-output psum'd over 'model'.
    ``valid`` ([B, T] bool) masks pad tokens out of MoE dispatch."""
    if cfg.n_experts > 0:
        b, t, d = x.shape
        y = _local_moe_mlp(x.reshape(b * t, d), p, cfg, dp,
                           valid=None if valid is None
                           else valid.reshape(b * t))
        return h + g_model(y.reshape(b, t, d))
    z = jax.nn.gelu(jnp.matmul(x, p["W1"].astype(x.dtype))
                    + p["b1"].astype(x.dtype))
    m = g_model(jnp.matmul(z, p["W2"].astype(z.dtype)))
    return h + m + p["b2"].astype(h.dtype)


def _local_block_prefill(h, p, cfg: TransformerConfig, tp: int,
                         dp: int, valid=None):
    """TP block forward over the full prompt, returning the block's
    LOCAL k/v rows (flattened local heads) for the cache.

    ``valid`` ([B, T] bool) marks real (non-pad) tokens in a bucket-
    padded continuous-batching prefill; causal attention already keeps
    pad positions (always to the RIGHT of valid ones) from influencing
    valid outputs, so the mask is only consumed by MoE dispatch.

    NOTE: this and _local_block_decode deliberately mirror
    models/transformer.block_forward/_block_decode and
    megatron._block_fwd_sharded with local head counts + the 'model'
    output psum; any change to the block math must land in all of
    them — tests/test_parallel_serving.py's token-for-token greedy
    equivalence is the guard that catches drift."""
    g_model = _g_sync("model")
    h_loc = cfg.n_heads // tp
    x = layer_norm(h, p["ln1g"], p["ln1b"], cfg.eps)

    def heads(y):
        return y.reshape(y.shape[0], y.shape[1], h_loc, cfg.d_head)

    q = heads(jnp.matmul(x, p["Wq"].astype(x.dtype)))
    k = heads(jnp.matmul(x, p["Wk"].astype(x.dtype)))
    v = heads(jnp.matmul(x, p["Wv"].astype(x.dtype)))
    a = dot_product_attention(q, k, v, causal=True)
    a = a.reshape(a.shape[0], a.shape[1], h_loc * cfg.d_head)
    h = h + g_model(jnp.matmul(a, p["Wo"].astype(a.dtype)))
    x = layer_norm(h, p["ln2g"], p["ln2b"], cfg.eps)
    h = _local_mlp(h, x, p, cfg, dp, g_model, valid=valid)
    kf = k.reshape(k.shape[0], k.shape[1], h_loc * cfg.d_head)
    vf = v.reshape(v.shape[0], v.shape[1], h_loc * cfg.d_head)
    return h, (kf, vf)


def _local_block_decode(h, p, ck_all, cv_all, layer: int, pos,
                        cfg: TransformerConfig, tp: int, dp: int):
    """One TP block, one new position, local-head cache update +
    attention over the local cache shard."""
    g_model = _g_sync("model")
    h_loc = cfg.n_heads // tp
    d_loc = h_loc * cfg.d_head
    x = layer_norm(h, p["ln1g"], p["ln1b"], cfg.eps)
    q = jnp.matmul(x, p["Wq"].astype(x.dtype)) \
        .reshape(x.shape[0], 1, h_loc, cfg.d_head)
    k = jnp.matmul(x, p["Wk"].astype(x.dtype))      # [B, 1, D_loc]
    v = jnp.matmul(x, p["Wv"].astype(x.dtype))
    z = jnp.asarray(0, pos.dtype)
    lz = jnp.asarray(layer, pos.dtype)
    ck_all = lax.dynamic_update_slice(
        ck_all, k[None].astype(ck_all.dtype), (lz, z, pos, z))
    cv_all = lax.dynamic_update_slice(
        cv_all, v[None].astype(cv_all.dtype), (lz, z, pos, z))
    # same split-K decode path as _block_decode (stacked local cache +
    # layer plane selected in the kernel's BlockSpec — prefix-bounded
    # HBM reads; jnp reference semantics off-TPU)
    from deeplearning4j_tpu.ops.flash_decode import decode_attention
    a = decode_attention(q[:, 0], ck_all, cv_all, pos,
                         n_heads=h_loc, layer=layer)    # [B, h_loc, Dh]
    h = h + g_model(jnp.matmul(a.reshape(a.shape[0], 1, d_loc),
                               p["Wo"].astype(h.dtype)))
    x = layer_norm(h, p["ln2g"], p["ln2b"], cfg.eps)
    h = _local_mlp(h, x, p, cfg, dp, g_model)
    return h, ck_all, cv_all


def _jit_program(run, maker: str, mesh: Mesh, in_specs, out_specs):
    """`jax.jit(shard_map(run))` under a module name of its own,
    `jit_run_<maker>`: every maker's body is a local `def run`, so
    without this all serving programs are `jit_run` in a profiler trace
    and can be told apart only by what they hold. int8 and constrained
    bodies share their maker's name."""
    run.__name__ = run.__qualname__ = f"run_{maker}"
    return jax.jit(jax.shard_map(run, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=True))


def make_parallel_generate(cfg: TransformerConfig, mesh: Mesh,
                           max_new_tokens: int,
                           temperature: float = 0.0,
                           top_k: int = 0, top_p: float = 1.0,
                           quantized=None):
    """Compiled sharded generate: (params, prompt [B, T0], key) ->
    [B, T0 + max_new_tokens]. Params must be placed with
    `shard_serving_params`; batch shards over 'data', heads/MLP over
    'model'. MoE configs serve with experts replicated and each
    expert's FFN hidden sharded over 'model' (module docstring).
    temperature<=0 is greedy; top_k/top_p apply the single-chip
    `_filter_logits` semantics (after temperature, before the
    categorical draw) — logits are replicated across 'model' ranks,
    so every rank filters and samples identically.

    ``quantized`` ("int8"/"fp8"): params are a
    `quant.model.quantize_params` tree placed with
    `shard_quantized_serving_params`; the decode math is unchanged —
    every weight use dequantizes on the fly via `.astype`."""
    tp, dp = _check_serving_mesh(cfg, mesh, top_k, top_p)
    quantized, _ = _resolve_quant(quantized, None)
    specs = _serving_specs(cfg, quantized)

    def run(params, prompt, key):
        dt = cfg.activation_dtype()
        b, t0 = prompt.shape
        if t0 + max_new_tokens > cfg.max_len:
            raise ValueError(
                f"generation length {t0 + max_new_tokens} exceeds "
                f"max_len={cfg.max_len}")
        # independent sampling noise per data shard (greedy ignores
        # the key; without the fold, equal prompts on different data
        # ranks would sample identical continuations). dp=1 skips the
        # fold so the key schedule matches single-chip generate
        # bit-for-bit — the sampled-path equivalence test's obligation.
        if dp > 1:
            key = jax.random.fold_in(key, lax.axis_index("data"))
        h = (params["embed"].astype(dt)[prompt]
             + params["pos"].astype(dt)[:t0][None])

        def pf_body(h, p):
            return _local_block_prefill(h, p, cfg, tp, dp)

        h, (ks, vs) = lax.scan(pf_body, h, params["blocks"])
        d_loc = (cfg.n_heads // tp) * cfg.d_head
        cdt = cfg.cache_jnp_dtype()
        ck = jnp.zeros((cfg.n_layers, b, cfg.max_len, d_loc), cdt)
        cv = jnp.zeros_like(ck)
        ck = ck.at[:, :, :t0].set(ks.astype(cdt))
        cv = cv.at[:, :, :t0].set(vs.astype(cdt))
        h = layer_norm(h, params["lnfg"], params["lnfb"], cfg.eps)
        logits = jnp.matmul(h[:, -1], params["Wout"].astype(h.dtype))
        pos0 = jnp.asarray(t0, jnp.int32)

        def sample(carry, i):
            ck, cv, pos, logits = carry
            if temperature <= 0:
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            else:
                # per-step fold, not pre-split xs — same rationale as
                # models/transformer._generate_jit (greedy traces no
                # threefry work); same _filter_logits so `generate` ->
                # `make_parallel_generate` keeps sampling semantics
                filt = _filter_logits(
                    logits.astype(jnp.float32) / temperature,
                    top_k, top_p)
                tok = jax.random.categorical(
                    jax.random.fold_in(key, i), filt,
                    axis=-1).astype(jnp.int32)
            emb = params["embed"].astype(dt)[tok]
            posv = lax.dynamic_slice_in_dim(params["pos"], pos, 1,
                                            axis=0).astype(dt)
            hh = (emb + posv)[:, None, :]
            for layer in range(cfg.n_layers):
                p_l = {kk: vv[layer]
                       for kk, vv in params["blocks"].items()}
                hh, ck, cv = _local_block_decode(hh, p_l, ck, cv,
                                                 layer, pos, cfg, tp,
                                                 dp)
            hh = layer_norm(hh, params["lnfg"], params["lnfb"],
                            cfg.eps)
            new_logits = jnp.matmul(hh[:, 0],
                                    params["Wout"].astype(hh.dtype))
            return (ck, cv, pos + 1, new_logits), tok

        _, toks = lax.scan(sample, (ck, cv, pos0, logits),
                           jnp.arange(max_new_tokens, dtype=jnp.int32))
        return jnp.concatenate([prompt, jnp.swapaxes(toks, 0, 1)],
                               axis=1)

    return _jit_program(run, "parallel_generate", mesh,
                        (specs, P("data", None), P()),
                        P("data", None))


def _check_serving_mesh(cfg: TransformerConfig, mesh: Mesh,
                        top_k: int, top_p: float):
    """Shared validation for every serving program factory. Returns
    (tp, dp)."""
    tp = mesh.shape["model"]
    dp = mesh.shape["data"]
    if not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    if top_k < 0:
        raise ValueError(f"top_k must be >= 0, got {top_k}")
    if cfg.n_heads % tp:
        raise ValueError(f"n_heads {cfg.n_heads} not divisible by "
                         f"model axis {tp}")
    if cfg.d_ff % tp:
        raise ValueError(f"d_ff {cfg.d_ff} not divisible by "
                         f"model axis {tp}")
    for ax in ("pipe", "seq", "expert"):
        if mesh.shape.get(ax, 1) > 1:
            raise ValueError(
                f"serving mesh uses only ('data', 'model'); axis "
                f"'{ax}'={mesh.shape[ax]} would silently shard the "
                "stacked layers with no schedule to reassemble them")
    return tp, dp


# ---------------------------------------------------------------------------
# continuous batching: persistent slot pool + prefill/decode split
# ---------------------------------------------------------------------------

_SLOT_CACHE_SPEC = P(None, "data", None, "model")   # [L, Ns, S, D]
_SLOT_VEC_SPEC = P("data")                          # per-slot scalars
# quantized-KV per-row scales [L, Ns, S, tp]: the trailing axis holds
# each model-rank's independent scale for its D_loc head shard (local
# view [L, ns, S, 1]) — see quant/kv.py for the layout rationale
_SLOT_SCALE_SPEC = P(None, "data", None, "model")


def _resolve_quant(quantized, kv_mode):
    """Normalize the two quantization knobs through
    `quant.core.resolve_mode` (fp8 falls back to int8 off-TPU) without
    importing quant at module load."""
    if quantized is None and kv_mode is None:
        return None, None
    from deeplearning4j_tpu.quant.core import resolve_mode
    return resolve_mode(quantized), resolve_mode(kv_mode)


def _refuse_unserved(cfg: TransformerConfig) -> None:
    """The serving programs hold one kind of state, a K/V cache of
    `n_heads` heads a layer. A config with layer kinds (recurrent state
    beside the cache, a latent in its place, layers outside the scanned
    stack) or fewer KV heads than query heads trains
    (models/layer_kinds.py) and is not served: refused here, where every
    `make_*` and `_check_spec` pass, by the field's name."""
    if cfg.layer_types:
        also = ""
        if "mla" in cfg.layer_types:
            also += (f"; latent attention (q_lora_rank={cfg.q_lora_rank}, "
                     f"kv_lora_rank={cfg.kv_lora_rank}) has no latent K/V "
                     "cache here")
        if cfg.lead_dense_layers or cfg.mtp_layers:
            also += (f"; lead_dense_layers={cfg.lead_dense_layers} and "
                     f"mtp_layers={cfg.mtp_layers} lie outside the stack "
                     "of like blocks the programs scan")
        raise ValueError(
            f"TransformerConfig.layer_types={cfg.layer_types}: the serving "
            "engine has no recurrent state (a Gated DeltaNet's or a "
            "Mamba-2 layer's) beside its paged K/V cache and cannot serve "
            "typed layers; they run on the training path only" + also)
    if cfg.n_kv_heads and cfg.n_kv_heads != cfg.n_heads:
        raise ValueError(
            f"TransformerConfig.n_kv_heads={cfg.n_kv_heads} with n_heads="
            f"{cfg.n_heads}: the serving K/V cache holds n_heads heads a "
            "layer; grouped-query attention is not served")


def _serving_specs(cfg: TransformerConfig, quantized):
    """Param in_specs/placement tree: the serving layout, run through
    `quant.model.quantize_specs` when the tree is quantized (values
    keep the float spec, scales drop sharding on their size-1 axis)."""
    _refuse_unserved(cfg)
    specs = serving_param_specs(cfg)
    if quantized:
        from deeplearning4j_tpu.quant.model import quantize_specs
        specs = quantize_specs(specs, mode=quantized)
    return specs


def _sample_slots(logits, posidx, key, dp: int, temperature: float,
                  top_k: int, top_p: float):
    """Per-slot sampling on [Ns, V] logits: the token generated at
    sequence index ``posidx[i]`` draws from fold_in(key, posidx[i]) —
    position-keyed, slot-placement-independent, so retries, solo
    isolation, preempt-resume, AND speculative verification reproduce
    the same continuation (models/transformer.sample_at_positions owns
    the core; this wrapper adds the data-rank key fold). Greedy
    (temperature<=0) ignores the key entirely."""
    with jax.named_scope("sample"):
        if temperature > 0 and dp > 1:
            key = jax.random.fold_in(key, lax.axis_index("data"))
        return sample_at_positions(logits, posidx, key, temperature,
                                   top_k, top_p)


# constrained-decoding runtime operands (ISSUE-20): every masked
# program variant takes five extra operands AFTER its regular runtime
# vectors — callow [C, V] bool + ctrans [C, V] int32 (the engine's
# ConstraintTable, replicated), cstate [Ns] int32 (each slot's global
# DFA state, chained call-to-call), cseed [Ns] bool + cseedval [Ns]
# int32 (host seat-time reseeds) — and returns the advanced cstate as
# one extra LAST output. Mask contents, transitions, and states are
# pure runtime data: the [C, V] table shape is fixed per engine, so
# the compiled-program set stays closed (zero steady-state recompiles).
_CTAB_SPEC = P(None, None)


def _c_start(cstate, cseed, cseedval):
    """Seed-or-carry: slots the host just (re)seated read their seeded
    DFA state (0 = the unconstrained all-allow row); everyone else
    carries the device-chained state."""
    return jnp.where(cseed, cseedval, cstate)


def _mask_allow(logits, allow):
    """Additive grammar fence before sampling: disallowed vocab
    entries drop to NEG_INF, allowed entries add 0.0 — an all-allow
    row (unconstrained slots / terminal states) is numerically inert,
    so co-resident unconstrained slots sample the same tokens a
    maskless program would."""
    from deeplearning4j_tpu.ops.flash_decode import NEG_INF
    return logits + jnp.where(allow, jnp.asarray(0.0, logits.dtype),
                              jnp.asarray(NEG_INF, logits.dtype))


def _local_block_decode_slotted(h, p, ck_all, cv_all, layer: int, pos,
                                act, cfg: TransformerConfig, tp: int,
                                dp: int):
    """One TP block, one new position PER SLOT: h [Ns, 1, D], stacked
    caches [L, Ns, S, D_loc], pos [Ns] (each slot's own filled length),
    act [Ns] (inactive slots neither write their cache row nor advance).
    The K/V row write is a per-slot scatter at (layer, slot, pos[slot]);
    attention masks each slot to its own filled prefix 0..pos[slot] —
    the per-slot generalization of _local_block_decode, sharing
    `ops/flash_decode.decode_attention` (vector-pos form) with the
    fused path so the slotted decode rides the same tuned primitive:
    jnp reference semantics off-TPU (token-identical to the fused
    path), the split-K kernel with per-slot DMA bounds on it."""
    from deeplearning4j_tpu.ops.flash_decode import decode_attention
    g_model = _g_sync("model")
    h_loc = cfg.n_heads // tp
    d_loc = h_loc * cfg.d_head
    ns = h.shape[0]
    s_max = ck_all.shape[2]
    x = layer_norm(h, p["ln1g"], p["ln1b"], cfg.eps)
    q = jnp.matmul(x[:, 0], p["Wq"].astype(x.dtype)) \
        .reshape(ns, h_loc, cfg.d_head)
    k = jnp.matmul(x[:, 0], p["Wk"].astype(x.dtype))      # [Ns, D_loc]
    v = jnp.matmul(x[:, 0], p["Wv"].astype(x.dtype))
    rows = jnp.arange(ns)
    wp = jnp.clip(pos, 0, s_max - 1)
    # masked in-place row write: inactive slots re-write their current
    # row with itself (scatter shape stays static; no branches)
    k_wr = jnp.where(act[:, None], k.astype(ck_all.dtype),
                     ck_all[layer, rows, wp])
    v_wr = jnp.where(act[:, None], v.astype(cv_all.dtype),
                     cv_all[layer, rows, wp])
    ck_all = ck_all.at[layer, rows, wp].set(k_wr)
    cv_all = cv_all.at[layer, rows, wp].set(v_wr)
    a = decode_attention(q, ck_all, cv_all, wp, n_heads=h_loc,
                         layer=layer)                    # [Ns, hl, Dh]
    h = h + g_model(jnp.matmul(a.reshape(ns, 1, d_loc),
                               p["Wo"].astype(h.dtype)))
    x = layer_norm(h, p["ln2g"], p["ln2b"], cfg.eps)
    h = _local_mlp(h, x, p, cfg, dp, g_model)
    return h, ck_all, cv_all


def _local_block_decode_slotted_q(h, p, ck_all, cv_all, ksc, vsc,
                                  layer: int, pos, act,
                                  cfg: TransformerConfig, tp: int,
                                  dp: int, kv_mode: str):
    """Quantized-KV variant of _local_block_decode_slotted: the new
    K/V row is quantized ON WRITE (per-row absmax — quant/kv.py) into
    the int8/fp8 caches, with its float32 scale written to the
    parallel [L, Ns, S, 1]-local scale planes. The attention consumer
    never rebuilds a dequantized cache: the K scale folds into the
    score row (``(q·k_int)·kscale_s``) and the V scale into the
    probability row (``(p·vscale_s)·v_int``) — algebraically the
    dequantized attention, touching [Ns, S] scale vectors instead of
    [Ns, S, D] panels. The fold now lives in
    `ops/flash_decode.decode_attention(k_scale=, v_scale=)` — one
    primitive for float, quantized, slotted, paged, and speculative-
    verify decode — with identical numerics (same NEG_INF mask, f32
    softmax, scale-before-1/sqrt(d) multiplication order)."""
    from deeplearning4j_tpu.ops.flash_decode import decode_attention
    from deeplearning4j_tpu.quant.kv import quantize_rows
    g_model = _g_sync("model")
    h_loc = cfg.n_heads // tp
    d_loc = h_loc * cfg.d_head
    ns = h.shape[0]
    s_max = ck_all.shape[2]
    x = layer_norm(h, p["ln1g"], p["ln1b"], cfg.eps)
    q = jnp.matmul(x[:, 0], p["Wq"].astype(x.dtype)) \
        .reshape(ns, h_loc, cfg.d_head)
    k = jnp.matmul(x[:, 0], p["Wk"].astype(x.dtype))      # [Ns, D_loc]
    v = jnp.matmul(x[:, 0], p["Wv"].astype(x.dtype))
    rows = jnp.arange(ns)
    wp = jnp.clip(pos, 0, s_max - 1)
    kq, ksr = quantize_rows(k, kv_mode)
    vq, vsr = quantize_rows(v, kv_mode)
    # masked in-place row+scale writes (same static-scatter trick as
    # the float path: inactive slots rewrite their current row/scale)
    k_wr = jnp.where(act[:, None], kq, ck_all[layer, rows, wp])
    v_wr = jnp.where(act[:, None], vq, cv_all[layer, rows, wp])
    ks_wr = jnp.where(act, ksr, ksc[layer, rows, wp, 0])
    vs_wr = jnp.where(act, vsr, vsc[layer, rows, wp, 0])
    ck_all = ck_all.at[layer, rows, wp].set(k_wr)
    cv_all = cv_all.at[layer, rows, wp].set(v_wr)
    ksc = ksc.at[layer, rows, wp, 0].set(ks_wr)
    vsc = vsc.at[layer, rows, wp, 0].set(vs_wr)
    a = decode_attention(q, ck_all, cv_all, wp, n_heads=h_loc,
                         layer=layer, k_scale=ksc[layer, :, :, 0],
                         v_scale=vsc[layer, :, :, 0])
    h = h + g_model(jnp.matmul(a.reshape(ns, 1, d_loc),
                               p["Wo"].astype(h.dtype)))
    x = layer_norm(h, p["ln2g"], p["ln2b"], cfg.eps)
    h = _local_mlp(h, x, p, cfg, dp, g_model)
    return h, ck_all, cv_all, ksc, vsc


def init_slot_state(cfg: TransformerConfig, mesh: Mesh, num_slots: int,
                    kv_mode=None, cache_dtype=None):
    """Allocate the persistent slot-pool state (ck, cv, pos, tok) on
    the serving mesh: KV caches [L, Ns, S, D] (slot axis over 'data',
    flattened heads over 'model' — models/transformer.slot_cache_shape)
    plus per-slot position and last-token vectors. These arrays live
    on device for the engine's lifetime; every prefill/decode program
    consumes and returns them functionally, so a failed call leaves
    the pool bit-identical (retry/isolation need no repair).

    ``kv_mode`` ("int8"/"fp8") switches to the QUANTIZED pool —
    `quant.kv.init_quant_slot_state`'s 6-tuple (ck, cv, kscale,
    vscale, pos, tok) consumed by the ``kv_mode=...`` program
    variants. ``cache_dtype`` (jnp dtype) overrides `cfg.cache_dtype`
    for the float pool (bf16 caches under f32 activations)."""
    from jax.sharding import NamedSharding

    from deeplearning4j_tpu.models.transformer import slot_cache_shape
    _, kv_mode = _resolve_quant(None, kv_mode)
    if kv_mode is not None:
        from deeplearning4j_tpu.quant.kv import init_quant_slot_state
        return init_quant_slot_state(cfg, mesh, num_slots, kv_mode)
    dp = mesh.shape["data"]
    if num_slots % dp:
        raise ValueError(f"num_slots {num_slots} not divisible by "
                         f"data axis {dp}")
    dt = (cache_dtype if cache_dtype is not None
          else cfg.cache_jnp_dtype())
    shape = slot_cache_shape(cfg, num_slots)
    kv_sh = NamedSharding(mesh, _SLOT_CACHE_SPEC)
    vec_sh = NamedSharding(mesh, _SLOT_VEC_SPEC)
    ck = jax.device_put(jnp.zeros(shape, dt), kv_sh)
    cv = jax.device_put(jnp.zeros(shape, dt), kv_sh)
    pos = jax.device_put(jnp.zeros((num_slots,), jnp.int32), vec_sh)
    tok = jax.device_put(jnp.zeros((num_slots,), jnp.int32), vec_sh)
    return ck, cv, pos, tok


def make_continuous_prefill(cfg: TransformerConfig, mesh: Mesh,
                            bucket_len: int, num_slots: int,
                            temperature: float = 0.0,
                            top_k: int = 0, top_p: float = 1.0,
                            quantized=None, kv_mode=None,
                            constrain: bool = False):
    """Compiled slot-pool prefill: (params, ck, cv, pos, tok,
    prompts [Ns, Tb], plen [Ns], key) -> (ck, cv, pos, tok,
    first [Ns]).

    ``constrain=True`` (ISSUE-20) inserts the five constraint operands
    before ``key`` and appends the advanced DFA-state vector as the
    last output: the admitted slot's first token samples under its
    seeded state's allow row and advances the state through it.

    Slots with plen[i] > 0 are ADMISSIONS: their prompt (right-padded
    to the Tb bucket) is prefilled in one batched pass, their cache
    rows [0, plen) are written (pad rows land too but sit beyond pos
    and are overwritten before ever being attended), pos[i] <- plen[i],
    and the slot's first generated token is sampled from the logits at
    row plen[i]-1 (returned in ``first``; -1 for non-admitted slots).
    Slots with plen[i] == 0 pass through untouched — so one fixed
    (bucket_len, num_slots) geometry serves every admission pattern
    with zero recompiles.

    ``quantized`` ("int8"/"fp8") marks the params as a quantized tree
    (specs adapt; math is unchanged via on-the-fly dequant).
    ``kv_mode`` switches to the QUANTIZED slot pool: the state grows
    per-row scale planes — (params, ck, cv, kscale, vscale, pos, tok,
    prompts, plen, key) -> (ck, cv, kscale, vscale, pos, tok, first)
    — and prefilled K/V rows are quantized on write (quant/kv.py)."""
    tp, dp = _check_serving_mesh(cfg, mesh, top_k, top_p)
    quantized, kv_mode = _resolve_quant(quantized, kv_mode)
    if num_slots % dp:
        raise ValueError(f"num_slots {num_slots} not divisible by "
                         f"data axis {dp}")
    if not 0 < bucket_len <= cfg.max_len:
        raise ValueError(f"bucket_len {bucket_len} out of "
                         f"(0, {cfg.max_len}]")
    specs = _serving_specs(cfg, quantized)

    def compute(params, prompts, plen, key, allow=None):
        """Shared prefill math: block scan + first-token sampling.
        Returns (admit, ks, vs, first, pos_new-ready pieces)."""
        dt = cfg.activation_dtype()
        ns, tb = prompts.shape
        admit = plen > 0
        h = (params["embed"].astype(dt)[prompts]
             + params["pos"].astype(dt)[:tb][None])
        valid = (jnp.arange(tb)[None, :] < plen[:, None]) \
            if cfg.n_experts > 0 else None

        def pf_body(hh, p):
            return _local_block_prefill(hh, p, cfg, tp, dp, valid=valid)

        h, (ks, vs) = lax.scan(pf_body, h, params["blocks"])
        h = layer_norm(h, params["lnfg"], params["lnfb"], cfg.eps)
        last = h[jnp.arange(ns), jnp.clip(plen - 1, 0, tb - 1)]
        logits = jnp.matmul(last, params["Wout"].astype(last.dtype))
        if allow is not None:
            logits = _mask_allow(logits, allow)
        first = _sample_slots(logits, plen, key, dp, temperature,
                              top_k, top_p)
        return admit, tb, ks, vs, first

    def finish(admit, first, plen, pos, tok):
        pos = jnp.where(admit, plen.astype(pos.dtype), pos)
        tok = jnp.where(admit, first, tok)
        return pos, tok, jnp.where(admit, first,
                                   jnp.asarray(-1, jnp.int32))

    if kv_mode is None:
        def base(params, ck, cv, pos, tok, prompts, plen, key,
                 callow=None, ctrans=None, ds0=None):
            admit, tb, ks, vs, first = compute(
                params, prompts, plen, key,
                allow=None if callow is None else callow[ds0])
            keep = admit[None, :, None, None]
            ck = ck.at[:, :, :tb, :].set(
                jnp.where(keep, ks.astype(ck.dtype), ck[:, :, :tb, :]))
            cv = cv.at[:, :, :tb, :].set(
                jnp.where(keep, vs.astype(cv.dtype), cv[:, :, :tb, :]))
            pos, tok, first = finish(admit, first, plen, pos, tok)
            if callow is None:
                return ck, cv, pos, tok, first
            ds = jnp.where(admit,
                           ctrans[ds0, jnp.maximum(first, 0)], ds0)
            return ck, cv, pos, tok, first, ds

        if constrain:
            def run(params, ck, cv, pos, tok, prompts, plen, callow,
                    ctrans, cstate, cseed, cseedval, key):
                return base(params, ck, cv, pos, tok, prompts, plen,
                            key, callow, ctrans,
                            _c_start(cstate, cseed, cseedval))

            in_specs = (specs, _SLOT_CACHE_SPEC, _SLOT_CACHE_SPEC,
                        _SLOT_VEC_SPEC, _SLOT_VEC_SPEC,
                        P("data", None), _SLOT_VEC_SPEC, _CTAB_SPEC,
                        _CTAB_SPEC, _SLOT_VEC_SPEC, _SLOT_VEC_SPEC,
                        _SLOT_VEC_SPEC, P())
            out_specs = (_SLOT_CACHE_SPEC, _SLOT_CACHE_SPEC,
                         _SLOT_VEC_SPEC, _SLOT_VEC_SPEC,
                         _SLOT_VEC_SPEC, _SLOT_VEC_SPEC)
        else:
            run = base
            in_specs = (specs, _SLOT_CACHE_SPEC, _SLOT_CACHE_SPEC,
                        _SLOT_VEC_SPEC, _SLOT_VEC_SPEC,
                        P("data", None), _SLOT_VEC_SPEC, P())
            out_specs = (_SLOT_CACHE_SPEC, _SLOT_CACHE_SPEC,
                         _SLOT_VEC_SPEC, _SLOT_VEC_SPEC,
                         _SLOT_VEC_SPEC)
    else:
        def base(params, ck, cv, ksc, vsc, pos, tok, prompts, plen,
                 key, callow=None, ctrans=None, ds0=None):
            from deeplearning4j_tpu.quant.kv import quantize_rows
            admit, tb, ks, vs, first = compute(
                params, prompts, plen, key,
                allow=None if callow is None else callow[ds0])
            kq, ksr = quantize_rows(ks, kv_mode)   # [L, Ns, Tb, D_loc]
            vq, vsr = quantize_rows(vs, kv_mode)
            keep = admit[None, :, None, None]
            keep3 = admit[None, :, None]
            ck = ck.at[:, :, :tb, :].set(
                jnp.where(keep, kq, ck[:, :, :tb, :]))
            cv = cv.at[:, :, :tb, :].set(
                jnp.where(keep, vq, cv[:, :, :tb, :]))
            ksc = ksc.at[:, :, :tb, 0].set(
                jnp.where(keep3, ksr, ksc[:, :, :tb, 0]))
            vsc = vsc.at[:, :, :tb, 0].set(
                jnp.where(keep3, vsr, vsc[:, :, :tb, 0]))
            pos, tok, first = finish(admit, first, plen, pos, tok)
            if callow is None:
                return ck, cv, ksc, vsc, pos, tok, first
            ds = jnp.where(admit,
                           ctrans[ds0, jnp.maximum(first, 0)], ds0)
            return ck, cv, ksc, vsc, pos, tok, first, ds

        if constrain:
            def run(params, ck, cv, ksc, vsc, pos, tok, prompts, plen,
                    callow, ctrans, cstate, cseed, cseedval, key):
                return base(params, ck, cv, ksc, vsc, pos, tok,
                            prompts, plen, key, callow, ctrans,
                            _c_start(cstate, cseed, cseedval))

            in_specs = (specs, _SLOT_CACHE_SPEC, _SLOT_CACHE_SPEC,
                        _SLOT_SCALE_SPEC, _SLOT_SCALE_SPEC,
                        _SLOT_VEC_SPEC, _SLOT_VEC_SPEC,
                        P("data", None), _SLOT_VEC_SPEC, _CTAB_SPEC,
                        _CTAB_SPEC, _SLOT_VEC_SPEC, _SLOT_VEC_SPEC,
                        _SLOT_VEC_SPEC, P())
            out_specs = (_SLOT_CACHE_SPEC, _SLOT_CACHE_SPEC,
                         _SLOT_SCALE_SPEC, _SLOT_SCALE_SPEC,
                         _SLOT_VEC_SPEC, _SLOT_VEC_SPEC,
                         _SLOT_VEC_SPEC, _SLOT_VEC_SPEC)
        else:
            run = base
            in_specs = (specs, _SLOT_CACHE_SPEC, _SLOT_CACHE_SPEC,
                        _SLOT_SCALE_SPEC, _SLOT_SCALE_SPEC,
                        _SLOT_VEC_SPEC, _SLOT_VEC_SPEC,
                        P("data", None), _SLOT_VEC_SPEC, P())
            out_specs = (_SLOT_CACHE_SPEC, _SLOT_CACHE_SPEC,
                         _SLOT_SCALE_SPEC, _SLOT_SCALE_SPEC,
                         _SLOT_VEC_SPEC, _SLOT_VEC_SPEC,
                         _SLOT_VEC_SPEC)

    return _jit_program(run, "continuous_prefill", mesh, in_specs,
                        out_specs)


def make_continuous_decode(cfg: TransformerConfig, mesh: Mesh,
                           chunk: int, num_slots: int,
                           temperature: float = 0.0,
                           top_k: int = 0, top_p: float = 1.0,
                           quantized=None, kv_mode=None,
                           constrain: bool = False):
    """Compiled slot-pool decode chunk: (params, ck, cv, pos, tok,
    active [Ns] bool, rem [Ns] int32, key) -> (ck, cv, pos, tok,
    toks [Ns, chunk]).

    ``constrain=True`` (ISSUE-20): five constraint operands before
    ``key``, the chained DFA-state vector appended as the last output;
    each scanned step gathers its slot's allow row, masks the logits
    before sampling, and advances the state through the sampled
    token — mask and transitions are runtime data, the program is one
    more fixed geometry.

    Advances every active slot up to ``chunk`` tokens from its own
    position: each scanned step embeds the slot's pending token at its
    own pos, writes its K/V cache row in place, attends only the
    slot's filled prefix, and samples the next token. A slot whose
    remaining budget (``rem``) hits 0 deactivates itself mid-chunk —
    no further writes, pos frozen, emitted tokens -1 — so per-slot
    budgets never overrun the cache and finished slots stop burning
    writes. active/rem/pos are runtime DATA: one compiled program per
    (chunk, num_slots) geometry covers all traffic.

    ``quantized`` ("int8"/"fp8") marks the params as a quantized tree;
    ``kv_mode`` switches to the quantized slot pool — the state grows
    per-row scale planes ((params, ck, cv, kscale, vscale, pos, tok,
    active, rem, key) -> (..., toks)) and the per-step K/V row is
    quantized on write (_local_block_decode_slotted_q)."""
    tp, dp = _check_serving_mesh(cfg, mesh, top_k, top_p)
    quantized, kv_mode = _resolve_quant(quantized, kv_mode)
    if num_slots % dp:
        raise ValueError(f"num_slots {num_slots} not divisible by "
                         f"data axis {dp}")
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    specs = _serving_specs(cfg, quantized)

    def sample_and_advance(params, h, act, pos, tok, rem, key,
                           ds=None, callow=None, ctrans=None):
        h = layer_norm(h, params["lnfg"], params["lnfb"], cfg.eps)
        logits = jnp.matmul(h[:, 0], params["Wout"].astype(h.dtype))
        if callow is not None:
            logits = _mask_allow(logits, callow[ds])
        nxt = _sample_slots(logits, pos + 1, key, dp, temperature,
                            top_k, top_p)
        if callow is not None:
            ds = jnp.where(act, ctrans[ds, nxt], ds)
        tok = jnp.where(act, nxt, tok)
        emit = jnp.where(act, nxt, jnp.asarray(-1, jnp.int32))
        pos = jnp.where(act, pos + 1, pos)
        rem = jnp.where(act, rem - 1, rem)
        return pos, tok, rem, emit, ds

    def embed_step(params, pos, tok):
        dt = cfg.activation_dtype()
        emb = params["embed"].astype(dt)[tok]
        pv = params["pos"].astype(dt)[
            jnp.clip(pos, 0, cfg.max_len - 1)]
        return (emb + pv)[:, None, :]

    if kv_mode is None:
        if constrain:
            def run(params, ck, cv, pos, tok, active, rem, callow,
                    ctrans, cstate, cseed, cseedval, key):
                def step(carry, _):
                    ck, cv, pos, tok, rem, ds = carry
                    act = active & (rem > 0)
                    h = embed_step(params, pos, tok)
                    for layer in range(cfg.n_layers):
                        p_l = {kk: vv[layer]
                               for kk, vv in params["blocks"].items()}
                        h, ck, cv = _local_block_decode_slotted(
                            h, p_l, ck, cv, layer, pos, act, cfg, tp,
                            dp)
                    pos, tok, rem, emit, ds = sample_and_advance(
                        params, h, act, pos, tok, rem, key, ds,
                        callow, ctrans)
                    return (ck, cv, pos, tok, rem, ds), emit

                ds0 = _c_start(cstate, cseed, cseedval)
                (ck, cv, pos, tok, _, ds), toks = lax.scan(
                    step, (ck, cv, pos, tok, rem, ds0), None,
                    length=chunk)
                return (ck, cv, pos, tok, jnp.swapaxes(toks, 0, 1),
                        ds)

            in_specs = (specs, _SLOT_CACHE_SPEC, _SLOT_CACHE_SPEC,
                        _SLOT_VEC_SPEC, _SLOT_VEC_SPEC,
                        _SLOT_VEC_SPEC, _SLOT_VEC_SPEC, _CTAB_SPEC,
                        _CTAB_SPEC, _SLOT_VEC_SPEC, _SLOT_VEC_SPEC,
                        _SLOT_VEC_SPEC, P())
            out_specs = (_SLOT_CACHE_SPEC, _SLOT_CACHE_SPEC,
                         _SLOT_VEC_SPEC, _SLOT_VEC_SPEC,
                         P("data", None), _SLOT_VEC_SPEC)
        else:
            def run(params, ck, cv, pos, tok, active, rem, key):
                def step(carry, _):
                    ck, cv, pos, tok, rem = carry
                    act = active & (rem > 0)
                    h = embed_step(params, pos, tok)
                    for layer in range(cfg.n_layers):
                        p_l = {kk: vv[layer]
                               for kk, vv in params["blocks"].items()}
                        h, ck, cv = _local_block_decode_slotted(
                            h, p_l, ck, cv, layer, pos, act, cfg, tp,
                            dp)
                    pos, tok, rem, emit, _ = sample_and_advance(
                        params, h, act, pos, tok, rem, key)
                    return (ck, cv, pos, tok, rem), emit

                (ck, cv, pos, tok, _), toks = lax.scan(
                    step, (ck, cv, pos, tok, rem), None, length=chunk)
                return ck, cv, pos, tok, jnp.swapaxes(toks, 0, 1)

            in_specs = (specs, _SLOT_CACHE_SPEC, _SLOT_CACHE_SPEC,
                        _SLOT_VEC_SPEC, _SLOT_VEC_SPEC,
                        _SLOT_VEC_SPEC, _SLOT_VEC_SPEC, P())
            out_specs = (_SLOT_CACHE_SPEC, _SLOT_CACHE_SPEC,
                         _SLOT_VEC_SPEC, _SLOT_VEC_SPEC,
                         P("data", None))
    else:
        if constrain:
            def run(params, ck, cv, ksc, vsc, pos, tok, active, rem,
                    callow, ctrans, cstate, cseed, cseedval, key):
                def step(carry, _):
                    ck, cv, ksc, vsc, pos, tok, rem, ds = carry
                    act = active & (rem > 0)
                    h = embed_step(params, pos, tok)
                    for layer in range(cfg.n_layers):
                        p_l = {kk: vv[layer]
                               for kk, vv in params["blocks"].items()}
                        h, ck, cv, ksc, vsc = \
                            _local_block_decode_slotted_q(
                                h, p_l, ck, cv, ksc, vsc, layer, pos,
                                act, cfg, tp, dp, kv_mode)
                    pos, tok, rem, emit, ds = sample_and_advance(
                        params, h, act, pos, tok, rem, key, ds,
                        callow, ctrans)
                    return (ck, cv, ksc, vsc, pos, tok, rem, ds), emit

                ds0 = _c_start(cstate, cseed, cseedval)
                (ck, cv, ksc, vsc, pos, tok, _, ds), toks = lax.scan(
                    step, (ck, cv, ksc, vsc, pos, tok, rem, ds0),
                    None, length=chunk)
                return (ck, cv, ksc, vsc, pos, tok,
                        jnp.swapaxes(toks, 0, 1), ds)

            in_specs = (specs, _SLOT_CACHE_SPEC, _SLOT_CACHE_SPEC,
                        _SLOT_SCALE_SPEC, _SLOT_SCALE_SPEC,
                        _SLOT_VEC_SPEC, _SLOT_VEC_SPEC,
                        _SLOT_VEC_SPEC, _SLOT_VEC_SPEC, _CTAB_SPEC,
                        _CTAB_SPEC, _SLOT_VEC_SPEC, _SLOT_VEC_SPEC,
                        _SLOT_VEC_SPEC, P())
            out_specs = (_SLOT_CACHE_SPEC, _SLOT_CACHE_SPEC,
                         _SLOT_SCALE_SPEC, _SLOT_SCALE_SPEC,
                         _SLOT_VEC_SPEC, _SLOT_VEC_SPEC,
                         P("data", None), _SLOT_VEC_SPEC)
        else:
            def run(params, ck, cv, ksc, vsc, pos, tok, active, rem,
                    key):
                def step(carry, _):
                    ck, cv, ksc, vsc, pos, tok, rem = carry
                    act = active & (rem > 0)
                    h = embed_step(params, pos, tok)
                    for layer in range(cfg.n_layers):
                        p_l = {kk: vv[layer]
                               for kk, vv in params["blocks"].items()}
                        h, ck, cv, ksc, vsc = \
                            _local_block_decode_slotted_q(
                                h, p_l, ck, cv, ksc, vsc, layer, pos,
                                act, cfg, tp, dp, kv_mode)
                    pos, tok, rem, emit, _ = sample_and_advance(
                        params, h, act, pos, tok, rem, key)
                    return (ck, cv, ksc, vsc, pos, tok, rem), emit

                (ck, cv, ksc, vsc, pos, tok, _), toks = lax.scan(
                    step, (ck, cv, ksc, vsc, pos, tok, rem), None,
                    length=chunk)
                return (ck, cv, ksc, vsc, pos, tok,
                        jnp.swapaxes(toks, 0, 1))

            in_specs = (specs, _SLOT_CACHE_SPEC, _SLOT_CACHE_SPEC,
                        _SLOT_SCALE_SPEC, _SLOT_SCALE_SPEC,
                        _SLOT_VEC_SPEC, _SLOT_VEC_SPEC,
                        _SLOT_VEC_SPEC, _SLOT_VEC_SPEC, P())
            out_specs = (_SLOT_CACHE_SPEC, _SLOT_CACHE_SPEC,
                         _SLOT_SCALE_SPEC, _SLOT_SCALE_SPEC,
                         _SLOT_VEC_SPEC, _SLOT_VEC_SPEC,
                         P("data", None))

    return _jit_program(run, "continuous_decode", mesh, in_specs,
                        out_specs)


def make_chunked_prefill(cfg: TransformerConfig, mesh: Mesh,
                         chunk_len: int, num_slots: int,
                         temperature: float = 0.0, top_k: int = 0,
                         top_p: float = 1.0, quantized=None,
                         kv_mode=None, constrain: bool = False):
    """Compiled CHUNKED admission prefill over the contiguous slot
    pool: (params, ck, cv, pos, tok, toks [Ns, C], clen [Ns],
    start [Ns], last [Ns] bool, key) -> (ck, cv, pos, tok,
    first [Ns]).

    Advances every slot with clen[i] > 0 by its next clen (<= C)
    prompt tokens: ``toks[i, :clen[i]]`` is the slice
    prompt[start[i] : start[i]+clen[i]] of the slot's committed
    prefix, its K/V rows are written at absolute positions start+t,
    and pos[i] <- start[i]+clen[i]. Attention per chunk query t is
    TWO-PIECE — the slot's already-written cache rows masked to
    s < start (exact zeros on the first chunk) plus causal float
    self-attention within the chunk, one softmax over the
    concatenated scores — the paged prefix-hit resume generalized to
    arbitrary chunk boundaries on the contiguous pool, reproducing
    `_local_block_prefill`'s numerics when the chunks are replayed in
    order. Slots with last[i] set additionally sample their first
    generated token at sequence index start+clen (the same
    position-keyed schedule one-shot prefill uses) into ``tok`` and
    ``first``; mid-prompt chunks leave ``tok`` untouched and report
    first = -1. start/clen/last are runtime DATA: one compiled
    program per (chunk_len, num_slots) geometry serves every resume
    position and partial-chunk budget with zero recompiles.

    ``quantized``/``kv_mode`` follow make_continuous_prefill: the
    quantized pool grows scale planes ((params, ck, cv, kscale,
    vscale, pos, tok, toks, clen, start, last, key) -> (..., first))
    and chunk rows quantize on write while the chunk still attends
    itself in float (the cached prefix re-reads through its
    quantization — the int8 decode envelope).

    ``constrain=True`` (ISSUE-20): five constraint operands before
    ``key``, the DFA-state vector appended last; only a FINAL chunk
    (last[i]) samples, so only final chunks mask and advance —
    mid-prompt chunks carry the seeded state unchanged."""
    from deeplearning4j_tpu.ops.flash_decode import NEG_INF
    tp, dp = _check_serving_mesh(cfg, mesh, top_k, top_p)
    quantized, kv_mode = _resolve_quant(quantized, kv_mode)
    if num_slots % dp:
        raise ValueError(f"num_slots {num_slots} not divisible by "
                         f"data axis {dp}")
    if not 0 < chunk_len <= cfg.max_len:
        raise ValueError(f"chunk_len {chunk_len} out of "
                         f"(0, {cfg.max_len}]")
    specs = _serving_specs(cfg, quantized)
    h_loc = cfg.n_heads // tp
    d_loc = h_loc * cfg.d_head
    scale = cfg.d_head ** -0.5

    def body(params, ck, cv, ksc, vsc, toks, clen, start, key,
             allow=None):
        dt = cfg.activation_dtype()
        acc = jnp.promote_types(dt, jnp.float32)
        ns, c = toks.shape
        s_max = ck.shape[2]
        adv = clen > 0
        absp = start[:, None] + jnp.arange(c)[None, :]     # [ns, C]
        valid = jnp.arange(c)[None, :] < clen[:, None]
        rows = jnp.arange(ns)[:, None]
        wp_g = jnp.clip(absp, 0, s_max - 1)   # in-bounds gather index
        pe = params["pos"].astype(dt)[jnp.clip(absp, 0,
                                               cfg.max_len - 1)]
        h = params["embed"].astype(dt)[toks] + pe
        mvalid = valid if cfg.n_experts > 0 else None
        causal = (jnp.arange(c)[None, :]
                  <= jnp.arange(c)[:, None])               # [C, C]
        pmask = (jnp.arange(s_max)[None, None, None, :]
                 < start[:, None, None, None])             # [ns,1,1,S]
        for layer in range(cfg.n_layers):
            p = {kk: vv[layer] for kk, vv in params["blocks"].items()}
            x = layer_norm(h, p["ln1g"], p["ln1b"], cfg.eps)
            q = jnp.matmul(x, p["Wq"].astype(x.dtype)) \
                .reshape(ns, c, h_loc, cfg.d_head)
            k = jnp.matmul(x, p["Wk"].astype(x.dtype))     # [ns,C,Dl]
            v = jnp.matmul(x, p["Wv"].astype(x.dtype))
            # write the chunk's rows at their absolute positions:
            # invalid (pad) entries rewrite their current row with
            # itself (the static-scatter trick) and positions past the
            # pool drop — per-row indices are distinct, so there is no
            # duplicate-index hazard on live rows
            if kv_mode is None:
                k_wr = jnp.where(valid[..., None], k.astype(ck.dtype),
                                 ck[layer][rows, wp_g])
                v_wr = jnp.where(valid[..., None], v.astype(cv.dtype),
                                 cv[layer][rows, wp_g])
                ck = ck.at[layer, rows, absp].set(k_wr, mode="drop")
                cv = cv.at[layer, rows, absp].set(v_wr, mode="drop")
            else:
                from deeplearning4j_tpu.quant.kv import quantize_rows
                kq, ksr = quantize_rows(k, kv_mode)
                vq, vsr = quantize_rows(v, kv_mode)
                k_wr = jnp.where(valid[..., None], kq,
                                 ck[layer][rows, wp_g])
                v_wr = jnp.where(valid[..., None], vq,
                                 cv[layer][rows, wp_g])
                ks_wr = jnp.where(valid, ksr,
                                  ksc[layer][rows, wp_g, 0])
                vs_wr = jnp.where(valid, vsr,
                                  vsc[layer][rows, wp_g, 0])
                ck = ck.at[layer, rows, absp].set(k_wr, mode="drop")
                cv = cv.at[layer, rows, absp].set(v_wr, mode="drop")
                ksc = ksc.at[layer, rows, absp, 0].set(ks_wr,
                                                       mode="drop")
                vsc = vsc.at[layer, rows, absp, 0].set(vs_wr,
                                                       mode="drop")
            kv4 = k.reshape(ns, c, h_loc, cfg.d_head)
            vv4 = v.reshape(ns, c, h_loc, cfg.d_head)
            # piece 2: float causal self-attention within the chunk —
            # bitwise dot_product_attention(q, k, v, causal=True)
            sc2 = jnp.einsum("bthd,bshd->bhts", q, kv4,
                             preferred_element_type=acc) * scale
            sc2 = jnp.where(causal[None, None], sc2, NEG_INF)
            # piece 1: the slot's cached prefix, masked to s < start
            # (fully masked — exact zeros — on the first chunk)
            if kv_mode is None:
                kh = ck[layer].reshape(ns, s_max, h_loc, cfg.d_head)
                vh = cv[layer].reshape(ns, s_max, h_loc, cfg.d_head)
                sc1 = jnp.einsum("bthd,bshd->bhts", q, kh,
                                 preferred_element_type=acc) * scale
            else:
                kh = ck[layer].astype(jnp.float32) \
                    .reshape(ns, s_max, h_loc, cfg.d_head)
                vh = cv[layer].astype(jnp.float32) \
                    .reshape(ns, s_max, h_loc, cfg.d_head)
                ksg = ksc[layer, :, :, 0]                  # [ns, S]
                vsg = vsc[layer, :, :, 0]
                sc1 = jnp.einsum("bthd,bshd->bhts",
                                 q.astype(jnp.float32), kh) \
                    * ksg[:, None, None, :] * scale
            sc1 = jnp.where(pmask, sc1, NEG_INF)
            # one softmax over [prefix | chunk] keys (logical order
            # preserved), then the two value pieces recombine — the
            # make_paged_prefill recombination on the contiguous pool
            w = jax.nn.softmax(
                jnp.concatenate([sc1.astype(acc), sc2], axis=-1),
                axis=-1)
            w1, w2 = w[..., :s_max], w[..., s_max:]
            if kv_mode is None:
                a1 = jnp.einsum("bhts,bshd->bthd",
                                w1.astype(vh.dtype), vh)
            else:
                a1 = jnp.einsum("bhts,bshd->bthd",
                                w1 * vsg[:, None, None, :], vh) \
                    .astype(v.dtype)
            a2 = jnp.einsum("bhts,bshd->bthd", w2.astype(v.dtype),
                            vv4)
            a = (a1 + a2).reshape(ns, c, d_loc)
            h = h + _g_sync("model")(
                jnp.matmul(a, p["Wo"].astype(a.dtype)))
            x = layer_norm(h, p["ln2g"], p["ln2b"], cfg.eps)
            h = _local_mlp(h, x, p, cfg, dp, _g_sync("model"),
                           valid=mvalid)
        h = layer_norm(h, params["lnfg"], params["lnfb"], cfg.eps)
        lastrow = h[jnp.arange(ns), jnp.clip(clen - 1, 0, c - 1)]
        logits = jnp.matmul(lastrow, params["Wout"].astype(
            lastrow.dtype))
        plen = start + clen
        if allow is not None:
            logits = _mask_allow(logits, allow)
        first = _sample_slots(logits, plen, key, dp, temperature,
                              top_k, top_p)
        return adv, plen, first, ck, cv, ksc, vsc

    def finish(adv, lastf, plen, first, pos, tok):
        take = adv & lastf
        pos = jnp.where(adv, plen.astype(pos.dtype), pos)
        tok = jnp.where(take, first, tok)
        return pos, tok, jnp.where(take, first,
                                   jnp.asarray(-1, jnp.int32))

    def c_advance(take, ds0, ctrans, first):
        """Final-chunk DFA advance: only slots that SAMPLED (take)
        step their state through the first generated token;
        mid-prompt chunks carry the seeded state forward."""
        return jnp.where(take, ctrans[ds0, jnp.maximum(first, 0)],
                         ds0)

    if kv_mode is None:
        if constrain:
            def run(params, ck, cv, pos, tok, toks, clen, start, last,
                    callow, ctrans, cstate, cseed, cseedval, key):
                ds0 = _c_start(cstate, cseed, cseedval)
                adv, plen, first, ck, cv, _, _ = body(
                    params, ck, cv, None, None, toks, clen, start,
                    key, allow=callow[ds0])
                pos, tok, first = finish(adv, last, plen, first, pos,
                                         tok)
                ds = c_advance(adv & last, ds0, ctrans, first)
                return ck, cv, pos, tok, first, ds

            in_specs = (specs, _SLOT_CACHE_SPEC, _SLOT_CACHE_SPEC,
                        _SLOT_VEC_SPEC, _SLOT_VEC_SPEC,
                        P("data", None), _SLOT_VEC_SPEC,
                        _SLOT_VEC_SPEC, _SLOT_VEC_SPEC, _CTAB_SPEC,
                        _CTAB_SPEC, _SLOT_VEC_SPEC, _SLOT_VEC_SPEC,
                        _SLOT_VEC_SPEC, P())
            out_specs = (_SLOT_CACHE_SPEC, _SLOT_CACHE_SPEC,
                         _SLOT_VEC_SPEC, _SLOT_VEC_SPEC,
                         _SLOT_VEC_SPEC, _SLOT_VEC_SPEC)
        else:
            def run(params, ck, cv, pos, tok, toks, clen, start, last,
                    key):
                adv, plen, first, ck, cv, _, _ = body(
                    params, ck, cv, None, None, toks, clen, start,
                    key)
                pos, tok, first = finish(adv, last, plen, first, pos,
                                         tok)
                return ck, cv, pos, tok, first

            in_specs = (specs, _SLOT_CACHE_SPEC, _SLOT_CACHE_SPEC,
                        _SLOT_VEC_SPEC, _SLOT_VEC_SPEC,
                        P("data", None), _SLOT_VEC_SPEC,
                        _SLOT_VEC_SPEC, _SLOT_VEC_SPEC, P())
            out_specs = (_SLOT_CACHE_SPEC, _SLOT_CACHE_SPEC,
                         _SLOT_VEC_SPEC, _SLOT_VEC_SPEC,
                         _SLOT_VEC_SPEC)
    else:
        if constrain:
            def run(params, ck, cv, ksc, vsc, pos, tok, toks, clen,
                    start, last, callow, ctrans, cstate, cseed,
                    cseedval, key):
                ds0 = _c_start(cstate, cseed, cseedval)
                adv, plen, first, ck, cv, ksc, vsc = body(
                    params, ck, cv, ksc, vsc, toks, clen, start, key,
                    allow=callow[ds0])
                pos, tok, first = finish(adv, last, plen, first, pos,
                                         tok)
                ds = c_advance(adv & last, ds0, ctrans, first)
                return ck, cv, ksc, vsc, pos, tok, first, ds

            in_specs = (specs, _SLOT_CACHE_SPEC, _SLOT_CACHE_SPEC,
                        _SLOT_SCALE_SPEC, _SLOT_SCALE_SPEC,
                        _SLOT_VEC_SPEC, _SLOT_VEC_SPEC,
                        P("data", None), _SLOT_VEC_SPEC,
                        _SLOT_VEC_SPEC, _SLOT_VEC_SPEC, _CTAB_SPEC,
                        _CTAB_SPEC, _SLOT_VEC_SPEC, _SLOT_VEC_SPEC,
                        _SLOT_VEC_SPEC, P())
            out_specs = (_SLOT_CACHE_SPEC, _SLOT_CACHE_SPEC,
                         _SLOT_SCALE_SPEC, _SLOT_SCALE_SPEC,
                         _SLOT_VEC_SPEC, _SLOT_VEC_SPEC,
                         _SLOT_VEC_SPEC, _SLOT_VEC_SPEC)
        else:
            def run(params, ck, cv, ksc, vsc, pos, tok, toks, clen,
                    start, last, key):
                adv, plen, first, ck, cv, ksc, vsc = body(
                    params, ck, cv, ksc, vsc, toks, clen, start, key)
                pos, tok, first = finish(adv, last, plen, first, pos,
                                         tok)
                return ck, cv, ksc, vsc, pos, tok, first

            in_specs = (specs, _SLOT_CACHE_SPEC, _SLOT_CACHE_SPEC,
                        _SLOT_SCALE_SPEC, _SLOT_SCALE_SPEC,
                        _SLOT_VEC_SPEC, _SLOT_VEC_SPEC,
                        P("data", None), _SLOT_VEC_SPEC,
                        _SLOT_VEC_SPEC, _SLOT_VEC_SPEC, P())
            out_specs = (_SLOT_CACHE_SPEC, _SLOT_CACHE_SPEC,
                         _SLOT_SCALE_SPEC, _SLOT_SCALE_SPEC,
                         _SLOT_VEC_SPEC, _SLOT_VEC_SPEC,
                         _SLOT_VEC_SPEC)

    return _jit_program(run, "chunked_prefill", mesh, in_specs,
                        out_specs)


# ---------------------------------------------------------------------------
# paged slot KV cache: fixed page pool + per-slot block tables (ISSUE-7)
# ---------------------------------------------------------------------------
#
# The contiguous pool above reserves every slot's full [S] token budget
# up front. The paged layout instead keeps ONE pool of
# `page_size`-token pages — [L, NP, page_size, D], heads over 'model'
# — addressed through a per-slot block table ([Ns, max_pages] int32 of
# physical page indices, HOST-owned and passed as runtime data, so the
# bucket-keyed compiled-program caches stay warm: remapping a page is
# an index edit, never a recompile). Physical page 0 is a reserved
# SCRATCH page: masked/inactive writes are routed there so the scatter
# shape stays static with no duplicate-index hazard on live pages
# (scratch content is never attended — the position mask covers it).
#
# Sharding: the page pool is the one structure slots SHARE, so the
# slot axis cannot shard over 'data' without cross-rank page
# ownership; paged programs therefore require a data=1 (tensor-
# parallel-only) serving mesh — the multi-host fleet work (ROADMAP)
# is where data-axis scaling of paged serving lands. Heads/MLP shard
# over 'model' exactly as the contiguous path; quantized-KV scale
# planes [L, NP, page_size, tp] keep quant/kv.py's per-model-rank
# layout.
#
# Token-exactness obligations (tests/test_serving_paged.py):
# - decode mirrors _local_block_decode_slotted(_q) with the gathered
#   page view standing in for the contiguous cache plane — same
#   values at the same logical positions, same einsum/softmax
#   numerics, so greedy decode is byte-identical to the contiguous
#   engine.
# - prefill is TWO-PIECE: the suffix (tokens not covered by a prefix-
#   cache hit) attends itself in float exactly as
#   _local_block_prefill's dot_product_attention does, PLUS the
#   gathered cache view masked to the shared prefix. With no hit the
#   cache piece is fully masked (exact zeros), reproducing the
#   contiguous prefill bit for bit — including int8-KV mode, where
#   contiguous prefill also attends float and quantizes on store.
#   With a hit, float-KV mode reads back the identical f32 rows the
#   shared prefill wrote, so outputs still match the contiguous run;
#   int8-KV prefix hits re-read the prefix through its quantization
#   (same error envelope as int8 decode — documented approximation).

_PAGE_POOL_SPEC = P(None, None, None, "model")    # [L, NP, ps, D]
_PAGE_SCALE_SPEC = P(None, None, None, "model")   # [L, NP, ps, tp]
_PAGE_VEC_SPEC = P(None)                          # per-slot scalars
_PAGE_BT_SPEC = P(None, None)                     # [Ns, max_pages]


def _check_paged_mesh(cfg: TransformerConfig, mesh: Mesh, top_k: int,
                      top_p: float, page_size: int, num_pages: int,
                      max_pages: int):
    """Paged-program validation: contiguous checks + data=1 (pages are
    shared across slots; a sharded slot axis would need cross-rank
    page ownership). Returns tp."""
    tp, dp = _check_serving_mesh(cfg, mesh, top_k, top_p)
    if dp != 1:
        raise ValueError(
            f"paged KV serving requires a data=1 mesh (got data={dp}): "
            "pages are shared across slots, which a 'data'-sharded "
            "slot axis cannot address")
    if page_size < 1:
        raise ValueError(f"page_size must be >= 1, got {page_size}")
    if num_pages < 2:
        raise ValueError(f"num_pages must be >= 2 (page 0 is the "
                         f"reserved scratch page), got {num_pages}")
    if max_pages * page_size < cfg.max_len:
        raise ValueError(
            f"block table of {max_pages} pages x {page_size} tokens "
            f"cannot address max_len={cfg.max_len}")
    return tp


def init_paged_state(cfg: TransformerConfig, mesh: Mesh,
                     num_slots: int, page_size: int, num_pages: int,
                     kv_mode=None, cache_dtype=None):
    """Allocate the persistent PAGED pool state on the serving mesh:
    (kp, vp, pos, tok) with kp/vp [L, num_pages, page_size, D] (heads
    over 'model'), or the 6-tuple (kp, vp, kscale, vscale, pos, tok)
    when ``kv_mode`` selects the quantized pool (quant/kv.py). The
    block table is NOT part of the device state: it is host-owned
    runtime data (the engine passes it per call), so page remapping —
    prefix sharing, copy-on-write, free-list recycling — never touches
    a compiled program's geometry."""
    from deeplearning4j_tpu.models.transformer import page_pool_shape
    _, kv_mode = _resolve_quant(None, kv_mode)
    if kv_mode is not None:
        from deeplearning4j_tpu.quant.kv import init_paged_quant_state
        return init_paged_quant_state(cfg, mesh, num_slots, page_size,
                                      num_pages, kv_mode)
    dt = (cache_dtype if cache_dtype is not None
          else cfg.cache_jnp_dtype())
    shape = page_pool_shape(cfg, num_pages, page_size)
    kv_sh = NamedSharding(mesh, _PAGE_POOL_SPEC)
    vec_sh = NamedSharding(mesh, _PAGE_VEC_SPEC)
    kp = jax.device_put(jnp.zeros(shape, dt), kv_sh)
    vp = jax.device_put(jnp.zeros(shape, dt), kv_sh)
    pos = jax.device_put(jnp.zeros((num_slots,), jnp.int32), vec_sh)
    tok = jax.device_put(jnp.zeros((num_slots,), jnp.int32), vec_sh)
    return kp, vp, pos, tok


def _gather_pages(plane, bt, ns: int, s_view: int):
    """[NP, ps, D_loc] plane -> the block-table-ordered logical view
    [Ns, s_view, D_loc]: unallocated table entries read the scratch
    page; the caller's position mask keeps them out of attention."""
    with jax.named_scope("gather_pages"):
        g = plane[bt]                   # [Ns, mp, ps, D_loc]
        return g.reshape(ns, s_view, g.shape[-1])


def _local_block_decode_paged(h, p, kp, vp, bt, layer: int, pos, act,
                              cfg: TransformerConfig, tp: int, dp: int,
                              page_size: int):
    """One TP block, one new position per slot, PAGED storage: the K/V
    row lands at (bt[slot, pos//ps], pos%ps) — inactive slots write the
    scratch page — and attention runs over the gathered logical view.
    Deliberately mirrors _local_block_decode_slotted's math (the
    gathered view holds the same values at the same logical positions,
    and attention goes through the same
    `ops/flash_decode.decode_attention` primitive over the gathered
    view), so paged greedy decode is byte-identical to the contiguous
    pool."""
    from deeplearning4j_tpu.ops.flash_decode import decode_attention
    g_model = _g_sync("model")
    h_loc = cfg.n_heads // tp
    d_loc = h_loc * cfg.d_head
    ns = h.shape[0]
    mp = bt.shape[1]
    s_view = mp * page_size
    x = layer_norm(h, p["ln1g"], p["ln1b"], cfg.eps)
    q = jnp.matmul(x[:, 0], p["Wq"].astype(x.dtype)) \
        .reshape(ns, h_loc, cfg.d_head)
    k = jnp.matmul(x[:, 0], p["Wk"].astype(x.dtype))      # [Ns, D_loc]
    v = jnp.matmul(x[:, 0], p["Wv"].astype(x.dtype))
    rows = jnp.arange(ns)
    wp = jnp.clip(pos, 0, s_view - 1)
    lp = jnp.clip(wp // page_size, 0, mp - 1)
    pg = jnp.where(act, bt[rows, lp], 0)     # inactive -> scratch
    off = wp % page_size
    kp = kp.at[layer, pg, off].set(k.astype(kp.dtype))
    vp = vp.at[layer, pg, off].set(v.astype(vp.dtype))
    kh = _gather_pages(kp[layer], bt, ns, s_view)    # [Ns, S_view, Dl]
    vh = _gather_pages(vp[layer], bt, ns, s_view)
    a = decode_attention(q, kh, vh, wp, n_heads=h_loc)
    h = h + g_model(jnp.matmul(a.reshape(ns, 1, d_loc),
                               p["Wo"].astype(h.dtype)))
    x = layer_norm(h, p["ln2g"], p["ln2b"], cfg.eps)
    h = _local_mlp(h, x, p, cfg, dp, g_model)
    return h, kp, vp


def _local_block_decode_paged_q(h, p, kp, vp, ksc, vsc, bt, layer: int,
                                pos, act, cfg: TransformerConfig,
                                tp: int, dp: int, page_size: int,
                                kv_mode: str):
    """Quantized-KV paged decode block: quantize-on-write into the
    int8/fp8 page pool + parallel scale planes, scales folded into
    scores/probabilities through the same
    `decode_attention(k_scale=, v_scale=)` call as
    _local_block_decode_slotted_q."""
    from deeplearning4j_tpu.ops.flash_decode import decode_attention
    from deeplearning4j_tpu.quant.kv import quantize_rows
    g_model = _g_sync("model")
    h_loc = cfg.n_heads // tp
    d_loc = h_loc * cfg.d_head
    ns = h.shape[0]
    mp = bt.shape[1]
    s_view = mp * page_size
    x = layer_norm(h, p["ln1g"], p["ln1b"], cfg.eps)
    q = jnp.matmul(x[:, 0], p["Wq"].astype(x.dtype)) \
        .reshape(ns, h_loc, cfg.d_head)
    k = jnp.matmul(x[:, 0], p["Wk"].astype(x.dtype))      # [Ns, D_loc]
    v = jnp.matmul(x[:, 0], p["Wv"].astype(x.dtype))
    rows = jnp.arange(ns)
    wp = jnp.clip(pos, 0, s_view - 1)
    lp = jnp.clip(wp // page_size, 0, mp - 1)
    pg = jnp.where(act, bt[rows, lp], 0)     # inactive -> scratch
    off = wp % page_size
    kq, ksr = quantize_rows(k, kv_mode)
    vq, vsr = quantize_rows(v, kv_mode)
    kp = kp.at[layer, pg, off].set(kq)
    vp = vp.at[layer, pg, off].set(vq)
    ksc = ksc.at[layer, pg, off, 0].set(ksr)
    vsc = vsc.at[layer, pg, off, 0].set(vsr)
    kh = _gather_pages(kp[layer].astype(jnp.float32), bt, ns, s_view)
    vh = _gather_pages(vp[layer].astype(jnp.float32), bt, ns, s_view)
    ksg = _gather_pages(ksc[layer], bt, ns, s_view)[..., 0]
    vsg = _gather_pages(vsc[layer], bt, ns, s_view)[..., 0]
    a = decode_attention(q, kh, vh, wp, n_heads=h_loc, k_scale=ksg,
                         v_scale=vsg)
    h = h + g_model(jnp.matmul(a.reshape(ns, 1, d_loc),
                               p["Wo"].astype(h.dtype)))
    x = layer_norm(h, p["ln2g"], p["ln2b"], cfg.eps)
    h = _local_mlp(h, x, p, cfg, dp, g_model)
    return h, kp, vp, ksc, vsc


def make_paged_prefill(cfg: TransformerConfig, mesh: Mesh,
                       bucket_len: int, num_slots: int, page_size: int,
                       max_pages: int, num_pages: int,
                       temperature: float = 0.0, top_k: int = 0,
                       top_p: float = 1.0, quantized=None,
                       kv_mode=None, chunked: bool = False,
                       constrain: bool = False):
    """Compiled PAGED admission prefill: (params, kp, vp, pos, tok,
    bt [Ns, max_pages], suffix [Ns, Tb], slen [Ns], start [Ns], key)
    -> (kp, vp, pos, tok, first [Ns]).

    ``suffix`` holds each admitted slot's NOT-YET-CACHED token tail
    (the full prefix when there is no prefix-cache hit), right-padded
    to the suffix bucket Tb; ``start[i]`` is the number of prefix
    tokens whose K/V the host already mapped into the slot's block
    table (a radix-cache hit — prefill RESUMES from that boundary, so
    shared system prompts share the prefill compute, not just the
    bytes). Suffix K/V rows are written to the slot's pages at
    absolute positions start+t; attention per suffix query t is the
    cached prefix (gathered pages, masked to s < start) plus causal
    float self-attention within the suffix — exactly
    _local_block_prefill's numerics when start == 0 (the cache piece
    contributes exact zeros), which is what keeps the paged engine
    token-identical to the contiguous one, int8-KV included. Slots
    with slen == 0 pass through untouched.

    ``kv_mode`` switches to the quantized page pool — the state grows
    scale planes ((params, kp, vp, ksc, vsc, pos, tok, bt, suffix,
    slen, start, key) -> (..., first)) and suffix rows quantize on
    write while the suffix still attends itself in float (mirroring
    the contiguous quant prefill, which also stores quantized but
    attends the float activations).

    ``chunked`` (ISSUE-10, see `make_paged_chunked_prefill`)
    generalizes the prefix-hit resume to ARBITRARY chunk boundaries:
    the signature grows a ``last`` [Ns] bool before the key, ``start``
    may be any mid-prompt position (not just a page-aligned cache-hit
    boundary — the attention math is already position-general), and
    only chunks with ``last`` set sample/commit the first generated
    token; mid-prompt chunks advance pos and report first = -1."""
    from deeplearning4j_tpu.ops.flash_decode import NEG_INF
    tp = _check_paged_mesh(cfg, mesh, top_k, top_p, page_size,
                           num_pages, max_pages)
    dp = 1
    quantized, kv_mode = _resolve_quant(quantized, kv_mode)
    if not 0 < bucket_len <= cfg.max_len:
        raise ValueError(f"bucket_len {bucket_len} out of "
                         f"(0, {cfg.max_len}]")
    specs = _serving_specs(cfg, quantized)
    h_loc = cfg.n_heads // tp
    d_loc = h_loc * cfg.d_head
    s_view = max_pages * page_size
    scale = cfg.d_head ** -0.5

    def body(params, kp, vp, ksc, vsc, bt, suffix, slen, start, key,
             allow=None):
        dt = cfg.activation_dtype()
        acc = jnp.promote_types(dt, jnp.float32)
        ns, tb = suffix.shape
        admit = slen > 0
        absp = start[:, None] + jnp.arange(tb)[None, :]   # [Ns, Tb]
        valid = jnp.arange(tb)[None, :] < slen[:, None]
        pe = params["pos"].astype(dt)[
            jnp.clip(absp, 0, cfg.max_len - 1)]
        h = params["embed"].astype(dt)[suffix] + pe
        # write targets: pad/unadmitted rows -> scratch page 0
        lp = jnp.clip(absp // page_size, 0, max_pages - 1)
        pg = jnp.where(valid, jnp.take_along_axis(bt, lp, axis=1), 0)
        off = absp % page_size
        mvalid = valid if cfg.n_experts > 0 else None
        causal = (jnp.arange(tb)[None, :]
                  <= jnp.arange(tb)[:, None])             # [Tb, Tb]
        pmask = (jnp.arange(s_view)[None, None, None, :]
                 < start[:, None, None, None])            # [Ns,1,1,S]
        for layer in range(cfg.n_layers):
            p = {kk: vv[layer] for kk, vv in params["blocks"].items()}
            x = layer_norm(h, p["ln1g"], p["ln1b"], cfg.eps)
            q = jnp.matmul(x, p["Wq"].astype(x.dtype)) \
                .reshape(ns, tb, h_loc, cfg.d_head)
            k = jnp.matmul(x, p["Wk"].astype(x.dtype))    # [Ns,Tb,Dl]
            v = jnp.matmul(x, p["Wv"].astype(x.dtype))
            # store the suffix rows (quantize-on-write in kv_mode)
            if kv_mode is None:
                kp = kp.at[layer, pg, off].set(k.astype(kp.dtype))
                vp = vp.at[layer, pg, off].set(v.astype(vp.dtype))
            else:
                from deeplearning4j_tpu.quant.kv import quantize_rows
                kq, ksr = quantize_rows(k, kv_mode)
                vq, vsr = quantize_rows(v, kv_mode)
                kp = kp.at[layer, pg, off].set(kq)
                vp = vp.at[layer, pg, off].set(vq)
                ksc = ksc.at[layer, pg, off, 0].set(ksr)
                vsc = vsc.at[layer, pg, off, 0].set(vsr)
            kv4 = k.reshape(ns, tb, h_loc, cfg.d_head)
            vv4 = v.reshape(ns, tb, h_loc, cfg.d_head)
            # piece 2: float causal self-attention within the suffix —
            # bitwise dot_product_attention(q, k, v, causal=True)
            sc2 = jnp.einsum("bthd,bshd->bhts", q, kv4,
                             preferred_element_type=acc) * scale
            sc2 = jnp.where(causal[None, None], sc2, NEG_INF)
            # piece 1: the cached prefix, gathered from the pages and
            # masked to s < start (fully masked when there is no hit)
            if kv_mode is None:
                kh = _gather_pages(kp[layer], bt, ns, s_view) \
                    .reshape(ns, s_view, h_loc, cfg.d_head)
                vh = _gather_pages(vp[layer], bt, ns, s_view) \
                    .reshape(ns, s_view, h_loc, cfg.d_head)
                sc1 = jnp.einsum("bthd,bshd->bhts", q, kh,
                                 preferred_element_type=acc) * scale
            else:
                kh = _gather_pages(kp[layer].astype(jnp.float32), bt,
                                   ns, s_view) \
                    .reshape(ns, s_view, h_loc, cfg.d_head)
                vh = _gather_pages(vp[layer].astype(jnp.float32), bt,
                                   ns, s_view) \
                    .reshape(ns, s_view, h_loc, cfg.d_head)
                ksg = _gather_pages(ksc[layer], bt, ns, s_view)[..., 0]
                vsg = _gather_pages(vsc[layer], bt, ns, s_view)[..., 0]
                sc1 = jnp.einsum("bthd,bshd->bhts",
                                 q.astype(jnp.float32), kh) \
                    * ksg[:, None, None, :] * scale
            sc1 = jnp.where(pmask, sc1, NEG_INF)
            # one softmax over [prefix-view | suffix] keys (logical
            # order preserved: prefix positions first), then the two
            # value pieces recombine — exact zeros where masked
            w = jax.nn.softmax(
                jnp.concatenate([sc1.astype(acc), sc2], axis=-1),
                axis=-1)
            w1, w2 = w[..., :s_view], w[..., s_view:]
            if kv_mode is None:
                a1 = jnp.einsum("bhts,bshd->bthd", w1.astype(vh.dtype),
                                vh)
            else:
                a1 = jnp.einsum("bhts,bshd->bthd",
                                w1 * vsg[:, None, None, :], vh) \
                    .astype(v.dtype)
            a2 = jnp.einsum("bhts,bshd->bthd", w2.astype(v.dtype), vv4)
            a = (a1 + a2).reshape(ns, tb, d_loc)
            h = h + _g_sync("model")(
                jnp.matmul(a, p["Wo"].astype(a.dtype)))
            x = layer_norm(h, p["ln2g"], p["ln2b"], cfg.eps)
            h = _local_mlp(h, x, p, cfg, dp, _g_sync("model"),
                           valid=mvalid)
        h = layer_norm(h, params["lnfg"], params["lnfb"], cfg.eps)
        last = h[jnp.arange(ns), jnp.clip(slen - 1, 0, tb - 1)]
        logits = jnp.matmul(last, params["Wout"].astype(last.dtype))
        if allow is not None:
            logits = _mask_allow(logits, allow)
        plen = start + slen
        first = _sample_slots(logits, plen, key, dp, temperature,
                              top_k, top_p)
        return admit, plen, first, kp, vp, ksc, vsc

    def finish(admit, plen, first, pos, tok, lastf=None):
        # chunked: only the prompt's FINAL chunk commits the sampled
        # first token; mid-prompt chunks advance pos only
        take = admit if lastf is None else (admit & lastf)
        pos = jnp.where(admit, plen.astype(pos.dtype), pos)
        tok = jnp.where(take, first, tok)
        return pos, tok, jnp.where(take, first,
                                   jnp.asarray(-1, jnp.int32))

    def c_advance(take, ds0, ctrans, first):
        # advance the DFA only where a first token was committed; the
        # sample was already masked by callow[ds0], so first is legal
        return jnp.where(take, ctrans[ds0, jnp.maximum(first, 0)], ds0)

    _CEXT = (_CTAB_SPEC, _CTAB_SPEC, _PAGE_VEC_SPEC, _PAGE_VEC_SPEC,
             _PAGE_VEC_SPEC)

    if kv_mode is None:
        if chunked:
            if constrain:
                def run(params, kp, vp, pos, tok, bt, suffix, slen,
                        start, last, callow, ctrans, cstate, cseed,
                        cseedval, key):
                    ds0 = _c_start(cstate, cseed, cseedval)
                    admit, plen, first, kp, vp, _, _ = body(
                        params, kp, vp, None, None, bt, suffix, slen,
                        start, key, allow=callow[ds0])
                    pos, tok, first = finish(admit, plen, first, pos,
                                             tok, last)
                    ds = c_advance(admit & last, ds0, ctrans, first)
                    return kp, vp, pos, tok, first, ds

                in_specs = (specs, _PAGE_POOL_SPEC, _PAGE_POOL_SPEC,
                            _PAGE_VEC_SPEC, _PAGE_VEC_SPEC,
                            _PAGE_BT_SPEC, P(None, None),
                            _PAGE_VEC_SPEC, _PAGE_VEC_SPEC,
                            _PAGE_VEC_SPEC) + _CEXT + (P(),)
            else:
                def run(params, kp, vp, pos, tok, bt, suffix, slen,
                        start, last, key):
                    admit, plen, first, kp, vp, _, _ = body(
                        params, kp, vp, None, None, bt, suffix, slen,
                        start, key)
                    pos, tok, first = finish(admit, plen, first, pos,
                                             tok, last)
                    return kp, vp, pos, tok, first

                in_specs = (specs, _PAGE_POOL_SPEC, _PAGE_POOL_SPEC,
                            _PAGE_VEC_SPEC, _PAGE_VEC_SPEC,
                            _PAGE_BT_SPEC, P(None, None),
                            _PAGE_VEC_SPEC, _PAGE_VEC_SPEC,
                            _PAGE_VEC_SPEC, P())
        else:
            if constrain:
                def run(params, kp, vp, pos, tok, bt, suffix, slen,
                        start, callow, ctrans, cstate, cseed, cseedval,
                        key):
                    ds0 = _c_start(cstate, cseed, cseedval)
                    admit, plen, first, kp, vp, _, _ = body(
                        params, kp, vp, None, None, bt, suffix, slen,
                        start, key, allow=callow[ds0])
                    pos, tok, first = finish(admit, plen, first, pos,
                                             tok)
                    ds = c_advance(admit, ds0, ctrans, first)
                    return kp, vp, pos, tok, first, ds

                in_specs = (specs, _PAGE_POOL_SPEC, _PAGE_POOL_SPEC,
                            _PAGE_VEC_SPEC, _PAGE_VEC_SPEC,
                            _PAGE_BT_SPEC, P(None, None),
                            _PAGE_VEC_SPEC, _PAGE_VEC_SPEC) \
                    + _CEXT + (P(),)
            else:
                def run(params, kp, vp, pos, tok, bt, suffix, slen,
                        start, key):
                    admit, plen, first, kp, vp, _, _ = body(
                        params, kp, vp, None, None, bt, suffix, slen,
                        start, key)
                    pos, tok, first = finish(admit, plen, first, pos,
                                             tok)
                    return kp, vp, pos, tok, first

                in_specs = (specs, _PAGE_POOL_SPEC, _PAGE_POOL_SPEC,
                            _PAGE_VEC_SPEC, _PAGE_VEC_SPEC,
                            _PAGE_BT_SPEC, P(None, None),
                            _PAGE_VEC_SPEC, _PAGE_VEC_SPEC, P())
        out_specs = (_PAGE_POOL_SPEC, _PAGE_POOL_SPEC, _PAGE_VEC_SPEC,
                     _PAGE_VEC_SPEC, _PAGE_VEC_SPEC)
    else:
        if chunked:
            if constrain:
                def run(params, kp, vp, ksc, vsc, pos, tok, bt,
                        suffix, slen, start, last, callow, ctrans,
                        cstate, cseed, cseedval, key):
                    ds0 = _c_start(cstate, cseed, cseedval)
                    admit, plen, first, kp, vp, ksc, vsc = body(
                        params, kp, vp, ksc, vsc, bt, suffix, slen,
                        start, key, allow=callow[ds0])
                    pos, tok, first = finish(admit, plen, first, pos,
                                             tok, last)
                    ds = c_advance(admit & last, ds0, ctrans, first)
                    return kp, vp, ksc, vsc, pos, tok, first, ds

                in_specs = (specs, _PAGE_POOL_SPEC, _PAGE_POOL_SPEC,
                            _PAGE_SCALE_SPEC, _PAGE_SCALE_SPEC,
                            _PAGE_VEC_SPEC, _PAGE_VEC_SPEC,
                            _PAGE_BT_SPEC, P(None, None),
                            _PAGE_VEC_SPEC, _PAGE_VEC_SPEC,
                            _PAGE_VEC_SPEC) + _CEXT + (P(),)
            else:
                def run(params, kp, vp, ksc, vsc, pos, tok, bt,
                        suffix, slen, start, last, key):
                    admit, plen, first, kp, vp, ksc, vsc = body(
                        params, kp, vp, ksc, vsc, bt, suffix, slen,
                        start, key)
                    pos, tok, first = finish(admit, plen, first, pos,
                                             tok, last)
                    return kp, vp, ksc, vsc, pos, tok, first

                in_specs = (specs, _PAGE_POOL_SPEC, _PAGE_POOL_SPEC,
                            _PAGE_SCALE_SPEC, _PAGE_SCALE_SPEC,
                            _PAGE_VEC_SPEC, _PAGE_VEC_SPEC,
                            _PAGE_BT_SPEC, P(None, None),
                            _PAGE_VEC_SPEC, _PAGE_VEC_SPEC,
                            _PAGE_VEC_SPEC, P())
        else:
            if constrain:
                def run(params, kp, vp, ksc, vsc, pos, tok, bt,
                        suffix, slen, start, callow, ctrans, cstate,
                        cseed, cseedval, key):
                    ds0 = _c_start(cstate, cseed, cseedval)
                    admit, plen, first, kp, vp, ksc, vsc = body(
                        params, kp, vp, ksc, vsc, bt, suffix, slen,
                        start, key, allow=callow[ds0])
                    pos, tok, first = finish(admit, plen, first, pos,
                                             tok)
                    ds = c_advance(admit, ds0, ctrans, first)
                    return kp, vp, ksc, vsc, pos, tok, first, ds

                in_specs = (specs, _PAGE_POOL_SPEC, _PAGE_POOL_SPEC,
                            _PAGE_SCALE_SPEC, _PAGE_SCALE_SPEC,
                            _PAGE_VEC_SPEC, _PAGE_VEC_SPEC,
                            _PAGE_BT_SPEC, P(None, None),
                            _PAGE_VEC_SPEC, _PAGE_VEC_SPEC) \
                    + _CEXT + (P(),)
            else:
                def run(params, kp, vp, ksc, vsc, pos, tok, bt,
                        suffix, slen, start, key):
                    admit, plen, first, kp, vp, ksc, vsc = body(
                        params, kp, vp, ksc, vsc, bt, suffix, slen,
                        start, key)
                    pos, tok, first = finish(admit, plen, first, pos,
                                             tok)
                    return kp, vp, ksc, vsc, pos, tok, first

                in_specs = (specs, _PAGE_POOL_SPEC, _PAGE_POOL_SPEC,
                            _PAGE_SCALE_SPEC, _PAGE_SCALE_SPEC,
                            _PAGE_VEC_SPEC, _PAGE_VEC_SPEC,
                            _PAGE_BT_SPEC, P(None, None),
                            _PAGE_VEC_SPEC, _PAGE_VEC_SPEC, P())
        out_specs = (_PAGE_POOL_SPEC, _PAGE_POOL_SPEC,
                     _PAGE_SCALE_SPEC, _PAGE_SCALE_SPEC,
                     _PAGE_VEC_SPEC, _PAGE_VEC_SPEC, _PAGE_VEC_SPEC)
    if constrain:
        out_specs = out_specs + (_PAGE_VEC_SPEC,)

    return _jit_program(run, "paged_chunked_prefill" if chunked
                        else "paged_prefill", mesh, in_specs,
                        out_specs)


def make_paged_chunked_prefill(cfg: TransformerConfig, mesh: Mesh,
                               chunk_len: int, num_slots: int,
                               page_size: int, max_pages: int,
                               num_pages: int, temperature: float = 0.0,
                               top_k: int = 0, top_p: float = 1.0,
                               quantized=None, kv_mode=None,
                               constrain: bool = False):
    """Paged twin of `make_chunked_prefill`: (params, kp, vp[, kscale,
    vscale], pos, tok, bt [Ns, max_pages], toks [Ns, C], clen [Ns],
    start [Ns], last [Ns] bool, key) -> (state', pos, tok, first).

    The paged prefill's two-piece attention already resumes from an
    arbitrary per-slot ``start`` as runtime data — the prefix-cache
    hit boundary was just its only caller — so the chunked variant IS
    `make_paged_prefill` with the chunk as the "suffix" plus the
    ``last`` flag gating first-token commitment. Chunk K/V rows land
    at (bt[slot, (start+t)//ps], (start+t)%ps); invalid rows route to
    the scratch page exactly as the one-shot paged prefill's pad rows
    do."""
    return make_paged_prefill(cfg, mesh, chunk_len, num_slots,
                              page_size, max_pages, num_pages,
                              temperature=temperature, top_k=top_k,
                              top_p=top_p, quantized=quantized,
                              kv_mode=kv_mode, chunked=True,
                              constrain=constrain)


def make_paged_decode(cfg: TransformerConfig, mesh: Mesh, chunk: int,
                      num_slots: int, page_size: int, max_pages: int,
                      num_pages: int, temperature: float = 0.0,
                      top_k: int = 0, top_p: float = 1.0,
                      quantized=None, kv_mode=None,
                      constrain: bool = False):
    """Compiled PAGED decode chunk: (params, kp, vp, pos, tok,
    bt [Ns, max_pages], active [Ns], rem [Ns], key) -> (kp, vp, pos,
    tok, toks [Ns, chunk]). Contract identical to
    make_continuous_decode — active/rem/pos AND the block table are
    runtime data, one compiled program per (chunk, num_slots,
    page geometry) — with K/V rows landing in block-table pages
    instead of contiguous slot rows. ``kv_mode`` adds the scale
    planes to the state exactly as the contiguous quant path."""
    tp = _check_paged_mesh(cfg, mesh, top_k, top_p, page_size,
                           num_pages, max_pages)
    dp = 1
    quantized, kv_mode = _resolve_quant(quantized, kv_mode)
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    specs = _serving_specs(cfg, quantized)

    def sample_and_advance(params, h, act, pos, tok, rem, key,
                           ds=None, callow=None, ctrans=None):
        h = layer_norm(h, params["lnfg"], params["lnfb"], cfg.eps)
        logits = jnp.matmul(h[:, 0], params["Wout"].astype(h.dtype))
        if callow is not None:
            logits = _mask_allow(logits, callow[ds])
        nxt = _sample_slots(logits, pos + 1, key, dp, temperature,
                            top_k, top_p)
        if callow is not None:
            ds = jnp.where(act, ctrans[ds, nxt], ds)
        tok = jnp.where(act, nxt, tok)
        emit = jnp.where(act, nxt, jnp.asarray(-1, jnp.int32))
        pos = jnp.where(act, pos + 1, pos)
        rem = jnp.where(act, rem - 1, rem)
        return pos, tok, rem, emit, ds

    def embed_step(params, pos, tok):
        dt = cfg.activation_dtype()
        emb = params["embed"].astype(dt)[tok]
        pv = params["pos"].astype(dt)[
            jnp.clip(pos, 0, cfg.max_len - 1)]
        return (emb + pv)[:, None, :]

    _CEXT = (_CTAB_SPEC, _CTAB_SPEC, _PAGE_VEC_SPEC, _PAGE_VEC_SPEC,
             _PAGE_VEC_SPEC)

    if kv_mode is None:
        if constrain:
            def run(params, kp, vp, pos, tok, bt, active, rem, callow,
                    ctrans, cstate, cseed, cseedval, key):
                def step(carry, _):
                    kp, vp, pos, tok, rem, ds = carry
                    act = active & (rem > 0)
                    h = embed_step(params, pos, tok)
                    for layer in range(cfg.n_layers):
                        p_l = {kk: vv[layer]
                               for kk, vv in params["blocks"].items()}
                        h, kp, vp = _local_block_decode_paged(
                            h, p_l, kp, vp, bt, layer, pos, act, cfg,
                            tp, dp, page_size)
                    pos, tok, rem, emit, ds = sample_and_advance(
                        params, h, act, pos, tok, rem, key, ds=ds,
                        callow=callow, ctrans=ctrans)
                    return (kp, vp, pos, tok, rem, ds), emit

                ds0 = _c_start(cstate, cseed, cseedval)
                (kp, vp, pos, tok, _, ds), toks = lax.scan(
                    step, (kp, vp, pos, tok, rem, ds0), None,
                    length=chunk)
                return kp, vp, pos, tok, jnp.swapaxes(toks, 0, 1), ds

            in_specs = (specs, _PAGE_POOL_SPEC, _PAGE_POOL_SPEC,
                        _PAGE_VEC_SPEC, _PAGE_VEC_SPEC, _PAGE_BT_SPEC,
                        _PAGE_VEC_SPEC, _PAGE_VEC_SPEC) \
                + _CEXT + (P(),)
            out_specs = (_PAGE_POOL_SPEC, _PAGE_POOL_SPEC,
                         _PAGE_VEC_SPEC, _PAGE_VEC_SPEC, P(None, None),
                         _PAGE_VEC_SPEC)
        else:
            def run(params, kp, vp, pos, tok, bt, active, rem, key):
                def step(carry, _):
                    kp, vp, pos, tok, rem = carry
                    act = active & (rem > 0)
                    h = embed_step(params, pos, tok)
                    for layer in range(cfg.n_layers):
                        p_l = {kk: vv[layer]
                               for kk, vv in params["blocks"].items()}
                        h, kp, vp = _local_block_decode_paged(
                            h, p_l, kp, vp, bt, layer, pos, act, cfg,
                            tp, dp, page_size)
                    pos, tok, rem, emit, _ = sample_and_advance(
                        params, h, act, pos, tok, rem, key)
                    return (kp, vp, pos, tok, rem), emit

                (kp, vp, pos, tok, _), toks = lax.scan(
                    step, (kp, vp, pos, tok, rem), None, length=chunk)
                return kp, vp, pos, tok, jnp.swapaxes(toks, 0, 1)

            in_specs = (specs, _PAGE_POOL_SPEC, _PAGE_POOL_SPEC,
                        _PAGE_VEC_SPEC, _PAGE_VEC_SPEC, _PAGE_BT_SPEC,
                        _PAGE_VEC_SPEC, _PAGE_VEC_SPEC, P())
            out_specs = (_PAGE_POOL_SPEC, _PAGE_POOL_SPEC,
                         _PAGE_VEC_SPEC, _PAGE_VEC_SPEC, P(None, None))
    else:
        if constrain:
            def run(params, kp, vp, ksc, vsc, pos, tok, bt, active,
                    rem, callow, ctrans, cstate, cseed, cseedval, key):
                def step(carry, _):
                    kp, vp, ksc, vsc, pos, tok, rem, ds = carry
                    act = active & (rem > 0)
                    h = embed_step(params, pos, tok)
                    for layer in range(cfg.n_layers):
                        p_l = {kk: vv[layer]
                               for kk, vv in params["blocks"].items()}
                        h, kp, vp, ksc, vsc = \
                            _local_block_decode_paged_q(
                                h, p_l, kp, vp, ksc, vsc, bt, layer,
                                pos, act, cfg, tp, dp, page_size,
                                kv_mode)
                    pos, tok, rem, emit, ds = sample_and_advance(
                        params, h, act, pos, tok, rem, key, ds=ds,
                        callow=callow, ctrans=ctrans)
                    return (kp, vp, ksc, vsc, pos, tok, rem, ds), emit

                ds0 = _c_start(cstate, cseed, cseedval)
                (kp, vp, ksc, vsc, pos, tok, _, ds), toks = lax.scan(
                    step, (kp, vp, ksc, vsc, pos, tok, rem, ds0), None,
                    length=chunk)
                return (kp, vp, ksc, vsc, pos, tok,
                        jnp.swapaxes(toks, 0, 1), ds)

            in_specs = (specs, _PAGE_POOL_SPEC, _PAGE_POOL_SPEC,
                        _PAGE_SCALE_SPEC, _PAGE_SCALE_SPEC,
                        _PAGE_VEC_SPEC, _PAGE_VEC_SPEC, _PAGE_BT_SPEC,
                        _PAGE_VEC_SPEC, _PAGE_VEC_SPEC) \
                + _CEXT + (P(),)
            out_specs = (_PAGE_POOL_SPEC, _PAGE_POOL_SPEC,
                         _PAGE_SCALE_SPEC, _PAGE_SCALE_SPEC,
                         _PAGE_VEC_SPEC, _PAGE_VEC_SPEC, P(None, None),
                         _PAGE_VEC_SPEC)
        else:
            def run(params, kp, vp, ksc, vsc, pos, tok, bt, active,
                    rem, key):
                def step(carry, _):
                    kp, vp, ksc, vsc, pos, tok, rem = carry
                    act = active & (rem > 0)
                    h = embed_step(params, pos, tok)
                    for layer in range(cfg.n_layers):
                        p_l = {kk: vv[layer]
                               for kk, vv in params["blocks"].items()}
                        h, kp, vp, ksc, vsc = \
                            _local_block_decode_paged_q(
                                h, p_l, kp, vp, ksc, vsc, bt, layer,
                                pos, act, cfg, tp, dp, page_size,
                                kv_mode)
                    pos, tok, rem, emit, _ = sample_and_advance(
                        params, h, act, pos, tok, rem, key)
                    return (kp, vp, ksc, vsc, pos, tok, rem), emit

                (kp, vp, ksc, vsc, pos, tok, _), toks = lax.scan(
                    step, (kp, vp, ksc, vsc, pos, tok, rem), None,
                    length=chunk)
                return (kp, vp, ksc, vsc, pos, tok,
                        jnp.swapaxes(toks, 0, 1))

            in_specs = (specs, _PAGE_POOL_SPEC, _PAGE_POOL_SPEC,
                        _PAGE_SCALE_SPEC, _PAGE_SCALE_SPEC,
                        _PAGE_VEC_SPEC, _PAGE_VEC_SPEC, _PAGE_BT_SPEC,
                        _PAGE_VEC_SPEC, _PAGE_VEC_SPEC, P())
            out_specs = (_PAGE_POOL_SPEC, _PAGE_POOL_SPEC,
                         _PAGE_SCALE_SPEC, _PAGE_SCALE_SPEC,
                         _PAGE_VEC_SPEC, _PAGE_VEC_SPEC, P(None, None))

    return _jit_program(run, "paged_decode", mesh, in_specs,
                        out_specs)


# ---------------------------------------------------------------------------
# speculative decoding: draft K tokens, verify them in ONE target pass
# (ISSUE-8)
# ---------------------------------------------------------------------------
#
# Decode is the engine's memory-bound tail: every sequential step pays
# the full weight + KV-prefix bandwidth to emit ONE token per slot.
# A speculative round instead (1) runs K cheap DRAFT steps — the
# int8-quantized weight tree, the model itself ("self"), or an
# early-exit truncation to the first `draft_layers` blocks — proposing
# d_1..d_K per active slot, then (2) runs ONE target-model VERIFY pass
# scoring all K+1 window positions [pending, d_1..d_K] at once, and
# (3) commits the longest accepted prefix plus the target's own token
# at the first divergence (rejection-resampling degenerates to "take
# the target's token" under position-keyed sampling — see below). The
# target pays one pass of bandwidth for up to K+1 committed tokens.
#
# EXACTNESS — stronger than the classic rejection-sampling guarantee:
# the committed token at sequence index j is ALWAYS
# sample(fold_in(key, j), target logits at j) — the verify pass scores
# every window position with the target model and samples it through
# the SAME position-keyed schedule sequential decode uses
# (models/transformer.sample_at_positions), accepting a draft only
# when it EQUALS that sample. By induction every committed token is
# bit-identical to what the non-speculative engine emits at the same
# position under the same seed — greedy AND temperature/top-k/top-p
# sampled, float AND int8 KV, contiguous AND paged — which trivially
# implies the distributional (rejection-sampling) guarantee, and makes
# rollback free: a slot that accepts 3 of 5 drafts simply IS a
# non-speculative slot at its new position.
#
# CACHE SAFETY: draft steps write draft-weight K/V rows at positions
# pos..pos+K-1 (through the ordinary slotted/paged block fns), but the
# verify pass REWRITES rows pos..pos+K with target-weight K/V before
# attending them, so the cache holds pure target K/V for every
# committed position. Rows past the committed prefix (rejected
# drafts) hold target K/V for tokens that never landed — they sit at
# indices >= the new pending position, are never attended (every
# attention mask here is s <= current position), and are overwritten
# in order as real tokens arrive: the same monotone-overwrite argument
# bucket-pad rows rely on. Paged pools route writes past a slot's
# block table (or inactive slots) to the reserved scratch page, and
# the engine's copy-on-write guard privatizes the whole K+1 write
# span before the call — a speculative write can never land on a page
# another slot or the prefix cache references.
#
# SHAPES: one fixed-shape program per (K, num_slots, kv_mode[, page
# geometry]) riding the engine's bucket-keyed compile caches;
# active/rem/poison and per-slot accept counts are runtime data, so
# acceptance variance never recompiles. ``poison`` [Ns] derails the
# drafts on-device ((d+1) mod V — guaranteed != the model's own
# proposal) for deterministic fault-injection
# (ServingFaultInjector.draft_poison_at): verification rejects every
# poisoned draft and the round degrades to one committed token,
# proving a poisoned draft pass cannot corrupt committed KV.
#
# MoE configs are rejected: the expert-capacity cap is a function of
# the tokens-per-call count, so a K+1-token verify pass would bind
# capacity differently than sequential decode and break the
# token-exactness contract (same reason bucket-padded MoE prefill is a
# documented divergence).


def _embed_pending(params, cfg: TransformerConfig, pos, tok):
    """Embed each slot's pending token at its own position — the
    shared first step of every sequential decode/draft step."""
    dt = cfg.activation_dtype()
    emb = params["embed"].astype(dt)[tok]
    pv = params["pos"].astype(dt)[jnp.clip(pos, 0, cfg.max_len - 1)]
    return (emb + pv)[:, None, :]


def _check_spec(cfg: TransformerConfig, spec_k: int, draft_layers: int):
    _refuse_unserved(cfg)
    if spec_k < 1:
        raise ValueError(f"spec_k must be >= 1, got {spec_k}")
    if cfg.n_experts > 0:
        raise ValueError(
            "speculative decoding does not support MoE configs: the "
            "expert-capacity cap depends on the tokens-per-call count, "
            "so a K+1-token verify pass would drop differently than "
            "sequential decode and break token-exactness")
    nd = draft_layers if draft_layers > 0 else cfg.n_layers
    if not 0 < nd <= cfg.n_layers:
        raise ValueError(f"draft_layers {draft_layers} out of "
                         f"(0, {cfg.n_layers}]")
    return nd


def _spec_accept_commit(spec_k: int, drafts, tgt, pos, tok, rem, act):
    """Accept the longest draft prefix matching the target's
    position-keyed samples, commit it plus the target's token at the
    first divergence (or the bonus token after K accepts), capped by
    the slot's remaining budget. Returns (pos', tok', rem', emit
    [Ns, K+1] with -1 past each slot's commit count, ncommit, drafted,
    accepted)."""
    k1 = spec_k + 1
    ns = tok.shape[0]
    rows = jnp.arange(ns)
    zero = jnp.asarray(0, jnp.int32)
    match = (drafts == tgt[:, :spec_k]) & act[:, None]
    # .astype(int32): jnp.sum promotes int32 to the default int, which
    # under jax_enable_x64 silently flips the slot pos/ncommit dtypes
    # to int64 after the first round — a hidden extra jit signature on
    # the lazy path and a hard aval mismatch for an AOT-compiled
    # executable (ISSUE-12). Pin the accept count instead.
    acc = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1),
                  axis=1).astype(jnp.int32)                 # [Ns] 0..K
    c = jnp.where(act, jnp.minimum(acc + 1, rem), zero)
    emit = jnp.where(jnp.arange(k1)[None, :] < c[:, None], tgt,
                     jnp.asarray(-1, jnp.int32))
    last = tgt[rows, jnp.clip(c - 1, 0, spec_k)]
    tok = jnp.where(act, last, tok)
    pos = jnp.where(act, pos + c, pos)
    rem = jnp.where(act, rem - c, rem)
    drafted = jnp.where(act, jnp.asarray(spec_k, jnp.int32), zero)
    accepted = jnp.maximum(c - 1, 0)
    return pos, tok, rem, emit, c, drafted, accepted


def _c_spec_window(spec_k: int, ds0, ctrans, drafts):
    """Constraint states for the K+1 verify-window positions: entry j
    is the DFA state after consuming drafts[:, :j] from ds0, so the
    target sample at window position j is masked by the state the
    masked sequential engine would hold there. Walked from the POST-
    poison drafts: on the accepted prefix drafts equal the committed
    tokens (so the states agree with the sequential walk by
    construction), and positions past the first divergence are never
    committed — a poisoned draft merely yields a scratch state whose
    masked sample the acceptance test then rejects."""
    sw = [ds0]
    for j in range(spec_k):
        sw.append(ctrans[sw[-1], drafts[:, j]])
    return jnp.stack(sw, axis=1)                         # [Ns, K+1]


def _c_spec_final(spec_k: int, swin, ctrans, tgt, c, act, ds0):
    """DFA state after a speculative commit: the state at the last
    committed window position (column c-1 of the window walk) advanced
    by the committed token there (tgt at c-1 — _spec_accept_commit's
    ``last``). Inactive slots keep ds0."""
    rows = jnp.arange(tgt.shape[0])
    j = jnp.clip(c - 1, 0, spec_k)
    return jnp.where(act, ctrans[swin[rows, j], tgt[rows, j]], ds0)


def make_speculative_decode(cfg: TransformerConfig, mesh: Mesh,
                            spec_k: int, num_slots: int,
                            temperature: float = 0.0, top_k: int = 0,
                            top_p: float = 1.0, quantized=None,
                            kv_mode=None, draft_quantized=None,
                            draft_layers: int = 0,
                            constrain: bool = False):
    """Compiled speculative decode round over the CONTIGUOUS slot
    pool: (params, draft_params, ck, cv[, kscale, vscale], pos, tok,
    active [Ns], rem [Ns], poison [Ns], key) -> (state', toks
    [Ns, K+1], ncommit [Ns], drafted [Ns], accepted [Ns]).

    One round advances every active slot 1..K+1 tokens: K draft steps
    with ``draft_params`` (optionally truncated to the first
    ``draft_layers`` blocks — early-exit self-drafting reads/writes
    exactly the layers the target shares, so its shallow K/V rows are
    the target's own) propose the window, one target pass verifies all
    K+1 positions, and the longest accepted prefix + the correction
    token commit (section comment above has the exactness and cache-
    safety arguments). ``toks[i, :ncommit[i]]`` are the committed
    tokens (-1 beyond); ``drafted``/``accepted`` feed the engine's
    acceptance metrics and adaptive-K controller as runtime data.
    ``quantized``/``draft_quantized`` mark the respective param trees;
    ``kv_mode`` selects the quantized slot pool exactly as
    make_continuous_decode."""
    from deeplearning4j_tpu.ops.flash_decode import \
        decode_window_attention
    tp, dp = _check_serving_mesh(cfg, mesh, top_k, top_p)
    quantized, kv_mode = _resolve_quant(quantized, kv_mode)
    draft_quantized, _ = _resolve_quant(draft_quantized, None)
    nd = _check_spec(cfg, spec_k, draft_layers)
    if num_slots % dp:
        raise ValueError(f"num_slots {num_slots} not divisible by "
                         f"data axis {dp}")
    specs = _serving_specs(cfg, quantized)
    dspecs = _serving_specs(cfg, draft_quantized)
    h_loc = cfg.n_heads // tp
    d_loc = h_loc * cfg.d_head
    k1 = spec_k + 1
    scale = cfg.d_head ** -0.5

    def draft_phase(dparams, st, pos, tok, act, key, ds0=None,
                    callow=None, ctrans=None):
        """K sequential draft steps through the ordinary slotted block
        fns (draft K/V rows land in the live cache; verify rewrites
        them with target K/V before any of them is attended). With a
        constraint table, each step masks its proposal by the slot's
        DFA state and advances the state per drafted token — the final
        draft state is scratch (verify recomputes the committed one
        from the accepted prefix)."""
        def dstep(carry, _):
            if callow is None:
                st, dpos, dtok = carry
            else:
                st, dpos, dtok, ds = carry
            h = _embed_pending(dparams, cfg, dpos, dtok)
            for layer in range(nd):
                p_l = {kk: vv[layer]
                       for kk, vv in dparams["blocks"].items()}
                if kv_mode is None:
                    h, ck, cv = _local_block_decode_slotted(
                        h, p_l, st[0], st[1], layer, dpos, act, cfg,
                        tp, dp)
                    st = (ck, cv)
                else:
                    h, ck, cv, ksc, vsc = _local_block_decode_slotted_q(
                        h, p_l, *st, layer, dpos, act, cfg, tp, dp,
                        kv_mode)
                    st = (ck, cv, ksc, vsc)
            h = layer_norm(h, dparams["lnfg"], dparams["lnfb"],
                           cfg.eps)
            logits = jnp.matmul(h[:, 0],
                                dparams["Wout"].astype(h.dtype))
            if callow is not None:
                logits = _mask_allow(logits, callow[ds])
            nxt = _sample_slots(logits, dpos + 1, key, dp, temperature,
                                top_k, top_p)
            dtok = jnp.where(act, nxt, dtok)
            dpos = jnp.where(act, dpos + 1, dpos)
            if callow is None:
                return (st, dpos, dtok), nxt
            ds = jnp.where(act, ctrans[ds, nxt], ds)
            return (st, dpos, dtok, ds), nxt

        if callow is None:
            (st, _, _), drafts = lax.scan(dstep, (st, pos, tok), None,
                                          length=spec_k)
        else:
            (st, _, _, _), drafts = lax.scan(
                dstep, (st, pos, tok, ds0), None, length=spec_k)
        return st, jnp.swapaxes(drafts, 0, 1)            # [Ns, K]

    def verify_phase(params, st, pos, tok, act, drafts, key,
                     allow_w=None):
        """ONE target pass over the K+1-token window [pending,
        d_1..d_K]: per-layer it rewrites the window's cache rows with
        target K/V, then attends each window position to s <= pos+j —
        element-for-element the slotted sequential decode's numerics
        (same einsum contraction, NEG_INF mask, f32 softmax, scale
        folds), batched over the window instead of scanned, which is
        the whole bandwidth win. ``allow_w`` [Ns, K+1, V] re-applies
        the constraint mask per window position (the state reached
        after the preceding window tokens), so acceptance compares
        masked target samples against masked drafts — bit-identical to
        the masked sequential engine."""
        g_model = _g_sync("model")
        ns = tok.shape[0]
        rows = jnp.arange(ns)
        dt = cfg.activation_dtype()
        if kv_mode is None:
            ck, cv = st
        else:
            ck, cv, ksc, vsc = st
        s_max = ck.shape[2]
        win = jnp.concatenate([tok[:, None], drafts], axis=1)
        posw = pos[:, None] + jnp.arange(k1, dtype=pos.dtype)[None, :]
        wp = jnp.clip(posw, 0, s_max - 1)
        h = (params["embed"].astype(dt)[win]
             + params["pos"].astype(dt)[
                 jnp.clip(posw, 0, cfg.max_len - 1)])
        for layer in range(cfg.n_layers):
            p = {kk: vv[layer] for kk, vv in params["blocks"].items()}
            x = layer_norm(h, p["ln1g"], p["ln1b"], cfg.eps)
            q = jnp.matmul(x, p["Wq"].astype(x.dtype)) \
                .reshape(ns, k1, h_loc, cfg.d_head)
            kw = jnp.matmul(x, p["Wk"].astype(x.dtype))  # [Ns,K1,Dl]
            vw = jnp.matmul(x, p["Wv"].astype(x.dtype))
            # window-row rewrite: inactive slots rewrite their current
            # rows with themselves (the static-scatter trick);
            # positions past the cache drop (mode="drop" — they can
            # only be beyond the slot's budget, never committed)
            if kv_mode is None:
                k_wr = jnp.where(act[:, None, None],
                                 kw.astype(ck.dtype),
                                 ck[layer][rows[:, None], wp])
                v_wr = jnp.where(act[:, None, None],
                                 vw.astype(cv.dtype),
                                 cv[layer][rows[:, None], wp])
                ck = ck.at[layer, rows[:, None], posw].set(
                    k_wr, mode="drop")
                cv = cv.at[layer, rows[:, None], posw].set(
                    v_wr, mode="drop")
                # fused K+1-window attention: the STACKED caches ride
                # into the primitive (kernel picks the layer plane in
                # its BlockSpec; jnp reference reproduces the old
                # inline masked-softmax bit-for-bit — flash_decode
                # .reference_window_attention holds the algebra)
                a = decode_window_attention(q, ck, cv, pos, h_loc,
                                            scale, layer=layer)
            else:
                from deeplearning4j_tpu.quant.kv import quantize_rows
                kq, ksr = quantize_rows(kw, kv_mode)
                vq, vsr = quantize_rows(vw, kv_mode)
                k_wr = jnp.where(act[:, None, None], kq,
                                 ck[layer][rows[:, None], wp])
                v_wr = jnp.where(act[:, None, None], vq,
                                 cv[layer][rows[:, None], wp])
                ks_wr = jnp.where(act[:, None], ksr,
                                  ksc[layer][rows[:, None], wp, 0])
                vs_wr = jnp.where(act[:, None], vsr,
                                  vsc[layer][rows[:, None], wp, 0])
                ck = ck.at[layer, rows[:, None], posw].set(
                    k_wr, mode="drop")
                cv = cv.at[layer, rows[:, None], posw].set(
                    v_wr, mode="drop")
                ksc = ksc.at[layer, rows[:, None], posw, 0].set(
                    ks_wr, mode="drop")
                vsc = vsc.at[layer, rows[:, None], posw, 0].set(
                    vs_wr, mode="drop")
                # per-row scale folds travel into the fused window
                # primitive (scores * kscale_s, probs * vscale_s —
                # identical multiplication order)
                a = decode_window_attention(
                    q, ck, cv, pos, h_loc, scale, layer=layer,
                    k_scale=ksc[layer, :, :, 0],
                    v_scale=vsc[layer, :, :, 0])
            h = h + g_model(jnp.matmul(a.reshape(ns, k1, d_loc),
                                       p["Wo"].astype(h.dtype)))
            x = layer_norm(h, p["ln2g"], p["ln2b"], cfg.eps)
            h = _local_mlp(h, x, p, cfg, dp, g_model)
        h = layer_norm(h, params["lnfg"], params["lnfb"], cfg.eps)
        logits = jnp.matmul(h, params["Wout"].astype(h.dtype))
        if allow_w is not None:
            logits = _mask_allow(logits, allow_w)
        tgt = _sample_slots(
            logits.reshape(ns * k1, logits.shape[-1]),
            (posw + 1).reshape(-1), key, dp, temperature, top_k,
            top_p).reshape(ns, k1)
        st = (ck, cv) if kv_mode is None else (ck, cv, ksc, vsc)
        return st, tgt

    def body(params, dparams, st, pos, tok, active, rem, poison, key,
             callow=None, ctrans=None, ds0=None):
        act = active & (rem > 0)
        st, drafts = draft_phase(dparams, st, pos, tok, act, key,
                                 ds0=ds0, callow=callow,
                                 ctrans=ctrans)
        # deterministic draft poisoning (runtime data): (d+1) mod V is
        # guaranteed to differ from the model's own proposal, so
        # verification MUST reject — the fault-injection proof that a
        # bad draft pass cannot corrupt committed state
        drafts = jnp.where(poison[:, None],
                           (drafts + 1) % cfg.vocab_size, drafts)
        if callow is None:
            st, tgt = verify_phase(params, st, pos, tok, act, drafts,
                                   key)
            pos, tok, rem, emit, c, drafted, accepted = \
                _spec_accept_commit(spec_k, drafts, tgt, pos, tok,
                                    rem, act)
            return st, pos, tok, emit, c, drafted, accepted
        swin = _c_spec_window(spec_k, ds0, ctrans, drafts)
        st, tgt = verify_phase(params, st, pos, tok, act, drafts, key,
                               allow_w=callow[swin])
        pos, tok, rem, emit, c, drafted, accepted = \
            _spec_accept_commit(spec_k, drafts, tgt, pos, tok, rem,
                                act)
        ds = _c_spec_final(spec_k, swin, ctrans, tgt, c, act, ds0)
        return st, pos, tok, emit, c, drafted, accepted, ds

    if kv_mode is None:
        if constrain:
            def run(params, dparams, ck, cv, pos, tok, active, rem,
                    poison, callow, ctrans, cstate, cseed, cseedval,
                    key):
                ds0 = _c_start(cstate, cseed, cseedval)
                st, pos, tok, emit, c, drafted, accepted, ds = body(
                    params, dparams, (ck, cv), pos, tok, active, rem,
                    poison, key, callow=callow, ctrans=ctrans,
                    ds0=ds0)
                return (*st, pos, tok, emit, c, drafted, accepted, ds)

            in_specs = (specs, dspecs, _SLOT_CACHE_SPEC,
                        _SLOT_CACHE_SPEC, _SLOT_VEC_SPEC,
                        _SLOT_VEC_SPEC, _SLOT_VEC_SPEC,
                        _SLOT_VEC_SPEC, _SLOT_VEC_SPEC, _CTAB_SPEC,
                        _CTAB_SPEC, _SLOT_VEC_SPEC, _SLOT_VEC_SPEC,
                        _SLOT_VEC_SPEC, P())
            out_specs = (_SLOT_CACHE_SPEC, _SLOT_CACHE_SPEC,
                         _SLOT_VEC_SPEC, _SLOT_VEC_SPEC,
                         P("data", None), _SLOT_VEC_SPEC,
                         _SLOT_VEC_SPEC, _SLOT_VEC_SPEC,
                         _SLOT_VEC_SPEC)
        else:
            def run(params, dparams, ck, cv, pos, tok, active, rem,
                    poison, key):
                st, pos, tok, emit, c, drafted, accepted = body(
                    params, dparams, (ck, cv), pos, tok, active, rem,
                    poison, key)
                return (*st, pos, tok, emit, c, drafted, accepted)

            in_specs = (specs, dspecs, _SLOT_CACHE_SPEC,
                        _SLOT_CACHE_SPEC, _SLOT_VEC_SPEC,
                        _SLOT_VEC_SPEC, _SLOT_VEC_SPEC,
                        _SLOT_VEC_SPEC, _SLOT_VEC_SPEC, P())
            out_specs = (_SLOT_CACHE_SPEC, _SLOT_CACHE_SPEC,
                         _SLOT_VEC_SPEC, _SLOT_VEC_SPEC,
                         P("data", None), _SLOT_VEC_SPEC,
                         _SLOT_VEC_SPEC, _SLOT_VEC_SPEC)
    else:
        if constrain:
            def run(params, dparams, ck, cv, ksc, vsc, pos, tok,
                    active, rem, poison, callow, ctrans, cstate,
                    cseed, cseedval, key):
                ds0 = _c_start(cstate, cseed, cseedval)
                st, pos, tok, emit, c, drafted, accepted, ds = body(
                    params, dparams, (ck, cv, ksc, vsc), pos, tok,
                    active, rem, poison, key, callow=callow,
                    ctrans=ctrans, ds0=ds0)
                return (*st, pos, tok, emit, c, drafted, accepted, ds)

            in_specs = (specs, dspecs, _SLOT_CACHE_SPEC,
                        _SLOT_CACHE_SPEC, _SLOT_SCALE_SPEC,
                        _SLOT_SCALE_SPEC, _SLOT_VEC_SPEC,
                        _SLOT_VEC_SPEC, _SLOT_VEC_SPEC,
                        _SLOT_VEC_SPEC, _SLOT_VEC_SPEC, _CTAB_SPEC,
                        _CTAB_SPEC, _SLOT_VEC_SPEC, _SLOT_VEC_SPEC,
                        _SLOT_VEC_SPEC, P())
            out_specs = (_SLOT_CACHE_SPEC, _SLOT_CACHE_SPEC,
                         _SLOT_SCALE_SPEC, _SLOT_SCALE_SPEC,
                         _SLOT_VEC_SPEC, _SLOT_VEC_SPEC,
                         P("data", None), _SLOT_VEC_SPEC,
                         _SLOT_VEC_SPEC, _SLOT_VEC_SPEC,
                         _SLOT_VEC_SPEC)
        else:
            def run(params, dparams, ck, cv, ksc, vsc, pos, tok,
                    active, rem, poison, key):
                st, pos, tok, emit, c, drafted, accepted = body(
                    params, dparams, (ck, cv, ksc, vsc), pos, tok,
                    active, rem, poison, key)
                return (*st, pos, tok, emit, c, drafted, accepted)

            in_specs = (specs, dspecs, _SLOT_CACHE_SPEC,
                        _SLOT_CACHE_SPEC, _SLOT_SCALE_SPEC,
                        _SLOT_SCALE_SPEC, _SLOT_VEC_SPEC,
                        _SLOT_VEC_SPEC, _SLOT_VEC_SPEC,
                        _SLOT_VEC_SPEC, _SLOT_VEC_SPEC, P())
            out_specs = (_SLOT_CACHE_SPEC, _SLOT_CACHE_SPEC,
                         _SLOT_SCALE_SPEC, _SLOT_SCALE_SPEC,
                         _SLOT_VEC_SPEC, _SLOT_VEC_SPEC,
                         P("data", None), _SLOT_VEC_SPEC,
                         _SLOT_VEC_SPEC, _SLOT_VEC_SPEC)

    return _jit_program(run, "speculative_decode", mesh, in_specs,
                        out_specs)


def make_paged_speculative_decode(cfg: TransformerConfig, mesh: Mesh,
                                  spec_k: int, num_slots: int,
                                  page_size: int, max_pages: int,
                                  num_pages: int,
                                  temperature: float = 0.0,
                                  top_k: int = 0, top_p: float = 1.0,
                                  quantized=None, kv_mode=None,
                                  draft_quantized=None,
                                  draft_layers: int = 0,
                                  constrain: bool = False):
    """Paged-pool speculative round: make_speculative_decode's
    contract with the block table as runtime data — (params,
    draft_params, kp, vp[, kscale, vscale], pos, tok, bt, active, rem,
    poison, key) -> (state', toks, ncommit, drafted, accepted).
    Draft steps go through _local_block_decode_paged(_q); the verify
    window's K/V rows land at (bt[slot, pos_j // ps], pos_j % ps),
    with inactive slots and positions past the slot's mapped pages
    routed to the scratch page (never attended). The engine's
    copy-on-write guard privatizes the whole window's pages before
    the call, so speculative writes are COW-safe by construction."""
    from deeplearning4j_tpu.ops.flash_decode import \
        decode_window_attention
    tp = _check_paged_mesh(cfg, mesh, top_k, top_p, page_size,
                           num_pages, max_pages)
    dp = 1
    quantized, kv_mode = _resolve_quant(quantized, kv_mode)
    draft_quantized, _ = _resolve_quant(draft_quantized, None)
    nd = _check_spec(cfg, spec_k, draft_layers)
    specs = _serving_specs(cfg, quantized)
    dspecs = _serving_specs(cfg, draft_quantized)
    h_loc = cfg.n_heads // tp
    d_loc = h_loc * cfg.d_head
    k1 = spec_k + 1
    s_view = max_pages * page_size
    scale = cfg.d_head ** -0.5

    def draft_phase(dparams, st, bt, pos, tok, act, key, ds0=None,
                    callow=None, ctrans=None):
        def dstep(carry, _):
            if callow is None:
                st, dpos, dtok = carry
            else:
                st, dpos, dtok, ds = carry
            h = _embed_pending(dparams, cfg, dpos, dtok)
            for layer in range(nd):
                p_l = {kk: vv[layer]
                       for kk, vv in dparams["blocks"].items()}
                if kv_mode is None:
                    h, kp, vp = _local_block_decode_paged(
                        h, p_l, st[0], st[1], bt, layer, dpos, act,
                        cfg, tp, dp, page_size)
                    st = (kp, vp)
                else:
                    h, kp, vp, ksc, vsc = _local_block_decode_paged_q(
                        h, p_l, *st, bt, layer, dpos, act, cfg, tp,
                        dp, page_size, kv_mode)
                    st = (kp, vp, ksc, vsc)
            h = layer_norm(h, dparams["lnfg"], dparams["lnfb"],
                           cfg.eps)
            logits = jnp.matmul(h[:, 0],
                                dparams["Wout"].astype(h.dtype))
            if callow is not None:
                logits = _mask_allow(logits, callow[ds])
            nxt = _sample_slots(logits, dpos + 1, key, dp, temperature,
                                top_k, top_p)
            dtok = jnp.where(act, nxt, dtok)
            dpos = jnp.where(act, dpos + 1, dpos)
            if callow is None:
                return (st, dpos, dtok), nxt
            ds = jnp.where(act, ctrans[ds, nxt], ds)
            return (st, dpos, dtok, ds), nxt

        if callow is None:
            (st, _, _), drafts = lax.scan(dstep, (st, pos, tok), None,
                                          length=spec_k)
        else:
            (st, _, _, _), drafts = lax.scan(
                dstep, (st, pos, tok, ds0), None, length=spec_k)
        return st, jnp.swapaxes(drafts, 0, 1)

    def verify_phase(params, st, bt, pos, tok, act, drafts, key,
                     allow_w=None):
        g_model = _g_sync("model")
        ns = tok.shape[0]
        mp = bt.shape[1]
        dt = cfg.activation_dtype()
        if kv_mode is None:
            kp, vp = st
        else:
            kp, vp, ksc, vsc = st
        win = jnp.concatenate([tok[:, None], drafts], axis=1)
        posw = pos[:, None] + jnp.arange(k1, dtype=pos.dtype)[None, :]
        # write routing: inactive slots and positions past the block
        # table land on the scratch page (page 0), like the paged
        # decode/prefill write paths
        lp = jnp.clip(posw // page_size, 0, mp - 1)
        pgw = jnp.where(act[:, None] & (posw < s_view),
                        jnp.take_along_axis(bt, lp, axis=1), 0)
        offw = posw % page_size
        h = (params["embed"].astype(dt)[win]
             + params["pos"].astype(dt)[
                 jnp.clip(posw, 0, cfg.max_len - 1)])
        for layer in range(cfg.n_layers):
            p = {kk: vv[layer] for kk, vv in params["blocks"].items()}
            x = layer_norm(h, p["ln1g"], p["ln1b"], cfg.eps)
            q = jnp.matmul(x, p["Wq"].astype(x.dtype)) \
                .reshape(ns, k1, h_loc, cfg.d_head)
            kw = jnp.matmul(x, p["Wk"].astype(x.dtype))
            vw = jnp.matmul(x, p["Wv"].astype(x.dtype))
            if kv_mode is None:
                kp = kp.at[layer, pgw, offw].set(kw.astype(kp.dtype))
                vp = vp.at[layer, pgw, offw].set(vw.astype(vp.dtype))
                # fused K+1-window attention over the gathered logical
                # view (jnp reference off-TPU reproduces the old
                # inline masked-softmax bit-for-bit; the kernel path
                # DMAs each gathered block once for all window rows)
                kh = _gather_pages(kp[layer], bt, ns, s_view)
                vh = _gather_pages(vp[layer], bt, ns, s_view)
                a = decode_window_attention(q, kh, vh, pos, h_loc,
                                            scale)
            else:
                from deeplearning4j_tpu.quant.kv import quantize_rows
                kq, ksr = quantize_rows(kw, kv_mode)
                vq, vsr = quantize_rows(vw, kv_mode)
                kp = kp.at[layer, pgw, offw].set(kq)
                vp = vp.at[layer, pgw, offw].set(vq)
                ksc = ksc.at[layer, pgw, offw, 0].set(ksr)
                vsc = vsc.at[layer, pgw, offw, 0].set(vsr)
                kh = _gather_pages(kp[layer].astype(jnp.float32), bt,
                                   ns, s_view)
                vh = _gather_pages(vp[layer].astype(jnp.float32), bt,
                                   ns, s_view)
                ksg = _gather_pages(ksc[layer], bt, ns, s_view)[..., 0]
                vsg = _gather_pages(vsc[layer], bt, ns, s_view)[..., 0]
                a = decode_window_attention(q, kh, vh, pos, h_loc,
                                            scale, k_scale=ksg,
                                            v_scale=vsg)
            h = h + g_model(jnp.matmul(a.reshape(ns, k1, d_loc),
                                       p["Wo"].astype(h.dtype)))
            x = layer_norm(h, p["ln2g"], p["ln2b"], cfg.eps)
            h = _local_mlp(h, x, p, cfg, dp, g_model)
        h = layer_norm(h, params["lnfg"], params["lnfb"], cfg.eps)
        logits = jnp.matmul(h, params["Wout"].astype(h.dtype))
        if allow_w is not None:
            logits = _mask_allow(logits, allow_w)
        tgt = _sample_slots(
            logits.reshape(ns * k1, logits.shape[-1]),
            (posw + 1).reshape(-1), key, dp, temperature, top_k,
            top_p).reshape(ns, k1)
        st = (kp, vp) if kv_mode is None else (kp, vp, ksc, vsc)
        return st, tgt

    def body(params, dparams, st, pos, tok, bt, active, rem, poison,
             key, callow=None, ctrans=None, ds0=None):
        act = active & (rem > 0)
        st, drafts = draft_phase(dparams, st, bt, pos, tok, act, key,
                                 ds0=ds0, callow=callow,
                                 ctrans=ctrans)
        drafts = jnp.where(poison[:, None],
                           (drafts + 1) % cfg.vocab_size, drafts)
        if callow is None:
            st, tgt = verify_phase(params, st, bt, pos, tok, act,
                                   drafts, key)
            pos, tok, rem, emit, c, drafted, accepted = \
                _spec_accept_commit(spec_k, drafts, tgt, pos, tok,
                                    rem, act)
            return st, pos, tok, emit, c, drafted, accepted
        swin = _c_spec_window(spec_k, ds0, ctrans, drafts)
        st, tgt = verify_phase(params, st, bt, pos, tok, act, drafts,
                               key, allow_w=callow[swin])
        pos, tok, rem, emit, c, drafted, accepted = \
            _spec_accept_commit(spec_k, drafts, tgt, pos, tok, rem,
                                act)
        ds = _c_spec_final(spec_k, swin, ctrans, tgt, c, act, ds0)
        return st, pos, tok, emit, c, drafted, accepted, ds

    if kv_mode is None:
        if constrain:
            def run(params, dparams, kp, vp, pos, tok, bt, active,
                    rem, poison, callow, ctrans, cstate, cseed,
                    cseedval, key):
                ds0 = _c_start(cstate, cseed, cseedval)
                st, pos, tok, emit, c, drafted, accepted, ds = body(
                    params, dparams, (kp, vp), pos, tok, bt, active,
                    rem, poison, key, callow=callow, ctrans=ctrans,
                    ds0=ds0)
                return (*st, pos, tok, emit, c, drafted, accepted, ds)

            in_specs = (specs, dspecs, _PAGE_POOL_SPEC,
                        _PAGE_POOL_SPEC, _PAGE_VEC_SPEC,
                        _PAGE_VEC_SPEC, _PAGE_BT_SPEC, _PAGE_VEC_SPEC,
                        _PAGE_VEC_SPEC, _PAGE_VEC_SPEC, _CTAB_SPEC,
                        _CTAB_SPEC, _PAGE_VEC_SPEC, _PAGE_VEC_SPEC,
                        _PAGE_VEC_SPEC, P())
            out_specs = (_PAGE_POOL_SPEC, _PAGE_POOL_SPEC,
                         _PAGE_VEC_SPEC, _PAGE_VEC_SPEC, P(None, None),
                         _PAGE_VEC_SPEC, _PAGE_VEC_SPEC,
                         _PAGE_VEC_SPEC, _PAGE_VEC_SPEC)
        else:
            def run(params, dparams, kp, vp, pos, tok, bt, active,
                    rem, poison, key):
                st, pos, tok, emit, c, drafted, accepted = body(
                    params, dparams, (kp, vp), pos, tok, bt, active,
                    rem, poison, key)
                return (*st, pos, tok, emit, c, drafted, accepted)

            in_specs = (specs, dspecs, _PAGE_POOL_SPEC,
                        _PAGE_POOL_SPEC, _PAGE_VEC_SPEC,
                        _PAGE_VEC_SPEC, _PAGE_BT_SPEC, _PAGE_VEC_SPEC,
                        _PAGE_VEC_SPEC, _PAGE_VEC_SPEC, P())
            out_specs = (_PAGE_POOL_SPEC, _PAGE_POOL_SPEC,
                         _PAGE_VEC_SPEC, _PAGE_VEC_SPEC, P(None, None),
                         _PAGE_VEC_SPEC, _PAGE_VEC_SPEC,
                         _PAGE_VEC_SPEC)
    else:
        if constrain:
            def run(params, dparams, kp, vp, ksc, vsc, pos, tok, bt,
                    active, rem, poison, callow, ctrans, cstate,
                    cseed, cseedval, key):
                ds0 = _c_start(cstate, cseed, cseedval)
                st, pos, tok, emit, c, drafted, accepted, ds = body(
                    params, dparams, (kp, vp, ksc, vsc), pos, tok, bt,
                    active, rem, poison, key, callow=callow,
                    ctrans=ctrans, ds0=ds0)
                return (*st, pos, tok, emit, c, drafted, accepted, ds)

            in_specs = (specs, dspecs, _PAGE_POOL_SPEC,
                        _PAGE_POOL_SPEC, _PAGE_SCALE_SPEC,
                        _PAGE_SCALE_SPEC, _PAGE_VEC_SPEC,
                        _PAGE_VEC_SPEC, _PAGE_BT_SPEC, _PAGE_VEC_SPEC,
                        _PAGE_VEC_SPEC, _PAGE_VEC_SPEC, _CTAB_SPEC,
                        _CTAB_SPEC, _PAGE_VEC_SPEC, _PAGE_VEC_SPEC,
                        _PAGE_VEC_SPEC, P())
            out_specs = (_PAGE_POOL_SPEC, _PAGE_POOL_SPEC,
                         _PAGE_SCALE_SPEC, _PAGE_SCALE_SPEC,
                         _PAGE_VEC_SPEC, _PAGE_VEC_SPEC, P(None, None),
                         _PAGE_VEC_SPEC, _PAGE_VEC_SPEC,
                         _PAGE_VEC_SPEC, _PAGE_VEC_SPEC)
        else:
            def run(params, dparams, kp, vp, ksc, vsc, pos, tok, bt,
                    active, rem, poison, key):
                st, pos, tok, emit, c, drafted, accepted = body(
                    params, dparams, (kp, vp, ksc, vsc), pos, tok, bt,
                    active, rem, poison, key)
                return (*st, pos, tok, emit, c, drafted, accepted)

            in_specs = (specs, dspecs, _PAGE_POOL_SPEC,
                        _PAGE_POOL_SPEC, _PAGE_SCALE_SPEC,
                        _PAGE_SCALE_SPEC, _PAGE_VEC_SPEC,
                        _PAGE_VEC_SPEC, _PAGE_BT_SPEC, _PAGE_VEC_SPEC,
                        _PAGE_VEC_SPEC, _PAGE_VEC_SPEC, P())
            out_specs = (_PAGE_POOL_SPEC, _PAGE_POOL_SPEC,
                         _PAGE_SCALE_SPEC, _PAGE_SCALE_SPEC,
                         _PAGE_VEC_SPEC, _PAGE_VEC_SPEC, P(None, None),
                         _PAGE_VEC_SPEC, _PAGE_VEC_SPEC,
                         _PAGE_VEC_SPEC)

    return _jit_program(run, "paged_speculative_decode", mesh, in_specs,
                        out_specs)


def serving_param_specs(cfg: TransformerConfig):
    """Megatron layout with serving-specific MoE placement: the
    training specs shard EXPERTS over 'data' (expert parallelism for
    throughput training), but serving shards each expert's FFN hidden
    over 'model' and replicates the expert set — every data rank must
    be able to run whatever experts its tokens route to without an
    all-to-all per decode step."""
    specs = param_specs(cfg)
    if cfg.n_experts > 0:
        specs["blocks"]["router"] = P("pipe", None, None)
        specs["blocks"]["We1"] = P("pipe", None, None, "model")
        specs["blocks"]["We2"] = P("pipe", None, "model", None)
    # serving meshes are validated pipe=1, so the training layout's
    # leading 'pipe' placement is dropped: naming a size-1 manual axis
    # still marks the params VARYING over it, which poisons the scan
    # carry's varying-manual-axes set (round 3 had to switch the check
    # off for that reason)
    specs["blocks"] = {
        k: P(*(None if a == "pipe" else a for a in sp))
        for k, sp in specs["blocks"].items()}
    return specs


def shard_serving_params(params, cfg: TransformerConfig, mesh: Mesh):
    """Place params for serving — megatron layout (pipe=1 on a
    serving mesh, so the stacked [L, ...] blocks stay whole per
    device while heads/MLP split over 'model'), with the serving MoE
    overrides of serving_param_specs. Quantized trees
    (`quant.model.quantize_params`) are detected and placed with
    their derived specs — one entry point for both."""
    from deeplearning4j_tpu.quant.core import QuantizedTensor
    blocks = params.get("blocks", {}) if isinstance(params, dict) else {}
    q = next((leaf for leaf in list(params.values()) +
              list(blocks.values())
              if isinstance(leaf, QuantizedTensor)), None)
    if q is not None:
        from deeplearning4j_tpu.quant.model import (
            shard_quantized_serving_params)
        return shard_quantized_serving_params(params, cfg, mesh,
                                              mode=q.mode)
    return shard_params(params, cfg, mesh,
                        specs=serving_param_specs(cfg))
