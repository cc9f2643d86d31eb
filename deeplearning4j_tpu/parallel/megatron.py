"""Composite-parallel transformer training: DP x TP x PP x SP x EP in one
compiled step.

NET-NEW vs the reference, whose only strategy is data parallelism by
host-staged parameter averaging (SURVEY.md §2.6); here every strategy is a
sharding of one traced program over the named mesh (parallel/mesh.py):

- data ('data'): batch sharded; gradient psum.
- tensor ('model'): megatron-style — attention heads and MLP hidden sharded;
  forward psum ("g" op) paired with an identity-forward/psum-backward "f" op
  at each parallel region's entry so residual-stream gradients stay exact.
- pipeline ('pipe'): blocks stacked [L] -> stages [S, L/S]; activations hop
  stages via ppermute; loss is computed on the last stage and psum-masked
  across the axis. Two microbatch schedules (``pipeline_schedule``):
  'gpipe' (default) — all-forward-then-all-backward, autodiff through the
  tick scan, activation memory O(M) microbatches deep; '1f1b' — explicit
  per-microbatch jax.vjp with an O(S)-deep input stash, forward and
  backward slots interleaved in one scanned round loop (see
  _value_and_grad_1f1b for the schedule math and the honest bubble
  accounting of a slot-synchronous SPMD 1F1B).
- sequence ('seq'): tokens sharded over time; cfg.seq_impl picks the
  strategy — 'ring' (parallel/ring.py: K/V blocks rotate via ppermute) or
  'ulysses' (parallel/ulysses.py: all_to_all head resharding).
- expert ('ep' rides the 'data' axis, Switch/GShard-style): experts sharded
  over 'data', tokens routed by all_to_all. n_experts % data-size == 0.

Gradient synchronization rule: a leaf's gradient is psum'd over exactly the
mesh axes it is replicated across among ('pipe','data','seq') — 'model' is
excluded because the f/g pairing already delivers full gradients on every
model rank.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.models.remat import remat_layer
from deeplearning4j_tpu.models.transformer import (
    TransformerConfig, embed_tokens, final_norm, head_loss_sum, nll_sum)
from deeplearning4j_tpu.nn.layers.attention import layer_norm
from deeplearning4j_tpu.parallel.optim import (AdamState,  # noqa: F401
                                               adam_update_tree,
                                               init_adam_state)
from deeplearning4j_tpu.parallel.ring import ring_attention
from deeplearning4j_tpu.parallel.ulysses import ulysses_attention

Array = jax.Array


# ---------------------------------------------------------------------------
# megatron f op: identity forward, psum backward
# ---------------------------------------------------------------------------

def _f_sync(axis_name: str):
    """Megatron 'f': identity forward, psum backward — placed at a
    tensor-parallel region's ENTRY so the residual stream's cotangent is
    reassembled from the per-rank partial paths."""
    @jax.custom_vjp
    def f(x):
        return x

    def fwd(x):
        return x, None

    def bwd(_, g):
        return (lax.psum(g, axis_name),)

    f.defvjp(fwd, bwd)
    return f


def _g_sync(axis_name: str):
    """Megatron 'g': psum forward, IDENTITY backward — a raw lax.psum is
    wrong here because its autodiff transpose is another psum, which
    double-counts the already-full cotangent on every rank."""
    @jax.custom_vjp
    def g(x):
        return lax.psum(x, axis_name)

    def fwd(x):
        return lax.psum(x, axis_name), None

    def bwd(_, ct):
        return (ct,)

    g.defvjp(fwd, bwd)
    return g


# ---------------------------------------------------------------------------
# parameter partition specs
# ---------------------------------------------------------------------------

def param_specs(cfg: TransformerConfig) -> Dict[str, Any]:
    """PartitionSpec pytree matching models/transformer.init_params."""
    if cfg.layer_types:
        # a period of unlike layers: every leaf stacked over periods, the
        # axis 'pipe' shards; nothing else of them is divided (the held
        # experts are this rank's share already)
        from deeplearning4j_tpu.models import layer_kinds
        out = {"embed": P(), "lnfg": P(), "blocks": {
            key: {name: P("pipe", *([None] * (len(shape) + len(lead))))
                  for name, shape in
                  layer_kinds.layer_shapes(cfg, kind).items()}
            for key, kind, lead in layer_kinds.block_keys(cfg)}}
        if not cfg.tie_head:
            out["Wout"] = P()
        # the leading layers and the MTP module are whole on every rank
        # (`make_parallel_train_step` refuses them a 'pipe' axis)
        whole = lambda shapes: jax.tree_util.tree_map(  # noqa: E731
            lambda _: P(), shapes, is_leaf=lambda x: isinstance(x, tuple))
        if cfg.lead_dense_layers:
            out["lead"] = whole(layer_kinds.lead_shapes(cfg))
        if cfg.mtp_layers:
            out["mtp"] = whole(layer_kinds.mtp_shapes(cfg))
        return out
    blocks: Dict[str, P] = {
        "Wq": P("pipe", None, "model"), "Wk": P("pipe", None, "model"),
        "Wv": P("pipe", None, "model"), "Wo": P("pipe", "model", None),
        "ln1g": P("pipe", None), "ln1b": P("pipe", None),
        "ln2g": P("pipe", None), "ln2b": P("pipe", None),
    }
    if cfg.n_experts > 0:
        blocks["router"] = P("pipe", None, None)
        blocks["We1"] = P("pipe", "data", None, None)
        blocks["We2"] = P("pipe", "data", None, None)
    else:
        blocks["W1"] = P("pipe", None, "model")
        blocks["b1"] = P("pipe", "model")
        blocks["W2"] = P("pipe", "model", None)
        blocks["b2"] = P("pipe", None)
    return {"embed": P(), "pos": P(), "blocks": blocks,
            "lnfg": P(), "lnfb": P(), "Wout": P()}


def _grad_psum_axes(spec: P, mesh: Mesh) -> Tuple[str, ...]:
    used = {a for part in spec if part is not None
            for a in ((part,) if isinstance(part, str) else part)}
    return tuple(a for a in ("pipe", "data", "seq")
                 if a not in used and mesh.shape[a] > 1)


# ---------------------------------------------------------------------------
# sharded block forward (operates on LOCAL shards inside shard_map)
# ---------------------------------------------------------------------------

def _block_fwd_sharded(h: Array, p: Dict[str, Array],
                       cfg: TransformerConfig, mesh: Mesh) -> Array:
    tp = mesh.shape["model"]
    sp = mesh.shape["seq"]
    dp = mesh.shape["data"]
    d = cfg.d_model
    h_loc = cfg.n_heads // tp
    f_model = _f_sync("model")
    g_model = _g_sync("model")

    def heads(y):
        return y.reshape(y.shape[0], y.shape[1], h_loc, cfg.d_head)

    with jax.named_scope("attn"):
        x = layer_norm(h, p["ln1g"], p["ln1b"], cfg.eps)
        x = f_model(x)
        q = heads(jnp.matmul(x, p["Wq"].astype(x.dtype)))
        k = heads(jnp.matmul(x, p["Wk"].astype(x.dtype)))
        v = heads(jnp.matmul(x, p["Wv"].astype(x.dtype)))
        if sp > 1:
            # seq_impl validated upfront by make_parallel_train_step
            if cfg.seq_impl == "ulysses":
                a = ulysses_attention(q, k, v, "seq", causal=True)
            else:
                a = ring_attention(q, k, v, "seq", causal=True)
        else:
            from deeplearning4j_tpu.nn.layers.attention import \
                dot_product_attention
            a = dot_product_attention(q, k, v, causal=True)
        a = a.reshape(a.shape[0], a.shape[1], h_loc * cfg.d_head)
        attn_out = jnp.matmul(a, p["Wo"].astype(a.dtype))
        attn_out = g_model(attn_out)
        h = h + attn_out

    with jax.named_scope("mlp"):
        x = layer_norm(h, p["ln2g"], p["ln2b"], cfg.eps)
        if cfg.n_experts > 0:
            h = h + _moe_sharded(x, p, cfg, dp)
        else:
            x = f_model(x)
            z = jax.nn.gelu(jnp.matmul(x, p["W1"].astype(x.dtype))
                            + p["b1"].astype(x.dtype))
            m = jnp.matmul(z, p["W2"].astype(z.dtype))
            m = g_model(m)
            h = h + m + p["b2"].astype(h.dtype)
    return h


def _moe_sharded(x: Array, p: Dict[str, Array], cfg: TransformerConfig,
                 dp: int) -> Array:
    """Expert-parallel top-1 MoE: experts sharded over 'data', tokens
    exchanged by all_to_all (Switch-style). Local x: [b, t, D]."""
    b, t, d = x.shape
    e = cfg.n_experts
    e_loc = e // dp
    xt = x.reshape(b * t, d)
    n = b * t
    logits = jnp.matmul(xt.astype(jnp.float32), p["router"])
    gates = jax.nn.softmax(logits, axis=-1)
    expert = jnp.argmax(gates, axis=-1)
    prob = jnp.take_along_axis(gates, expert[:, None], 1)[:, 0]
    cap = max(1, int(cfg.capacity_factor * n / e))
    onehot = jax.nn.one_hot(expert, e, dtype=jnp.float32)
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1.0
    keep = (pos >= 0) & (pos < cap)
    posc = jnp.clip(pos, 0, cap - 1).astype(jnp.int32)
    disp = (jax.nn.one_hot(posc, cap, dtype=jnp.float32)
            * keep[..., None].astype(jnp.float32) * onehot[..., None])
    xin = jnp.einsum("nec,nd->ecd", disp, xt.astype(jnp.float32))  # [E,C,D]
    if dp > 1:
        # [E, C, D] -> [E/dp, dp*C, D]: each data rank keeps its experts'
        # tokens from every peer
        xin = lax.all_to_all(xin, "data", split_axis=0, concat_axis=1,
                             tiled=True)
    z = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", xin, p["We1"]))
    out = jnp.einsum("ecf,efd->ecd", z, p["We2"])
    if dp > 1:
        out = lax.all_to_all(out, "data", split_axis=1, concat_axis=0,
                             tiled=True)                            # [E,C,D]
    comb = disp * prob[:, None, None]
    y = jnp.einsum("nec,ecd->nd", comb, out)
    return y.astype(x.dtype).reshape(b, t, d)


# ---------------------------------------------------------------------------
# GPipe pipeline over stacked local blocks
# ---------------------------------------------------------------------------

def _stage_fn(x: Array, blocks_local, cfg, mesh) -> Array:
    if cfg.layer_types:
        from deeplearning4j_tpu.models import layer_kinds
        return layer_kinds.periods_forward(x, blocks_local, cfg)

    # blockwise rematerialization under the scan (prevent_cse=False: the
    # loop structure already blocks the CSE the default guards)
    block = remat_layer(lambda h, p: _block_fwd_sharded(h, p, cfg, mesh),
                        cfg, site="parallel.megatron", prevent_cse=False)
    y, _ = lax.scan(lambda h, p: (block(h, p), None), x, blocks_local)
    return y


def _pipeline_apply(blocks_local, h_mb: Array, cfg, mesh) -> Array:
    """h_mb: [M, mb, tl, D] local microbatches -> outputs [M, mb, tl, D]
    (meaningful on the LAST pipe stage; other stages produce their own
    stage outputs, masked out by the caller)."""
    s = mesh.shape["pipe"]
    if s == 1:
        m_, mb, tl, d = h_mb.shape
        y = _stage_fn(h_mb.reshape(m_ * mb, tl, d), blocks_local, cfg, mesh)
        return y.reshape(m_, mb, tl, d)
    i = lax.axis_index("pipe")
    m_ = h_mb.shape[0]
    perm_fwd = [(j, j + 1) for j in range(s - 1)]

    def vary(x):
        return lax.pcast(x, ("pipe", "data", "seq"), to="varying")
    recv0 = vary(jnp.zeros_like(h_mb[0]))
    out0 = vary(jnp.zeros_like(h_mb))

    def tick_full(carry, t):
        recv, out_buf = carry
        x0 = lax.dynamic_index_in_dim(h_mb, jnp.clip(t, 0, m_ - 1), 0,
                                      keepdims=False)
        x = jnp.where(i == 0, x0, recv)
        y = _stage_fn(x, blocks_local, cfg, mesh)
        recv_new = lax.ppermute(y, "pipe", perm_fwd)
        store = jnp.clip(t - (s - 1), 0, m_ - 1)
        cur = lax.dynamic_index_in_dim(out_buf, store, 0, keepdims=False)
        upd = jnp.where(t >= s - 1, y, cur)
        out_buf = lax.dynamic_update_index_in_dim(out_buf, upd, store, 0)
        return (recv_new, out_buf), None

    (recv, out_buf), _ = lax.scan(tick_full, (recv0, out0),
                                  jnp.arange(m_ + s - 1))
    return out_buf


# ---------------------------------------------------------------------------
# 1F1B pipeline schedule (explicit per-microbatch vjp, O(S) activations)
# ---------------------------------------------------------------------------

def pipeline_bubble_fraction(schedule: str, n_stages: int,
                             n_microbatches: int) -> float:
    """Analytic pipeline-bubble fraction (idle slot share per stage).

    gpipe: the forward tick scan runs M+S-1 ticks for M useful forwards
    per stage (autodiff mirrors it in reverse) -> (S-1)/(M+S-1).
    1f1b (slot-synchronous, see _value_and_grad_1f1b): M+2(S-1) rounds,
    each carrying one F slot and one B slot, M of each useful ->
    2(S-1)/(M+2(S-1)). The 1f1b schedule trades a larger bubble at
    EQUAL M for activation memory independent of M — the point is that
    M can then grow (memory freed ~M/S-fold) until the bubble is
    smaller than any M the gpipe schedule can afford."""
    if n_stages <= 1:
        return 0.0
    s, m = n_stages, n_microbatches
    if schedule == "gpipe":
        return (s - 1) / (m + s - 1)
    if schedule == "1f1b":
        return 2 * (s - 1) / (m + 2 * (s - 1))
    raise ValueError(f"unknown pipeline schedule {schedule!r}")


def _value_and_grad_1f1b(params, tokens_loc, targets_loc,
                         cfg: TransformerConfig, mesh: Mesh, m_: int):
    """Loss + grads under a 1F1B-style pipeline schedule, computed with
    EXPLICIT per-microbatch vjp instead of autodiff through the GPipe
    tick scan.

    Schedule (stage i of S, round r of M+2(S-1); every round holds one
    forward slot and one backward slot, executed by every rank with
    validity masks — SPMD can't give ranks different control flow):

      forward of microbatch j at stage i  -> round i + j
      backward of microbatch j at stage i -> round 2(S-1) - i + j

    so the LAST stage runs F(j) and B(j) in the same round (the 1F1B
    signature move) and cotangents flow upstream one stage per round
    via reverse ppermute. In-flight forwards at stage i never exceed
    2(S-1-i)+1 microbatches, so the input stash is a fixed 2S-slot ring
    buffer — activation memory is O(S) and INDEPENDENT of M, vs the
    GPipe path whose scan residuals are O(M) deep. The backward slot
    re-runs the stage forward inside jax.vjp from the stashed input
    (stage-granular rematerialization — the same fwd+recompute+bwd
    FLOP count the remat'd GPipe path pays).

    Equality contract: loss and every grad leaf match the GPipe path
    (and therefore single-device training) to float tolerance — the
    per-microbatch loss head is scaled 1/global_count so summed
    microbatch cotangents reproduce the global-mean loss exactly
    (tests/test_megatron.py::test_1f1b_*).

    Role analog: net-new (SURVEY §5.7 — the reference has no pipeline
    parallelism); schedule per Narayanan et al.'s PipeDream-flush /
    Megatron-LM 1F1B, re-expressed as a masked SPMD round loop.
    """
    s = mesh.shape["pipe"]
    dp = mesh.shape["data"]
    sp_ = mesh.shape["seq"]
    dt = cfg.activation_dtype()
    b_loc, tl = tokens_loc.shape
    mb = b_loc // m_
    d = cfg.d_model
    i = lax.axis_index("pipe")
    toks_mb = tokens_loc.reshape(m_, mb, tl)
    tgts_mb = targets_loc.reshape(m_, mb, tl)
    count = b_loc * tl * dp * sp_
    seq_idx = lax.axis_index("seq").astype(jnp.int32)
    blocks = params["blocks"]
    ep_params = {"embed": params["embed"], "pos": params["pos"]}
    head_params = {"lnfg": params["lnfg"], "lnfb": params["lnfb"],
                   "Wout": params["Wout"]}

    def embed_one(ep, toks):
        pos = lax.dynamic_slice(ep["pos"], (seq_idx * tl, jnp.int32(0)),
                                (tl, d))
        return ep["embed"].astype(dt)[toks] + pos.astype(dt)[None]

    def head_loss_sum(hp, y, tgt):
        hf = layer_norm(y, hp["lnfg"], hp["lnfb"], cfg.eps)
        return nll_sum(cfg, hp["Wout"], hf, tgt)

    n_slots = 2 * s          # 2S-1 live ring slots + 1 trash slot
    perm_fwd = [(j, j + 1) for j in range(s - 1)]
    perm_bwd = [(j + 1, j) for j in range(s - 1)]
    is_last = i == s - 1
    t_total = m_ + 2 * (s - 1)

    g0 = jax.tree_util.tree_map(
        jnp.zeros_like, {"blocks": blocks, "head": head_params,
                         "ep": ep_params})
    carry0 = (jnp.zeros((mb, tl, d), dt),         # recv_f
              jnp.zeros((mb, tl, d), dt),         # recv_b (cotangent)
              jnp.zeros((n_slots, mb, tl, d), dt),
              g0, jnp.zeros((), jnp.float32))

    def round_body(carry, r):
        recv_f, recv_b, stash, gacc, loss_acc = carry
        # ---- forward slot: F(j_f) with j_f = r - i
        j_f = r - i
        vf = (j_f >= 0) & (j_f < m_)
        jf_c = jnp.clip(j_f, 0, m_ - 1)
        x0 = embed_one(ep_params, lax.dynamic_index_in_dim(
            toks_mb, jf_c, 0, keepdims=False))
        x_in = jnp.where(i == 0, x0, recv_f)
        y = _stage_fn(x_in, blocks, cfg, mesh)
        # invalid slots write to the trash slot so drain-phase garbage
        # can't clobber a stash entry whose backward is still pending
        slot = jnp.where(vf, jf_c % (n_slots - 1), n_slots - 1)
        stash = lax.dynamic_update_index_in_dim(stash, x_in, slot, 0)
        recv_f_new = lax.ppermute(y, "pipe", perm_fwd)

        # ---- backward slot: B(j_b) with j_b = r - 2(S-1) + i
        j_b = r - 2 * (s - 1) + i
        vb = (j_b >= 0) & (j_b < m_)
        jb_c = jnp.clip(j_b, 0, m_ - 1)
        x_s = lax.dynamic_index_in_dim(stash, jb_c % (n_slots - 1), 0,
                                       keepdims=False)
        toks_j = lax.dynamic_index_in_dim(toks_mb, jb_c, 0,
                                          keepdims=False)
        tgt_j = lax.dynamic_index_in_dim(tgts_mb, jb_c, 0,
                                         keepdims=False)

        def fb(x, blk, hp):
            yy = _stage_fn(x, blk, cfg, mesh)
            # every rank computes the head (SPMD-uniform, as the GPipe
            # path does); only the last stage's cotangent is nonzero
            return yy, head_loss_sum(hp, yy, tgt_j) / count

        (_, ls), pull = jax.vjp(fb, x_s, blocks, head_params)
        # zero cotangents make every invalid/masked grad exactly zero
        ct_y = jnp.where(vb & ~is_last, recv_b, 0).astype(dt)
        ct_l = jnp.where(vb & is_last, 1.0, 0.0).astype(jnp.float32)
        dx, dblk, dhp = pull((ct_y, ct_l))
        _, pull_e = jax.vjp(lambda ep: embed_one(ep, toks_j), ep_params)
        dep = pull_e(jnp.where(i == 0, dx, 0).astype(dt))[0]
        gacc = jax.tree_util.tree_map(
            lambda a, b: a + b, gacc,
            {"blocks": dblk, "head": dhp, "ep": dep})
        loss_acc = loss_acc + jnp.where(vb & is_last, ls, 0.0)
        recv_b_new = lax.ppermute(dx, "pipe", perm_bwd)
        return (recv_f_new, recv_b_new, stash, gacc, loss_acc), None

    (_, _, _, gacc, loss_acc), _ = lax.scan(
        round_body, carry0, jnp.arange(t_total, dtype=jnp.int32))
    loss = lax.psum(loss_acc, ("pipe", "data", "seq"))
    grads = {"embed": gacc["ep"]["embed"], "pos": gacc["ep"]["pos"],
             "blocks": gacc["blocks"], "lnfg": gacc["head"]["lnfg"],
             "lnfb": gacc["head"]["lnfb"],
             "Wout": gacc["head"]["Wout"]}
    return loss, grads


# ---------------------------------------------------------------------------
# the train step factory
# ---------------------------------------------------------------------------

def make_parallel_train_step(cfg: TransformerConfig, mesh: Mesh, *,
                             learning_rate: float = 1e-3,
                             n_microbatches: Optional[int] = None,
                             b1: float = 0.9, b2: float = 0.999,
                             eps: float = 1e-8,
                             pipeline_schedule: str = "gpipe"):
    """Build the jitted composite-parallel train step.

    Returns ``step(params, opt_state, tokens, targets) ->
    (params, opt_state, loss)``. ``tokens``/``targets`` are GLOBAL [B, T]
    int32 arrays (sharded on entry by the step's in_shardings).
    ``pipeline_schedule``: 'gpipe' (all-F-then-all-B, O(M) activation
    memory) or '1f1b' (interleaved, O(S) activation memory — see
    _value_and_grad_1f1b); identical losses and grads either way.
    """
    s = mesh.shape["pipe"]
    dp = mesh.shape["data"]
    sp = mesh.shape["seq"]
    tp = mesh.shape["model"]
    if mesh.shape.get("expert", 1) != 1:
        raise ValueError("expert parallelism rides the 'data' axis; use "
                         "expert=1 in the mesh (Switch-style EP)")
    if cfg.layer_types:
        from deeplearning4j_tpu.models import layer_kinds
        if tp > 1 or sp > 1:
            raise ValueError(
                f"TransformerConfig.layer_types={cfg.layer_types}: the "
                "typed layers (Gated DeltaNet, Mamba-2, grouped-query "
                "attention, top-k MoE, dense SwiGLU) are not divided over "
                "the 'model' or 'seq' axes; use data and pipe")
        if pipeline_schedule == "1f1b" and s > 1:
            raise ValueError("TransformerConfig.layer_types: the 1f1b "
                             "schedule is not there for typed layers")
        if layer_kinds.n_periods(cfg) % s:
            raise ValueError("whole periods of layer_types must divide "
                             "by pipe size")
        if s > 1 and (cfg.lead_dense_layers or cfg.mtp_layers):
            raise ValueError(
                f"TransformerConfig.lead_dense_layers="
                f"{cfg.lead_dense_layers}, mtp_layers={cfg.mtp_layers}: "
                "layers outside the periods (the leading dense layers, the "
                "multi-token-prediction module) are not placed on a stage "
                "of the 'pipe' axis; use pipe=1")
    else:
        if cfg.n_layers % s:
            raise ValueError("n_layers must divide by pipe size")
        if cfg.n_heads % tp or cfg.d_ff % tp:
            raise ValueError("n_heads and d_ff must divide by model size")
        if cfg.n_experts and cfg.n_experts % dp:
            raise ValueError("n_experts must divide by data size")
    if cfg.seq_impl not in ("ring", "ulysses"):
        raise ValueError(f"unknown seq_impl {cfg.seq_impl!r}: expected "
                         "'ring' or 'ulysses'")
    if cfg.seq_impl == "ulysses" and sp > 1 and (cfg.n_heads // tp) % sp:
        raise ValueError(
            f"seq_impl='ulysses' needs local heads (n_heads/tp = "
            f"{cfg.n_heads // tp}) divisible by seq size {sp}; use "
            "seq_impl='ring' (any head count) or change the mesh")
    if pipeline_schedule not in ("gpipe", "1f1b"):
        raise ValueError(f"unknown pipeline_schedule "
                         f"{pipeline_schedule!r}: expected 'gpipe' or "
                         "'1f1b'")
    m_ = n_microbatches or s
    specs = param_specs(cfg)
    use_1f1b = pipeline_schedule == "1f1b" and s > 1

    def local_forward_loss(params, tokens_loc, targets_loc):
        """Everything after sharding: local token block -> global mean
        loss (identical scalar on every device)."""
        dt = cfg.activation_dtype()
        b_loc, tl = tokens_loc.shape
        seq_idx = lax.axis_index("seq").astype(jnp.int32)
        with jax.named_scope("embed"):
            h = embed_tokens(cfg, params, tokens_loc)
            if not cfg.layer_types:     # typed layers add no positions
                pos = lax.dynamic_slice(params["pos"],
                                        (seq_idx * tl, jnp.int32(0)),
                                        (tl, cfg.d_model))
                h = h + pos.astype(dt)[None]
        if cfg.lead_dense_layers:
            from deeplearning4j_tpu.models.layer_kinds import lead_forward
            h = lead_forward(h, params["lead"], cfg)
        # microbatch split for the pipeline
        if b_loc % m_:
            raise ValueError(f"local batch {b_loc} not divisible by "
                             f"{m_} microbatches")
        mb = b_loc // m_
        h_mb = h.reshape(m_, mb, tl, cfg.d_model)
        out = _pipeline_apply(params["blocks"], h_mb, cfg, mesh)
        hf = out.reshape(b_loc, tl, cfg.d_model)
        # the head and the loss on the LOCAL tokens (the head is
        # replicated; with xent_chunk each shard scans its own panels)
        with jax.named_scope("head_loss"):
            hf = final_norm(cfg, params, hf)
        local_sum = head_loss_sum(cfg, params, hf, targets_loc)
        if s > 1:
            is_last = (lax.axis_index("pipe") == s - 1)
            local_sum = jnp.where(is_last, local_sum, 0.0)
        total = lax.psum(local_sum, ("pipe", "data", "seq"))
        count = b_loc * tl * dp * sp
        return total / count

    def sharded_step(params, opt_m, opt_v, count, tokens_loc, targets_loc):
        if use_1f1b:
            if tokens_loc.shape[0] % m_:
                raise ValueError(f"local batch {tokens_loc.shape[0]} "
                                 f"not divisible by {m_} microbatches")
            loss, grads = _value_and_grad_1f1b(params, tokens_loc,
                                               targets_loc, cfg, mesh, m_)
        else:
            loss, grads = jax.value_and_grad(
                lambda p: local_forward_loss(p, tokens_loc,
                                             targets_loc))(params)
        # sync gradients over the axes each leaf is replicated across
        grads = jax.tree_util.tree_map(
            lambda g, sp_: lax.psum(g, _grad_psum_axes(sp_, mesh))
            if _grad_psum_axes(sp_, mesh) else g,
            grads, specs)
        # adam on local shards (identical math on every replica)
        cnt = count + 1
        with jax.named_scope("optimizer"):
            new_p, new_m, new_v = adam_update_tree(
                params, grads, opt_m, opt_v, cnt.astype(jnp.float32),
                learning_rate=learning_rate, b1=b1, b2=b2, eps=eps)
        return new_p, new_m, new_v, cnt, loss

    data_spec = P(("data",), ("seq",))
    smapped = jax.shard_map(
        sharded_step, mesh=mesh,
        in_specs=(specs, specs, specs, P(), data_spec, data_spec),
        out_specs=(specs, specs, specs, P(), P()),
        check_vma=False)

    def step(params, opt_state: AdamState, tokens, targets):
        p2, m2, v2, cnt, loss = smapped(params, opt_state.m, opt_state.v,
                                        opt_state.count, tokens, targets)
        return p2, AdamState(m2, v2, cnt), loss

    return jax.jit(step, donate_argnums=(0, 1))


def shard_params(params, cfg: TransformerConfig, mesh: Mesh,
                 specs=None):
    """Place a host/replicated param pytree onto the mesh per
    param_specs (or caller-supplied ``specs`` — e.g. the serving
    layout's MoE overrides)."""
    if specs is None:
        specs = param_specs(cfg)
    return jax.tree_util.tree_map(
        lambda p, sp_: jax.device_put(p, NamedSharding(mesh, sp_)),
        params, specs)
