"""Batched XLA formulations of SkipGram / CBOW / PV-DM / PV-DBOW.

The reference trains embeddings hogwild-style: worker threads race
unsynchronized updates into shared syn0/syn1 (reference:
SequenceVectors.java:289 VectorCalculationsThread; SkipGram.java:271
builds an ND4J `AggregateSkipGram` native batched op; CBOW.java;
sequence/{DBOW,DM}.java). Shared-memory racing has no TPU analog
(SURVEY.md §3.4): instead each minibatch of (center, context) pairs
becomes ONE jitted XLA step — gather the touched rows, compute exact
negative-sampling/hierarchical-softmax gradients, scatter-add them back.
Updates are dense per-batch but sparse per-vocab (only touched rows
change), mathematically equivalent to one hogwild round with
deterministic ordering.

All steps are functional: (syn0, syn1*) in → (syn0, syn1*) out, donated
buffers so XLA updates in place in HBM.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array


def _sg_neg_loss_and_grads(syn0_c, syn1_ctx, syn1_neg):
    """Negative-sampling skip-gram math for one batch.

    syn0_c:   [B, D] center vectors
    syn1_ctx: [B, D] positive context output vectors
    syn1_neg: [B, K, D] negative output vectors
    Returns (loss, g_center, g_ctx, g_neg) with the word2vec gradient
    (label - sigmoid(dot)) * other_side.
    """
    pos_dot = jnp.sum(syn0_c * syn1_ctx, axis=-1)            # [B]
    neg_dot = jnp.einsum("bd,bkd->bk", syn0_c, syn1_neg)     # [B, K]
    # loss = -log σ(pos) - Σ log σ(-neg)
    loss = (jnp.mean(jax.nn.softplus(-pos_dot))
            + jnp.mean(jnp.sum(jax.nn.softplus(neg_dot), axis=-1)))
    g_pos = jax.nn.sigmoid(pos_dot) - 1.0                     # [B]
    g_neg = jax.nn.sigmoid(neg_dot)                           # [B, K]
    g_center = (g_pos[:, None] * syn1_ctx
                + jnp.einsum("bk,bkd->bd", g_neg, syn1_neg))
    g_ctx = g_pos[:, None] * syn0_c                           # [B, D]
    g_negv = g_neg[:, :, None] * syn0_c[:, None, :]           # [B, K, D]
    return loss, g_center, g_ctx, g_negv


def skipgram_neg_impl(syn0: Array, syn1neg: Array, centers: Array,
                      contexts: Array, negatives: Array, lr: Array
                      ) -> Tuple[Array, Array, Array]:
    """One batched skip-gram negative-sampling update.

    centers/contexts: [B] int32; negatives: [B, K] int32; lr: [B]
    per-example learning rates (0 for padding rows, keeping batch shapes
    static across the corpus tail — no recompiles, no padding bias).
    Replaces the reference's AggregateSkipGram native op
    (SkipGram.java:271) with gather → grad → scatter-add in one XLA
    program.
    """
    syn0_c = syn0[centers]                                    # [B, D]
    syn1_ctx = syn1neg[contexts]                              # [B, D]
    syn1_negv = syn1neg[negatives]                            # [B, K, D]
    loss, g_c, g_ctx, g_neg = _sg_neg_loss_and_grads(syn0_c, syn1_ctx,
                                                     syn1_negv)
    syn0 = syn0.at[centers].add(-lr[:, None] * g_c)
    syn1neg = syn1neg.at[contexts].add(-lr[:, None] * g_ctx)
    syn1neg = syn1neg.at[negatives.reshape(-1)].add(
        (-lr[:, None, None] * g_neg).reshape(-1, g_neg.shape[-1]))
    return syn0, syn1neg, loss


# single-device jitted form (donated buffers update in place in HBM)
skipgram_neg_step = jax.jit(skipgram_neg_impl, donate_argnums=(0, 1))


def _epoch_scan(impl, n_carry: int, **jit_kwargs):
    """Build the scanned whole-epoch form of a batched update kernel:
    the first ``n_carry`` arguments are the embedding tables (scan
    carry, donated — they stay in HBM across batches), the rest are
    stacked per-batch operands with a leading [N] axis. The per-batch
    loop stays on device — the same dispatch-amortization move as
    MultiLayerNetwork.fit_batched. Returns (*tables, losses [N]).
    ``jit_kwargs`` lets mesh callers add in/out shardings."""
    def scan_impl(*args):
        carry, xs = args[:n_carry], args[n_carry:]

        def body(c, b):
            out = impl(*c, *b)
            return tuple(out[:-1]), out[-1]

        carry, losses = jax.lax.scan(body, tuple(carry), tuple(xs))
        return (*carry, losses)

    return jax.jit(scan_impl, donate_argnums=tuple(range(n_carry)),
                   **jit_kwargs)


skipgram_neg_scan = _epoch_scan(skipgram_neg_impl, 2)


def make_sharded_skipgram_step(mesh):
    """Data-parallel skip-gram (the reference's distributed Word2Vec role,
    spark/dl4j-spark-nlp/.../Word2Vec.java map-partitions + weight-delta
    accumulation, SURVEY.md §2.6): pair batches shard over the mesh's
    'data' axis, syn0/syn1neg stay replicated, and GSPMD turns the
    scatter-adds into an allreduce of per-shard deltas over ICI —
    equivalent math, collective-speed sync every batch."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    rep = NamedSharding(mesh, P())
    row = NamedSharding(mesh, P("data"))
    mat = NamedSharding(mesh, P("data", None))
    return jax.jit(skipgram_neg_impl,
                   in_shardings=(rep, rep, row, row, mat, row),
                   out_shardings=(rep, rep, rep),
                   donate_argnums=(0, 1))


def make_sharded_skipgram_scan(mesh):
    """Scanned whole-chunk form of the sharded skip-gram step: the
    stacked [N, B] pair batches shard over 'data' on the batch dim and
    the per-batch loop scans on device with the per-batch allreduce
    inside the program."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    rep = NamedSharding(mesh, P())
    row = NamedSharding(mesh, P(None, "data"))
    mat = NamedSharding(mesh, P(None, "data", None))
    return _epoch_scan(skipgram_neg_impl, 2,
                       in_shardings=(rep, rep, row, row, mat, row),
                       out_shardings=(rep, rep, rep))


def skipgram_hs_impl(syn0: Array, syn1: Array, centers: Array,
                     points: Array, codes: Array, code_mask: Array,
                     lr: Array) -> Tuple[Array, Array, Array]:
    """Hierarchical-softmax skip-gram update (reference: SkipGram.java
    useHS path :238; Huffman codes from vocab.py).

    centers: [B]; points: [B, L] inner-node rows; codes/mask: [B, L].
    """
    syn0_c = syn0[centers]                                    # [B, D]
    nodes = syn1[points]                                      # [B, L, D]
    dots = jnp.einsum("bd,bld->bl", syn0_c, nodes)            # [B, L]
    # label = 1 - code  (word2vec convention)
    labels = 1.0 - codes
    sig = jax.nn.sigmoid(dots)
    loss = jnp.mean(jnp.sum(
        code_mask * (jax.nn.softplus(dots) - labels * dots), axis=-1))
    g = (sig - labels) * code_mask                            # [B, L]
    g_center = jnp.einsum("bl,bld->bd", g, nodes)
    g_nodes = g[:, :, None] * syn0_c[:, None, :]              # [B, L, D]
    syn0 = syn0.at[centers].add(-lr[:, None] * g_center)
    syn1 = syn1.at[points.reshape(-1)].add(
        (-lr[:, None, None] * g_nodes).reshape(-1, g_nodes.shape[-1]))
    return syn0, syn1, loss


skipgram_hs_step = jax.jit(skipgram_hs_impl, donate_argnums=(0, 1))
skipgram_hs_scan = _epoch_scan(skipgram_hs_impl, 2)


def skipgram_hs_tables_impl(syn0: Array, syn1: Array, pts_t: Array,
                            codes_t: Array, cmask_t: Array,
                            centers: Array, contexts: Array, lr: Array
                            ) -> Tuple[Array, ...]:
    """HS skip-gram with DEVICE-RESIDENT Huffman tables (r5).

    The r4 path staged per-pair [B, L] points/codes/mask arrays from
    the host — ~3 full [chunk, B, 17] panels per scanned chunk
    (hundreds of MB of H2D per epoch, plus the
    host-side table gathers that built them: the profiled reason HS ran
    9x under negative sampling). Here the [V, L] tables ride the scan
    carry in HBM — uploaded once per fit — and each batch gathers its
    rows by context id ON DEVICE, so the host stages exactly what the
    neg path stages: int32 index streams. Same math as
    skipgram_hs_impl (device gather of the same table rows), so
    scanned/stepped equivalence is preserved bit-for-bit."""
    points = pts_t[contexts]
    codes = codes_t[contexts]
    cmask = cmask_t[contexts]
    syn0, syn1, loss = skipgram_hs_impl(syn0, syn1, centers, points,
                                        codes, cmask, lr)
    return syn0, syn1, pts_t, codes_t, cmask_t, loss


skipgram_hs_tables_scan = _epoch_scan(skipgram_hs_tables_impl, 5)


def cbow_neg_impl(syn0: Array, syn1neg: Array, context_windows: Array,
                  context_mask: Array, targets: Array, negatives: Array,
                  lr: Array) -> Tuple[Array, Array, Array]:
    """CBOW with negative sampling (reference: elements/CBOW.java):
    mean of context vectors predicts the target.

    context_windows: [B, W] int32 (padded); context_mask: [B, W];
    targets: [B]; negatives: [B, K].
    """
    ctx = syn0[context_windows]                               # [B, W, D]
    denom = jnp.maximum(context_mask.sum(-1, keepdims=True), 1.0)
    mean_ctx = (ctx * context_mask[:, :, None]).sum(1) / denom  # [B, D]
    syn1_t = syn1neg[targets]                                 # [B, D]
    syn1_n = syn1neg[negatives]                               # [B, K, D]
    loss, g_mean, g_t, g_n = _sg_neg_loss_and_grads(mean_ctx, syn1_t, syn1_n)
    # distribute mean-gradient to context rows (each gets g_mean / |ctx|)
    g_ctx_rows = (g_mean[:, None, :] * context_mask[:, :, None]) / \
        denom[:, :, None]                                     # [B, W, D]
    syn0 = syn0.at[context_windows.reshape(-1)].add(
        (-lr[:, None, None] * g_ctx_rows).reshape(-1, g_ctx_rows.shape[-1]))
    syn1neg = syn1neg.at[targets].add(-lr[:, None] * g_t)
    syn1neg = syn1neg.at[negatives.reshape(-1)].add(
        (-lr[:, None, None] * g_n).reshape(-1, g_n.shape[-1]))
    return syn0, syn1neg, loss


cbow_neg_step = jax.jit(cbow_neg_impl, donate_argnums=(0, 1))
cbow_neg_scan = _epoch_scan(cbow_neg_impl, 2)


def cbow_hs_impl(syn0: Array, syn1: Array, context_windows: Array,
                 context_mask: Array, points: Array, codes: Array,
                 code_mask: Array, lr: Array
                 ) -> Tuple[Array, Array, Array]:
    """CBOW with hierarchical softmax (reference: CBOW.java useHS): the
    mean of the window's context vectors predicts the CENTER word's
    Huffman path.

    context_windows/context_mask: [B, W]; points/codes/code_mask:
    [B, L] (the center word's tree path); lr: [B].
    """
    ctx = syn0[context_windows]                               # [B, W, D]
    denom = jnp.maximum(context_mask.sum(-1, keepdims=True), 1.0)
    mean_ctx = (ctx * context_mask[:, :, None]).sum(1) / denom  # [B, D]
    nodes = syn1[points]                                      # [B, L, D]
    dots = jnp.einsum("bd,bld->bl", mean_ctx, nodes)
    labels = 1.0 - codes
    sig = jax.nn.sigmoid(dots)
    loss = jnp.mean(jnp.sum(
        code_mask * (jax.nn.softplus(dots) - labels * dots), axis=-1))
    g = (sig - labels) * code_mask                            # [B, L]
    g_mean = jnp.einsum("bl,bld->bd", g, nodes)               # [B, D]
    g_nodes = g[:, :, None] * mean_ctx[:, None, :]            # [B, L, D]
    g_ctx_rows = (g_mean[:, None, :] * context_mask[:, :, None]) / \
        denom[:, :, None]                                     # [B, W, D]
    syn0 = syn0.at[context_windows.reshape(-1)].add(
        (-lr[:, None, None] * g_ctx_rows).reshape(-1,
                                                  g_ctx_rows.shape[-1]))
    syn1 = syn1.at[points.reshape(-1)].add(
        (-lr[:, None, None] * g_nodes).reshape(-1, g_nodes.shape[-1]))
    return syn0, syn1, loss


cbow_hs_step = jax.jit(cbow_hs_impl, donate_argnums=(0, 1))
cbow_hs_scan = _epoch_scan(cbow_hs_impl, 2)


def dm_neg_impl(syn0: Array, doc_vecs: Array, syn1neg: Array,
                doc_ids: Array, context_windows: Array, context_mask: Array,
                targets: Array, negatives: Array, lr: Array
                ) -> Tuple[Array, Array, Array, Array]:
    """PV-DM (reference: sequence/DM.java): doc vector + mean context
    predicts target word."""
    ctx = syn0[context_windows]
    denom = context_mask.sum(-1, keepdims=True) + 1.0  # +1 for the doc vec
    dv = doc_vecs[doc_ids]                                    # [B, D]
    mean_ctx = ((ctx * context_mask[:, :, None]).sum(1) + dv) / denom
    syn1_t = syn1neg[targets]
    syn1_n = syn1neg[negatives]
    loss, g_mean, g_t, g_n = _sg_neg_loss_and_grads(mean_ctx, syn1_t, syn1_n)
    g_ctx_rows = (g_mean[:, None, :] * context_mask[:, :, None]) / \
        denom[:, :, None]
    g_doc = g_mean / denom
    syn0 = syn0.at[context_windows.reshape(-1)].add(
        (-lr[:, None, None] * g_ctx_rows).reshape(-1, g_ctx_rows.shape[-1]))
    doc_vecs = doc_vecs.at[doc_ids].add(-lr[:, None] * g_doc)
    syn1neg = syn1neg.at[targets].add(-lr[:, None] * g_t)
    syn1neg = syn1neg.at[negatives.reshape(-1)].add(
        (-lr[:, None, None] * g_n).reshape(-1, g_n.shape[-1]))
    return syn0, doc_vecs, syn1neg, loss


def dbow_neg_impl(doc_vecs: Array, syn1neg: Array, doc_ids: Array,
                  targets: Array, negatives: Array, lr: Array
                  ) -> Tuple[Array, Array, Array]:
    """PV-DBOW (reference: sequence/DBOW.java): the doc vector plays the
    center role of skip-gram against each word of the doc."""
    d_c = doc_vecs[doc_ids]
    s_t = syn1neg[targets]
    s_n = syn1neg[negatives]
    loss, g_d, g_t, g_n = _sg_neg_loss_and_grads(d_c, s_t, s_n)
    doc_vecs = doc_vecs.at[doc_ids].add(-lr[:, None] * g_d)
    syn1neg = syn1neg.at[targets].add(-lr[:, None] * g_t)
    syn1neg = syn1neg.at[negatives.reshape(-1)].add(
        (-lr[:, None, None] * g_n).reshape(-1, g_n.shape[-1]))
    return doc_vecs, syn1neg, loss


dbow_neg_scan = _epoch_scan(dbow_neg_impl, 2)
dm_neg_scan = _epoch_scan(dm_neg_impl, 3)


def glove_impl(w_main: Array, w_ctx: Array, b_main: Array, b_ctx: Array,
               rows: Array, cols: Array, xij: Array, lr: Array,
               x_max: float = 100.0, alpha: float = 0.75
               ) -> Tuple[Array, Array, Array, Array, Array]:
    """Batched GloVe update (reference: elements/GloVe.java /
    glove/Glove.java AdaGrad on co-occurrence pairs; plain SGD here, the
    weighting f(x)=min(1,(x/xmax)^α) matches)."""
    wm = w_main[rows]
    wc = w_ctx[cols]
    bm = b_main[rows]
    bc = b_ctx[cols]
    weight = jnp.minimum(1.0, (xij / x_max) ** alpha)
    diff = jnp.sum(wm * wc, axis=-1) + bm + bc - jnp.log(xij)
    loss = jnp.mean(weight * diff * diff)
    g = weight * diff                                        # [B]
    w_main = w_main.at[rows].add(-lr[:, None] * g[:, None] * wc)
    w_ctx = w_ctx.at[cols].add(-lr[:, None] * g[:, None] * wm)
    b_main = b_main.at[rows].add(-lr * g)
    b_ctx = b_ctx.at[cols].add(-lr * g)
    return w_main, w_ctx, b_main, b_ctx, loss


def _glove_scan_impl(w_main, w_ctx, b_main, b_ctx, rows, cols, xij, lr,
                     x_max, alpha):
    """GloVe epoch chunk as one scanned program (leading [N] batches
    axis; padding rows carry lr=0 and xij=1 so log(xij)=0 — no-ops)."""
    def body(carry, bt):
        wm, wc, bm, bc = carry
        r, c, x, l = bt
        wm, wc, bm, bc, loss = glove_impl(wm, wc, bm, bc, r, c, x, l,
                                          x_max, alpha)
        return (wm, wc, bm, bc), loss

    (w_main, w_ctx, b_main, b_ctx), losses = jax.lax.scan(
        body, (w_main, w_ctx, b_main, b_ctx), (rows, cols, xij, lr))
    return w_main, w_ctx, b_main, b_ctx, losses


glove_scan = jax.jit(_glove_scan_impl, donate_argnums=(0, 1, 2, 3))


def make_sharded_glove_scan(mesh):
    """Data-parallel GloVe (the reference's distributed GloVe role,
    spark/dl4j-spark-nlp GlovePerformer): co-occurrence pair batches
    shard over 'data', embedding/bias tables stay replicated, GSPMD
    allreduces the per-shard scatter-add deltas inside the scanned
    program."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    rep = NamedSharding(mesh, P())
    row = NamedSharding(mesh, P(None, "data"))
    return jax.jit(_glove_scan_impl,
                   in_shardings=(rep, rep, rep, rep, row, row, row, row,
                                 None, None),
                   out_shardings=(rep,) * 5,
                   donate_argnums=(0, 1, 2, 3))


@jax.jit
def dbow_infer_step(doc_vec: Array, syn1neg: Array, targets: Array,
                    negatives: Array, lr: Array) -> Tuple[Array, Array]:
    """Inference-time PV-DBOW: update ONLY the doc vector, word weights
    frozen (reference: ParagraphVectors.inferVector). No donation — the
    caller keeps syn1neg alive across steps."""
    d_c = jnp.broadcast_to(doc_vec, (targets.shape[0], doc_vec.shape[-1]))
    s_t = syn1neg[targets]
    s_n = syn1neg[negatives]
    loss, g_d, _, _ = _sg_neg_loss_and_grads(d_c, s_t, s_n)
    doc_vec = doc_vec - jnp.sum(lr[:, None] * g_d, axis=0)
    return doc_vec, loss
