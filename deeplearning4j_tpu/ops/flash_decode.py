"""Pallas decode-attention (split-K) kernel for KV-cached sampling.

Role parity: the reference streams inference state through
rnnTimeStep (MultiLayerNetwork.java:2234); the flagship family's
streamed state is the KV cache, and this kernel is the fast path for
its per-step attention. The training flash kernel
(ops/flash_attention.py) is ineligible at q-length 1, so round-3
decode fell back to the jnp path — which attends over the ENTIRE
allocated max_len cache every step and was measured ~5x off the HBM
bandwidth roofline at (B=64, S=2048) (VERDICT r3 weak #2).

Design (one query row per batch-head, bandwidth-bound):

- grid = (B/bb, S/bs): each program loads a [bb, bs, D] K/V cache
  block (heads flattened, D = H*Dh — the cache's native layout, no
  reshape in HBM) and runs the online-softmax update for all H heads
  of bb batch rows. The last grid dim is sequential on TPU, so the
  per-(batch, head) running max / normalizer / accumulator live in
  VMEM scratch across the S-blocks.
- Each batch block's furthest position rides as a PREFETCHED SCALAR:
  the K/V index_map clamps the block index at ceil((pos+1)/bs), and
  Mosaic does not re-issue a DMA whose block index is unchanged — so
  a step at position p reads only the filled ceil((p+1)/bs) prefix of
  the cache from HBM, not max_len. This is what makes early-decode steps cheap (the jnp path
  read all S rows regardless of p) AND what keeps the full-cache
  regime at the bandwidth roofline: each cache byte is read once.
  Blocks past the prefix skip their compute via pl.when on the same
  bound.
- The rows' OWN positions (slotted / paged decode and spec verify have
  every row at a different prefix; a scalar pos broadcasts) reach the
  kernel as a [bb, 1, 1] VMEM block, not as a second prefetched
  vector: scalar memory serves scalar reads only, and the mask needs
  the block's bb positions as one vector operand.
- Per-head score/PV products are head-unrolled multiply+reduce on the
  lane-sliced cache block (H is small and static; Dh=64 slices are
  static lane sub-ranges, no transpose of the cache block needed).
  Mosaic rejects both batched dot_general and >2-D gathers/stacks in
  this kernel on the real backend — see the in-kernel comments for
  the exact errors each formulation hit.

Numerics: bf16 products with f32 accumulation (the MXU contract,
applied on the VPU), f32 softmax statistics, probabilities cast to
the value dtype for the PV product — tested head-to-head against the
jnp reference in tests/test_flash_decode.py.

On the installed jax 0.9.0 / libtpu 0.0.34 (chip_smoke.py, 2026-09-26)
both kernels compile and match the float32 reference at B=16, S=2048,
D=1024 (8 heads of 128), K+1=5. History (one v5e, 2026-07-30/31, an
earlier toolchain; BASELINE.md r4/r5 — not re-measured):
B=64 12L/512d S=2048 ran 2.07 ms/step at short prefixes and 9.2
ms/step at a ~full cache, against 21.7 ms/step for the round-3 jnp
path; a (bs, bb) sweep found
full-cache time invariant to block geometry, bs=128 best at short
prefixes (finer prefix read), and 4MB cache blocks failing to compile
— hence the 128-row, 2MB defaults below, which this module keeps
until a chip run on the installed toolchain says otherwise.
"""
from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp

Array = jax.Array

NEG_INF = -1e30


def _largest_divisor(n: int, cap: int) -> int:
    """Largest power-of-two divisor of n that is <= cap (n itself when
    n <= cap). The cap is floored to a power of two first — halving
    down from a non-power-of-two cap (e.g. 10 for d=384 caches) would
    skip valid divisors like 8 and land on a needlessly small block."""
    if n <= cap:
        return n
    b = 1 << (cap.bit_length() - 1)
    while n % b and b > 1:
        b //= 2
    return b if n % b == 0 else 1


def reference_decode_attention(q: Array, k_cache: Array, v_cache: Array,
                               pos, n_heads: int,
                               scale: Optional[float] = None,
                               k_scale: Optional[Array] = None,
                               v_scale: Optional[Array] = None) -> Array:
    """jnp reference: q [B, H, Dh] at position ``pos`` attends cache
    rows 0..pos (inclusive) of k/v [B, S, D=H*Dh]. Returns [B, H, Dh].

    ``pos`` may be a scalar (every batch row at the same prefix — the
    fused-generate path) or a [B] vector (each row masked to ITS OWN
    filled prefix — the slotted/paged per-slot decode).

    ``k_scale``/``v_scale`` ([B, S] float32, quantized-KV pools,
    quant/kv.py): per-row dequantization scales folded into the scores
    and probabilities — ``(q·k_int)·kscale_s`` then
    ``(p·vscale_s)·v_int`` — exactly the slot-pool quantized-attention
    algebra, with the SAME multiplication order (scale-of-row before
    1/sqrt(d)) so fusing the call sites stays bit-identical. Scaled
    calls promote the cache to f32 (int8/fp8 storage) and return in
    ``q.dtype``."""
    b, s, d = k_cache.shape
    h = n_heads
    dh = d // h
    if scale is None:
        scale = 1.0 / (dh ** 0.5)
    pos = jnp.asarray(pos)
    bound = pos[:, None, None] if pos.ndim else pos
    if k_scale is None:
        kh = k_cache.reshape(b, s, h, dh)
        vh = v_cache.reshape(b, s, h, dh)
        sc = jnp.einsum("bhd,bshd->bhs", q, kh).astype(jnp.float32) \
            * scale
        sc = jnp.where(jnp.arange(s)[None, None, :] <= bound, sc,
                       NEG_INF)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("bhs,bshd->bhd", p.astype(q.dtype), vh)
    kh = k_cache.astype(jnp.float32).reshape(b, s, h, dh)
    vh = v_cache.astype(jnp.float32).reshape(b, s, h, dh)
    sc = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32), kh) \
        * k_scale[:, None, :] * scale
    sc = jnp.where(jnp.arange(s)[None, None, :] <= bound, sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    a = jnp.einsum("bhs,bshd->bhd", p * v_scale[:, None, :], vh)
    return a.astype(q.dtype)


def reference_window_attention(q: Array, k_cache: Array, v_cache: Array,
                               pos, n_heads: int,
                               scale: Optional[float] = None,
                               k_scale: Optional[Array] = None,
                               v_scale: Optional[Array] = None) -> Array:
    """jnp reference for the speculative-verify WINDOW: q [B, T, H, Dh]
    holds T = K+1 query rows per batch row; row t sits at position
    ``pos[b] + t`` and attends cache rows 0..pos[b]+t of k/v
    [B, S, D=H*Dh]. Returns [B, T, H, Dh].

    This is the spec verify pass's inline masked-softmax algebra,
    copied EXACTLY — same einsum contractions ("bthd,bshd->bhts" /
    "bhts,bshd->bthd"), same cast order (float path: einsum in the
    activation dtype then ``.astype(f32) * scale``; quantized path:
    f32 einsum ``* k_scale * scale``, probabilities ``* v_scale``, PV
    cast back to ``q.dtype``), same clipped per-row bound — so routing
    parallel/serving.py's verify_phase call sites through this one
    primitive is bit-identical, which is what keeps speculative decode
    token-exact against sequential decode (and the pipelined spec
    engine token-exact against the sync one)."""
    b, t, h, dh = q.shape
    s = k_cache.shape[-2]
    if scale is None:
        scale = 1.0 / (dh ** 0.5)
    pos = jnp.asarray(pos)
    posw = pos[:, None] + jnp.arange(t, dtype=pos.dtype)[None, :]
    wp = jnp.clip(posw, 0, s - 1)
    if k_scale is None:
        kh = k_cache.reshape(b, s, h, dh)
        vh = v_cache.reshape(b, s, h, dh)
        sc = jnp.einsum("bthd,bshd->bhts", q, kh) \
            .astype(jnp.float32) * scale
        sc = jnp.where(jnp.arange(s)[None, None, None, :]
                       <= wp[:, None, :, None], sc, NEG_INF)
        pr = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("bhts,bshd->bthd", pr.astype(q.dtype), vh)
    kh = k_cache.astype(jnp.float32).reshape(b, s, h, dh)
    vh = v_cache.astype(jnp.float32).reshape(b, s, h, dh)
    sc = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32), kh) \
        * k_scale[:, None, None, :] * scale
    sc = jnp.where(jnp.arange(s)[None, None, None, :]
                   <= wp[:, None, :, None], sc, NEG_INF)
    pr = jax.nn.softmax(sc, axis=-1)
    a = jnp.einsum("bhts,bshd->bthd", pr * v_scale[:, None, None, :],
                   vh)
    return a.astype(q.dtype)


def _decode_kernel(blk_ref, pos_ref, q_ref, k_ref, v_ref, o_ref, m_scr,
                   l_scr, acc_scr, *, scale: float, h: int, bs: int,
                   n_blocks: int):
    import jax.experimental.pallas as pl

    i = pl.program_id(0)
    j = pl.program_id(1)
    # per-batch-block prefix bound (max over the block's rows): the DMA
    # clamp and the compute skip both use it, while the per-ROW mask
    # below uses each row's own pos — the slotted pools' per-slot
    # prefixes ride the same kernel as the fused path's shared scalar
    # (which arrives here broadcast to a constant [B] vector).
    last = blk_ref[i] // bs

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(j <= last)
    def _block():
        q = q_ref[...]                     # [bb, H, Dh]
        k = k_ref[...]                     # [bb, bs, D]
        v = v_ref[...]
        if k.ndim == 4:                    # stacked-cache block [1,...]
            k, v = k[0], v[0]
        dh = q.shape[-1]
        # Per-head scores/PV as elementwise multiply + reduce on the
        # lane-sliced cache columns: Mosaic rejects batched dot_general
        # in this kernel on the real backend
        # ("#tpu.dot_dimension_numbers ... expected integer value"),
        # and at one query row per head the op is bandwidth-bound —
        # the VPU mul-reduce is noise next to the cache block DMA.
        # Scores are kept [bb, bs, H] (heads on the lane axis) so every
        # head access below is a PURE slice — mixed integer/None
        # indexing (q[:, hh, None, :]) lowers to a >2-D gather, which
        # Mosaic refuses ("Only 2D gather is supported").
        sc = []
        for hh in range(h):
            kh = k[:, :, hh * dh:(hh + 1) * dh]
            qh = q[:, hh:hh + 1, :]                        # [bb, 1, Dh]
            sc.append(jnp.sum(kh * qh, axis=-1,
                              dtype=jnp.float32))          # [bb, bs]
        s = jnp.stack(sc, axis=-1) * scale                 # [bb, bs, H]
        ki = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * bs
        s = jnp.where(ki <= pos_ref[...], s, NEG_INF)     # pos [bb,1,1]
        m_prev = m_scr[...]                                # [bb, H]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None, :])                 # [bb, bs, H]
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1)
        m_scr[...] = m_new
        # per-head accumulator update via slice stores (a 3-D stack of
        # the per-head PV rows trips Mosaic: "result/input offset
        # mismatch on non-concat dimension")
        for hh in range(h):
            vh = v[:, :, hh * dh:(hh + 1) * dh]
            pv = jnp.sum(p[:, :, hh:hh + 1].astype(v.dtype) * vh,
                         axis=1, dtype=jnp.float32)        # [bb, Dh]
            acc_scr[:, hh:hh + 1, :] = (
                acc_scr[:, hh:hh + 1, :]
                * corr[:, hh:hh + 1][..., None]
                + pv[:, None, :])

    @pl.when(j == n_blocks - 1)
    def _out():
        o_ref[...] = (acc_scr[...]
                      / l_scr[...][..., None]).astype(o_ref.dtype)


def decode_attention_available(q: Array, k_cache: Array) -> bool:
    """Kernel eligibility: TPU backend (or forced interpret via
    DL4JTPU_FLASH=interpret; =0 disables), supported dtype, head-dim a
    lane-friendly multiple of 8, and batch/cache extents the block
    search can tile. ``k_cache`` may be [B, S, D] or the stacked
    [L, B, S, D] (with ``layer`` selecting the plane in the BlockSpec,
    see decode_attention)."""
    env = os.environ.get("DL4JTPU_FLASH", "auto")
    if env == "0":
        return False
    if q.ndim != 3 or k_cache.ndim not in (3, 4):
        return False
    if q.dtype not in (jnp.bfloat16, jnp.float16, jnp.float32):
        return False
    b, h, dh = q.shape
    s = k_cache.shape[-2]
    if dh % 8 != 0 or s < 128:
        return False
    if env == "interpret":
        return True
    return jax.default_backend() == "tpu"


def decode_attention(q: Array, k_cache: Array, v_cache: Array, pos,
                     n_heads: int, scale: Optional[float] = None,
                     layer: int = 0, k_scale: Optional[Array] = None,
                     v_scale: Optional[Array] = None) -> Array:
    """Dispatching decode attention: q [B, H, Dh] at position ``pos``
    (cache row ``pos`` already written) attends rows 0..pos of the
    flattened-head caches. Returns [B, H, Dh]. ``pos`` may be traced
    (it is, inside generate's sampling scan), and may be a [B] VECTOR
    — each row masked (and, on the kernel path, DMA-bounded per batch
    block) to its own filled prefix, which is what lets the slotted /
    paged per-slot decode and the speculative verify share this one
    primitive with the fused path.

    ``k_scale``/``v_scale`` ([B, S]): quantized-KV per-row scales,
    folded into scores/probabilities (reference_decode_attention);
    scaled calls currently always take the jnp path (the kernel reads
    float caches only — int8 cache blocks + scale DMA is follow-up
    work, see docs/quantization.md).

    Caches may be [B, S, D] or the model's stacked [L, B, S, D] with a
    static ``layer``. Pass the STACKED buffer on the kernel path: XLA
    cannot fuse a slice into a custom call, so ``ck_all[layer]`` as an
    operand materializes a full [B, S, D] copy (264MB at the flagship
    decode shape) per layer per step — measured ~9ms of the round-3
    12ms step. The kernel instead picks the layer plane in the
    BlockSpec index_map, so only the blocks it DMAs are ever read."""
    if k_scale is not None or not decode_attention_available(q, k_cache):
        if k_cache.ndim == 4:
            k_cache, v_cache = k_cache[layer], v_cache[layer]
        return reference_decode_attention(q, k_cache, v_cache, pos,
                                          n_heads, scale,
                                          k_scale=k_scale,
                                          v_scale=v_scale)
    return _split_k_call(_decode_kernel, q, k_cache, v_cache, pos, layer,
                         scale, h=q.shape[1])


# a K/V block's cache rows and its bytes in VMEM: see _split_k_call
_BLOCK_ROWS = 128
_BLOCK_BYTES = 1 << 21


def _split_k_call(kernel, q3: Array, k_cache: Array, v_cache: Array, pos,
                  layer: int, scale: Optional[float], h: int,
                  window: int = 1) -> Array:
    """The one pallas_call both kernels share: q3 [B, P, Dh] (P = h
    heads, or window * h pseudo-heads) against [B, S, D] or stacked
    [L, B, S, D] caches. ``pos`` (scalar or [B]) is each row's mask
    position; the last cache row a batch row may read is pos + window
    - 1, and the per-batch-block maximum of that is the prefetched
    scalar that clamps the K/V DMA and skips the compute past it.

    Block geometry: bs=128 cache rows is the prefix-read granularity,
    and the batch block keeps each K/V block within 2MB of VMEM (~8MB
    in flight double-buffered), sized by the cache's actual itemsize.
    Both were chosen by a sweep on an earlier toolchain (module
    docstring) and are unchanged here.

    ``window`` (T query rows per batch row) divides the block budget:
    Mosaic keeps one [bb, bs, Dh] f32 product live per unrolled
    pseudo-head, so at T*H pseudo-heads the 2MB block that fits the
    decode kernel asks for 28MB of scoped VMEM against a 16MB limit
    (libtpu 0.0.34, K+1=5, H=8, D=1024). A T-times smaller batch block
    gives the window kernel the decode kernel's footprint."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from deeplearning4j_tpu.ops.pallas_util import (interpret_arg,
                                                    out_struct)

    b, p, dh = q3.shape
    s, d = k_cache.shape[-2], k_cache.shape[-1]
    if scale is None:
        scale = 1.0 / (dh ** 0.5)
    pos_rows = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    reach = jnp.minimum(pos_rows + (window - 1), s - 1)
    bs = _largest_divisor(s, _BLOCK_ROWS)
    itemsize = jnp.dtype(k_cache.dtype).itemsize
    bb = _largest_divisor(
        b, max(1, _BLOCK_BYTES // max(1, window * bs * d * itemsize)))
    n_blocks = s // bs
    reach_blk = jnp.max(reach.reshape(b // bb, bb), axis=1)

    if k_cache.ndim == 4:
        kv_block = (1, bb, bs, d)

        def kv_map(i, j, blk_ref):
            return (layer, i, jnp.minimum(j, blk_ref[i] // bs), 0)
    else:
        kv_block = (bb, bs, d)

        def kv_map(i, j, blk_ref):
            return (i, jnp.minimum(j, blk_ref[i] // bs), 0)

    def row_map(i, j, blk_ref):
        return (i, 0, 0)

    return pl.pallas_call(
        functools.partial(kernel, scale=float(scale), h=h, bs=bs,
                          n_blocks=n_blocks),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b // bb, n_blocks),
            in_specs=[
                pl.BlockSpec((bb, 1, 1), row_map),
                pl.BlockSpec((bb, p, dh), row_map),
                pl.BlockSpec(kv_block, kv_map),
                pl.BlockSpec(kv_block, kv_map),
            ],
            out_specs=pl.BlockSpec((bb, p, dh), row_map),
            scratch_shapes=[
                pltpu.VMEM((bb, p), jnp.float32),
                pltpu.VMEM((bb, p), jnp.float32),
                pltpu.VMEM((bb, p, dh), jnp.float32),
            ],
        ),
        out_shape=out_struct((b, p, dh), q3.dtype, reach_blk, pos_rows,
                             q3, k_cache, v_cache),
        interpret=interpret_arg(
            os.environ.get("DL4JTPU_FLASH") == "interpret",
            q3, k_cache, v_cache),
        name="flash_decode" if window == 1 else "flash_decode_window",
    )(reach_blk, pos_rows.reshape(b, 1, 1), q3, k_cache, v_cache)


def _window_kernel(blk_ref, pos_ref, q_ref, k_ref, v_ref, o_ref, m_scr,
                   l_scr, acc_scr, *, scale: float, h: int, bs: int,
                   n_blocks: int):
    """_decode_kernel generalized to a T-row speculative-verify window
    per batch row, by flattening the window into the head axis: q
    arrives [bb, T*H, Dh] where pseudo-head p = t*H + hh is window row
    t of real head hh. Each pseudo-head's score row slices the SAME
    H-head cache block (hh = p % h) but masks to its own bound
    pos + p // h — a static per-pseudo-head offset riding the existing
    per-row vector-pos mask. Everything else (online softmax, per-head
    mul-reduce, slice-store accumulators) is the decode kernel
    verbatim, so one cache-block DMA serves all T window rows — the
    T-fold read amplification of calling the decode kernel per window
    row is exactly what this variant removes."""
    import jax.experimental.pallas as pl

    i = pl.program_id(0)
    j = pl.program_id(1)
    # DMA clamp: blk_ref already includes the +T-1 window reach (the
    # dispatch adds it), so a block covers its furthest WINDOW row.
    last = blk_ref[i] // bs

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(j <= last)
    def _block():
        q = q_ref[...]                     # [bb, T*H, Dh]
        k = k_ref[...]                     # [bb, bs, D]
        v = v_ref[...]
        if k.ndim == 4:                    # stacked-cache block [1,...]
            k, v = k[0], v[0]
        _, th, dh = q.shape
        sc = []
        for p_i in range(th):
            hh = p_i % h                   # real head of pseudo-head
            kh = k[:, :, hh * dh:(hh + 1) * dh]
            qh = q[:, p_i:p_i + 1, :]                      # [bb, 1, Dh]
            sc.append(jnp.sum(kh * qh, axis=-1,
                              dtype=jnp.float32))          # [bb, bs]
        s = jnp.stack(sc, axis=-1) * scale              # [bb, bs, T*H]
        ki = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * bs
        # static window offset per pseudo-head: row t attends to
        # pos + t. Unclipped bound == the reference's clip(pos+t, s-1)
        # bound — ki never exceeds s-1, so the masks are identical.
        off = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2) // h
        s = jnp.where(ki <= pos_ref[...] + off, s, NEG_INF)
        # blocks wholly past a row's bound are exact no-ops under the
        # running stats: all-NEG_INF scores leave m unchanged (finite
        # -1e30 < any live max), p underflows to 0, corr = 1.
        m_prev = m_scr[...]                              # [bb, T*H]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None, :])               # [bb, bs, T*H]
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1)
        m_scr[...] = m_new
        for p_i in range(th):
            hh = p_i % h
            vh = v[:, :, hh * dh:(hh + 1) * dh]
            pv = jnp.sum(p[:, :, p_i:p_i + 1].astype(v.dtype) * vh,
                         axis=1, dtype=jnp.float32)        # [bb, Dh]
            acc_scr[:, p_i:p_i + 1, :] = (
                acc_scr[:, p_i:p_i + 1, :]
                * corr[:, p_i:p_i + 1][..., None]
                + pv[:, None, :])

    @pl.when(j == n_blocks - 1)
    def _out():
        o_ref[...] = (acc_scr[...]
                      / l_scr[...][..., None]).astype(o_ref.dtype)


def window_attention_available(q: Array, k_cache: Array) -> bool:
    """Kernel eligibility for the verify window: decode_attention's
    gates with a 4-D q [B, T, H, Dh] (T = K+1 window rows)."""
    env = os.environ.get("DL4JTPU_FLASH", "auto")
    if env == "0":
        return False
    if q.ndim != 4 or k_cache.ndim not in (3, 4):
        return False
    if q.dtype not in (jnp.bfloat16, jnp.float16, jnp.float32):
        return False
    b, t, h, dh = q.shape
    s = k_cache.shape[-2]
    if dh % 8 != 0 or s < 128:
        return False
    if env == "interpret":
        return True
    return jax.default_backend() == "tpu"


def decode_window_attention(q: Array, k_cache: Array, v_cache: Array,
                            pos, n_heads: int,
                            scale: Optional[float] = None,
                            layer: int = 0,
                            k_scale: Optional[Array] = None,
                            v_scale: Optional[Array] = None) -> Array:
    """Dispatching K+1-window attention for the speculative verify
    pass: q [B, T, H, Dh] — window row t of batch row b sits at
    position ``pos[b] + t`` (its cache row already written) and
    attends rows 0..pos[b]+t. Returns [B, T, H, Dh].

    The kernel path flattens the window into the head axis (q ->
    [B, T*H, Dh]) so every cache block is DMA'd ONCE for all T window
    rows — same split-K geometry, prefetched-scalar DMA clamp
    (extended by T-1 rows of window reach), and per-head mul-reduce as
    decode_attention, with a static per-pseudo-head position offset in
    the mask. Off-TPU (and for quantized caches, which fold
    ``k_scale``/``v_scale`` per-row exactly like
    reference_decode_attention) it takes the jnp reference, which
    reproduces the verify pass's historical inline algebra bit-for-
    bit. Caches may be [B, S, D] or stacked [L, B, S, D] with a static
    ``layer`` (plane selected in the BlockSpec index_map on the kernel
    path, never materialized)."""
    if k_scale is not None or not window_attention_available(q, k_cache):
        if k_cache.ndim == 4:
            k_cache, v_cache = k_cache[layer], v_cache[layer]
        return reference_window_attention(q, k_cache, v_cache, pos,
                                          n_heads, scale,
                                          k_scale=k_scale,
                                          v_scale=v_scale)
    b, t, h, dh = q.shape
    out = _split_k_call(_window_kernel, q.reshape(b, t * h, dh), k_cache,
                        v_cache, pos, layer, scale, h=h, window=t)
    return out.reshape(b, t, h, dh)
