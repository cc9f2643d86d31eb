"""Ring attention — sequence/context parallelism.

NET-NEW vs the reference (no attention, no sequence parallelism; SURVEY.md
§5.7): the sequence axis is sharded over the mesh's 'seq' axis and K/V blocks
rotate around the ring via `lax.ppermute` while each device accumulates its
queries' attention with an online (flash-style) softmax. Communication is
neighbor-to-neighbor — exactly the ICI-friendly pattern — and compute for the
current block overlaps the next block's transfer inside the XLA schedule.

Causality is applied on GLOBAL positions (block offsets from
`lax.axis_index`), so the math matches single-device causal attention
exactly; fully-masked future blocks contribute nothing because the running
max starts from the local (always partially valid) diagonal block.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

Array = jax.Array

NEG_INF = -1e30


def ring_attention(q: Array, k: Array, v: Array, axis_name: str, *,
                   causal: bool = True,
                   scale: Optional[float] = None) -> Array:
    """Blockwise ring attention inside a `shard_map`.

    q, k, v: LOCAL blocks [B, Tl, H, Dh]; the global sequence length is
    Tl * axis_size. Returns the local output block [B, Tl, H, Dh].
    Accumulation is float32 throughout.
    """
    s = lax.psum(1, axis_name)          # ring size (static under jit)
    idx = lax.axis_index(axis_name)
    b, tl, h, dh = q.shape
    scale = (1.0 / jnp.sqrt(dh)) if scale is None else scale
    q32 = q.astype(jnp.float32)
    q_off = idx * tl
    qpos = q_off + jnp.arange(tl)

    # carry: running max m [B,H,Tl], normalizer l [B,H,Tl],
    # accumulator acc [B,H,Tl,Dh], and the rotating k/v blocks.
    # pcast: the initial accumulators are constants, but the scan carry is
    # device-varying over the ring axis — the vma type system requires the
    # init to be marked varying too.
    def vary(x):
        return lax.pcast(x, (axis_name,), to="varying")

    m0 = vary(jnp.full((b, h, tl), NEG_INF, jnp.float32))
    l0 = vary(jnp.zeros((b, h, tl), jnp.float32))
    acc0 = vary(jnp.zeros((b, h, tl, dh), jnp.float32))
    perm = [(i, (i + 1) % s) for i in range(s)]

    def step(carry, sidx):
        m, l, acc, kb, vb = carry
        kv_idx = (idx - sidx) % s
        kpos = kv_idx * tl + jnp.arange(tl)
        scores = jnp.einsum("bthd,bshd->bhts", q32,
                            kb.astype(jnp.float32)) * scale
        if causal:
            cm = qpos[:, None] >= kpos[None, :]
            scores = jnp.where(cm[None, None], scores, NEG_INF)
        blk_max = jnp.max(scores, axis=-1)
        new_m = jnp.maximum(m, blk_max)
        # guard: rows with no valid key yet keep exp(NEG_INF-NEG_INF)=1 from
        # poisoning l — mask p where scores are NEG_INF
        p = jnp.exp(scores - new_m[..., None])
        p = jnp.where(scores <= NEG_INF / 2, 0.0, p)
        corr = jnp.exp(m - new_m)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhts,bshd->bhtd", p, vb.astype(jnp.float32))
        kb = lax.ppermute(kb, axis_name, perm)
        vb = lax.ppermute(vb, axis_name, perm)
        return (new_m, l, acc, kb, vb), None

    (m, l, acc, _, _), _ = lax.scan(step, (m0, l0, acc0, k, v),
                                    jnp.arange(s))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return jnp.einsum("bhtd->bthd", out).astype(q.dtype)
