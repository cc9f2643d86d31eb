"""Head-packing experiment for the Dh=64 fused attention backward
(VERDICT r3 #3).

Hypothesis under test: the flagship d=512 config's 107 ms fused
backward dominates its 185 ms step, and its per-program operands are
64 wide (head_dim) — packing TWO Dh=64 heads per program (grid
(B*H/2, nsb), tile-level slot interleave) might recover utilization
via shared per-program overhead, halved program count, and more
independent work for Mosaic to overlap (MXU of one head's tile against
VPU exp of the other's).

What packing can NOT do here, for the record: merge the per-head MXU
contractions. Attention contracts each head's Dh independently —
concatenating two heads' Dh columns into one 128-wide GEMM sums
cross-head products (wrong), and the block-diagonal embedding that
fixes it doubles the MAC count, so the only honest formulation is two
per-head GEMM sequences per program, interleaved. The exp/mask panel
work is [bq, bk] = [128, 256] — already full 128-lane registers — so
the VPU-softmax floor (BASELINE.md round-3 notes) is untouched by
packing.

Run (on the chip): PYTHONPATH=. python
benchmarks/headpack_experiment.py
Prints one JSON line per variant (ms per fused-backward call at the
flagship shape, best-of-3 over a 10-call scanned program) plus a
correctness check of the packed kernel against the production one.

MEASURED RESULT (r4, 5 standalone runs + 2x2 interleaved flagship A/B)
— NEGATIVE, the experiment is kept as the record:

- packed2 vs the q-chunked production control: 1.001 / 1.001 / 0.978 /
  0.944 — packing two heads per program buys NOTHING once chunking is
  equalized. The analysis in the header is why: per-head GEMMs cannot
  merge, and the exp/mask panels were never lane-starved.
- standalone runs showed the monolithic production call bimodal (8.7 /
  11.9 ms) vs chunked ~7.4-9.1, suggesting q-chunking helps — but the
  END-TO-END flagship A/B (DL4JTPU_BWD_Q_CHUNK=512 vs 4096,
  interleaved) measured 208.7/208.5 ms-per-step chunked vs 179.3/178.9
  unchunked: chunking COSTS 16% in the real training program (4x K/V
  re-reads + 4x call overhead; the microbench bimodality was a cold
  window artifact). Production keeps the monolithic call.
- Flagship d=512 MFU therefore stays 28.1% with the config-bound
  justification (same code at d1024/head-dim-128: 49.5%) — now backed
  by this measured dead end rather than an untried idea.
"""
from __future__ import annotations

import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.ops.flash_attention import (_flash_backward,
                                                    _flash_forward,
                                                    _flash_dqkv_kernel,
                                                    _inner_block)


def _packed2_kernel(q_ref, k_ref, v_ref, do_ref, m_ref, logl_ref,
                    delta_ref, dq_ref, dk_ref, dv_ref, dq_acc, *,
                    scale, causal, qo, ko, bq, bk):
    """Two batch-heads per program: per k-tile, both slots' q-loops run
    back-to-back (tile-level interleave). Body math is the production
    kernel's (shared _masked_scores/_qtile_bounds via the slot-sliced
    refs)."""
    import jax.experimental.pallas as pl

    from deeplearning4j_tpu.ops.flash_attention import (_masked_scores,
                                                        _qtile_bounds)

    tq, d = q_ref.shape[1], q_ref.shape[2]
    ksb = k_ref.shape[1]
    nqb = tq // bq
    skip_safe = causal and ko <= qo
    k_base = pl.program_id(1) * ksb

    @pl.when(pl.program_id(1) == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def k_tile(jk, _):
        ki0 = k_base + jk * bk + ko
        if skip_safe:
            start = jnp.maximum(0, -(-(ki0 - qo - (bq - 1)) // bq))
        else:
            start = 0
        if causal:
            full_start = jnp.clip(-(-(ki0 + bk - 1 - qo) // bq),
                                  start, nqb)
        else:
            full_start = start

        for slot in range(2):
            k = k_ref[slot, pl.ds(jk * bk, bk), :]
            v = v_ref[slot, pl.ds(jk * bk, bk), :]

            def make_body(masked, slot=slot, k=k, v=v):
                def body(i, carry):
                    dk, dv = carry
                    qi = q_ref[slot, pl.ds(i * bq, bq), :]
                    doi = do_ref[slot, pl.ds(i * bq, bq), :]
                    mi = m_ref[slot, pl.ds(i * bq, bq), :]
                    logli = logl_ref[slot, pl.ds(i * bq, bq), :]
                    deltai = delta_ref[slot, pl.ds(i * bq, bq), :]
                    s, valid = _masked_scores(qi, k, scale, masked,
                                              i * bq + qo, ki0)
                    p = jnp.exp(s - (mi + logli)) if skip_safe \
                        else jnp.exp((s - mi) - logli)
                    dv = dv + jax.lax.dot_general(
                        p.astype(doi.dtype), doi,
                        (((0,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    dp = jax.lax.dot_general(
                        doi, v, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    ds = p * (dp - deltai)
                    if valid is not None:
                        ds = jnp.where(valid, ds, 0.0)
                    dsq = ds.astype(qi.dtype)
                    dk = dk + jax.lax.dot_general(
                        dsq, qi, (((0,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    dq_acc[slot, pl.ds(i * bq, bq), :] += \
                        jax.lax.dot_general(
                            dsq, k, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
                    return dk, dv
                return body

            init = (jnp.zeros((bk, d), jnp.float32),
                    jnp.zeros((bk, d), jnp.float32))
            carry = jax.lax.fori_loop(start, full_start,
                                      make_body(causal), init)
            dk, dv = jax.lax.fori_loop(full_start, nqb,
                                       make_body(False), carry)
            dk_ref[slot, pl.ds(jk * bk, bk), :] = \
                (dk * scale).astype(dk_ref.dtype)
            dv_ref[slot, pl.ds(jk * bk, bk), :] = dv.astype(dv_ref.dtype)
        return ()

    jax.lax.fori_loop(0, ksb // bk, k_tile, ())
    dq_ref[...] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def packed2_backward(q3, k3, v3, o3, m, logl, g, scale, causal,
                     q_offset, kv_offset):
    """Packed variant needs HALF the q-extent per call: two slots'
    lane-padded [T, 1] stat columns alone are 6MB at T=2048 and the
    whole residency hit 26MB > the 16MB scoped-VMEM limit (measured,
    diagnostic preserved here) — so the packed experiment q-chunks at
    512 (dk/dv sum over chunks, dq concatenates; the production
    kernel's _BWD_Q_CHUNK pattern)."""
    tq = q3.shape[1]
    chunk = 512
    if tq > chunk and tq % chunk == 0:
        dqs, dk, dv = [], None, None
        for lo in range(0, tq, chunk):
            sl = slice(lo, lo + chunk)
            dq_c, dk_c, dv_c = _packed2_call(
                q3[:, sl], k3, v3, o3[:, sl], m[:, sl], logl[:, sl],
                g[:, sl], scale, causal, q_offset + lo, kv_offset)
            dqs.append(dq_c)
            dk = dk_c.astype(jnp.float32) if dk is None \
                else dk + dk_c.astype(jnp.float32)
            dv = dv_c.astype(jnp.float32) if dv is None \
                else dv + dv_c.astype(jnp.float32)
        return (jnp.concatenate(dqs, axis=1), dk.astype(k3.dtype),
                dv.astype(v3.dtype))
    return _packed2_call(q3, k3, v3, o3, m, logl, g, scale, causal,
                         q_offset, kv_offset)


def _packed2_call(q3, k3, v3, o3, m, logl, g, scale, causal,
                  q_offset, kv_offset):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, tq, d = q3.shape
    assert bh % 2 == 0
    sk = k3.shape[1]
    bq = _inner_block(tq)
    bk = _inner_block(sk, 256)
    delta = jnp.sum(g.astype(jnp.float32) * o3.astype(jnp.float32), -1,
                    keepdims=True)
    statics = dict(scale=scale, causal=causal, qo=int(q_offset),
                   ko=int(kv_offset), bq=bq, bk=bk)
    full = pl.BlockSpec((2, tq, d), lambda b, j: (b, 0, 0))
    kspec = pl.BlockSpec((2, sk, d), lambda b, j: (b, j, 0))
    col = pl.BlockSpec((2, tq, 1), lambda b, j: (b, 0, 0))
    return pl.pallas_call(
        functools.partial(_packed2_kernel, **statics),
        out_shape=[jax.ShapeDtypeStruct((bh, tq, d), q3.dtype),
                   jax.ShapeDtypeStruct((bh, sk, d), k3.dtype),
                   jax.ShapeDtypeStruct((bh, sk, d), v3.dtype)],
        grid=(bh // 2, 1),
        in_specs=[full, kspec, kspec, full, col, col, col],
        out_specs=[full, kspec, kspec],
        scratch_shapes=[pltpu.VMEM((2, tq, d), jnp.float32)],
    )(q3, k3, v3, g, m, logl, delta)


def main():
    B, H, T, Dh = 16, 8, 2048, 64      # flagship attention shape
    bh = B * H
    scale = 1.0 / (Dh ** 0.5)
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q3 = jax.random.normal(ks[0], (bh, T, Dh), jnp.bfloat16)
    k3 = jax.random.normal(ks[1], (bh, T, Dh), jnp.bfloat16)
    v3 = jax.random.normal(ks[2], (bh, T, Dh), jnp.bfloat16)
    g = jax.random.normal(ks[3], (bh, T, Dh), jnp.bfloat16)
    o3, m, logl = jax.jit(lambda a, b, c: _flash_forward(
        a, b, c, scale, True, 0, 0, False))(q3, k3, v3)

    prod = jax.jit(lambda *a: _flash_backward(*a, scale, True, 0, 0,
                                              False))
    pack = jax.jit(lambda *a: packed2_backward(*a, scale, True, 0, 0))

    def chunked_prod(q3, k3, v3, o3, m, logl, g, chunk=512):
        """Attribution control: the PRODUCTION kernel host-q-chunked
        exactly like the packed variant — separates 'chunking helps'
        from 'packing helps'."""
        dqs, dk, dv = [], None, None
        for lo in range(0, q3.shape[1], chunk):
            sl = slice(lo, lo + chunk)
            dq_c, dk_c, dv_c = _flash_backward(
                q3[:, sl], k3, v3, o3[:, sl], m[:, sl], logl[:, sl],
                g[:, sl], scale, True, lo, 0, False)
            dqs.append(dq_c)
            dk = dk_c.astype(jnp.float32) if dk is None \
                else dk + dk_c.astype(jnp.float32)
            dv = dv_c.astype(jnp.float32) if dv is None \
                else dv + dv_c.astype(jnp.float32)
        return (jnp.concatenate(dqs, axis=1), dk.astype(k3.dtype),
                dv.astype(v3.dtype))

    chunk_ctl = jax.jit(chunked_prod)

    # correctness: packed == production on identical inputs
    dq1, dk1, dv1 = prod(q3, k3, v3, o3, m, logl, g)
    dq2, dk2, dv2 = pack(q3, k3, v3, o3, m, logl, g)
    for a, b, name in ((dq1, dq2, "dq"), (dk1, dk2, "dk"),
                       (dv1, dv2, "dv")):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=2e-2, atol=2e-2, err_msg=name)

    def timed(fn, n=10, reps=3):
        def run(q3, k3, v3, o3, m, logl, g):
            def body(c, _):
                dq, dk, dv = fn(q3, k3, v3, o3, m, logl, g)
                return (c + dq.astype(jnp.float32).sum()
                        + dk.astype(jnp.float32).sum()
                        + dv.astype(jnp.float32).sum()), ()
            c, _ = jax.lax.scan(body, jnp.asarray(0.0, jnp.float32),
                                None, length=n)
            return c
        f = jax.jit(run)
        float(f(q3, k3, v3, o3, m, logl, g))
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            float(f(q3, k3, v3, o3, m, logl, g))
            best = min(best, time.perf_counter() - t0)
        return best / n * 1e3

    base = timed(prod)
    packed = timed(pack)
    ctl = timed(chunk_ctl)
    print(json.dumps({"experiment": "headpack2_fused_backward",
                      "shape": f"bh{bh}_T{T}_Dh{Dh}",
                      "production_ms": round(base, 2),
                      "packed2_q512_ms": round(packed, 2),
                      "production_q512_ms": round(ctl, 2),
                      "speedup_vs_production": round(base / packed, 3),
                      "speedup_vs_chunked_control": round(ctl / packed,
                                                          3)}),
          flush=True)


if __name__ == "__main__":
    main()
