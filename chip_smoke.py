#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system starts on the chip.

    python chip_smoke.py                 # one TPU chip; what the driver runs
    python chip_smoke.py --four-chips    # instead: the model spread over four
    python chip_smoke.py --rehearsal     # CPU, toy sizes, interpreted kernels

One process, no arguments, no network. It drives the two main paths through
the entry points a user calls: `make_parallel_train_step` for a few steps and
`InferenceEngine` for a few requests — at the full width of the widest dense
model the repo has run (12 layers, d_model 1024, 8 heads of 128, T = max_len =
2048, bf16, vocab 32768 with xent_chunk 2048; weights random from a seed), and
first checks that the four Pallas kernels lower to Mosaic and agree with their
float32 `jax.numpy` references. Every leg that fails makes the exit code
non-zero. The times it prints are set-up information (compile, run), not a
benchmark.

It refuses to start without a TPU, or with the kernels forced off or into the
interpreter: nothing here runs on the CPU unless `--rehearsal` says so, and
then every line says `rehearsal`. The last line of standard output is
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import json
import os
import sys
import time
import traceback

# max |kernel - reference| <= KERNEL_TOL * max(1, max |reference|): bf16 keeps
# 8 significant bits (spacing 2^-7 relative), and the kernels round the
# probabilities and the outputs to bf16 once each, so 2^-6 of the output
# scale is two roundings' worth.
KERNEL_TOL = 2.0 ** -6
# A generated token must score within REF_TOL standard deviations (of the
# float32 reference's logits) of the reference's best token at its position.
# Random weights give near-ties, and bf16 flips a near-tie as soon as two runs
# round differently, so token equality cannot be asked between two different
# schedules of the same request — agreement with the reference, to a margin
# that a few tokens in 32768 meet by chance, can.
REF_TOL = 0.1
FORBIDDEN_KERNEL_MODES = ("interpret", "0")
KERNEL_SWITCHES = ("DL4JTPU_FLASH", "DL4JTPU_FUSED_LSTM")


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Everything that differs between the chip run and the CPU rehearsal."""
    n_layers: int = 12
    d_model: int = 1024
    n_heads: int = 8
    seq: int = 2048
    vocab: int = 32768
    xent_chunk: int = 2048
    train_batch: int = 8
    attn_batch: int = 8             # x n_heads = 64 batch-heads
    lstm: tuple = (256, 256, 64, 256)        # B, T, F, H
    slots: int = 16
    prefill_chunk: int = 256
    prompt_lens: tuple = (100, 1500)
    shared_prefix: int = 512
    wave: int = 8
    new_tokens: int = 32
    spec_requests: int = 4


FULL = Sizes()
TOY = Sizes(n_layers=2, d_model=64, n_heads=4, seq=256, vocab=512,
            xent_chunk=128, train_batch=2, attn_batch=1,
            lstm=(8, 8, 16, 128), slots=4, prefill_chunk=64,
            prompt_lens=(20, 150), shared_prefix=64, wave=4, new_tokens=8,
            spec_requests=2)

_PREFIX = ""


def say(msg: str) -> None:
    print(f"{_PREFIX}{msg}", flush=True)


def check(cond, msg: str) -> None:
    """A failed check fails the leg (never `assert`: -O must not pass it)."""
    if not cond:
        raise RuntimeError(f"check failed: {msg}")


def within_tol(name: str, got, ref) -> dict:
    import numpy as np
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    check(got.shape == ref.shape, f"{name}: {got.shape} != {ref.shape}")
    check(bool(np.all(np.isfinite(got))), f"{name}: non-finite output")
    err, scale = float(np.abs(got - ref).max()), float(np.abs(ref).max())
    bound = KERNEL_TOL * max(1.0, scale)
    check(err <= bound,
          f"{name}: max abs error {err:.4g} exceeds {bound:.4g} "
          f"(2^-6 of the reference's scale {scale:.4g})")
    return {"max_abs_err": round(err, 6), "ref_scale": round(scale, 4)}


def lower_compile(name: str, jitted, *args, rehearsal: bool):
    """Lower and compile one jitted function; on the chip the lowered text
    must hold the Mosaic custom call, or the jnp reference was dispatched."""
    t0 = time.perf_counter()
    lowered = jitted.lower(*args)
    n_mosaic = lowered.as_text().count("tpu_custom_call")
    compiled = lowered.compile()
    secs = time.perf_counter() - t0
    check(rehearsal or n_mosaic > 0,
          f"{name}: no Mosaic custom call in the lowered program")
    return compiled, n_mosaic, secs


def run_timed(compiled, *args):
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    return out, time.perf_counter() - t0


# ---------------------------------------------------------------------------
# leg: kernels
# ---------------------------------------------------------------------------

def leg_kernels(sz: Sizes, rehearsal: bool) -> dict:
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.layers.attention import dot_product_attention
    from deeplearning4j_tpu.nn.layers.recurrent import LSTM
    from deeplearning4j_tpu.ops import flash_decode as fd
    from deeplearning4j_tpu.ops.flash_attention import \
        flash_attention_available
    from deeplearning4j_tpu.ops.lstm import fused_lstm_available

    out = {}
    h, dh = sz.n_heads, sz.d_model // sz.n_heads
    ks = jax.random.split(jax.random.PRNGKey(21), 10)
    f32 = jnp.float32

    def report(name, n_mosaic, t_compile, t_run, facts):
        out[name] = dict(facts, mosaic_calls=n_mosaic,
                         compile_s=round(t_compile, 2),
                         run_s=round(t_run, 3))
        say(f"kernels: {name} mosaic_calls={n_mosaic} "
            f"compile={t_compile:.2f}s run={t_run:.3f}s {facts}")

    # flash attention, forward and backward, causal
    b, t = sz.attn_batch, sz.seq
    q, k, v = (jax.random.normal(ks[i], (b, t, h, dh), jnp.bfloat16)
               for i in range(3))
    check(flash_attention_available(q, k, None),
          "flash_attention_available is false at the smoke shape")

    def attn_loss(q, k, v, mask=None):
        o = dot_product_attention(q, k, v, causal=True, mask=mask)
        return jnp.sum(o.astype(f32) ** 2), o

    fused = jax.jit(jax.value_and_grad(attn_loss, argnums=(0, 1, 2),
                                       has_aux=True))
    comp, n_mosaic, t_c = lower_compile("flash_attention", fused, q, k, v,
                                        rehearsal=rehearsal)
    ((_, o), grads), t_r = run_timed(comp, q, k, v)
    # a key-validity mask of ones is ineligible for the kernel: the same
    # entry point then runs its jnp path, here in float32 at full precision
    ones = jnp.ones((b, t), jnp.int32)
    with jax.default_matmul_precision("highest"):
        (_, o_ref), g_ref = jax.jit(jax.value_and_grad(
            lambda q, k, v: attn_loss(q, k, v, ones), argnums=(0, 1, 2),
            has_aux=True))(q.astype(f32), k.astype(f32), v.astype(f32))
    facts = {"fwd": within_tol("flash fwd", o, o_ref)}
    for nm, g, gr in zip(("dq", "dk", "dv"), grads, g_ref):
        facts[nm] = within_tol(f"flash {nm}", g, gr)
    report("flash_attention_fwd_bwd", n_mosaic, t_c, t_r, facts)
    del q, k, v, o, grads, o_ref, g_ref

    # decode attention: per-row positions on the stacked cache
    L, ns, s, d = sz.n_layers, sz.slots, sz.seq, sz.d_model
    ck = jax.random.normal(ks[3], (L, ns, s, d), jnp.bfloat16)
    cv = jax.random.normal(ks[4], (L, ns, s, d), jnp.bfloat16)
    window = 5                                  # K + 1 at spec_k = 4
    pos = jax.random.randint(ks[5], (ns,), 0, s - window, jnp.int32)
    layer = L // 2
    ck_ref, cv_ref = ck[layer].astype(f32), cv[layer].astype(f32)

    q1 = jax.random.normal(ks[6], (ns, h, dh), jnp.bfloat16)
    check(fd.decode_attention_available(q1, ck),
          "decode_attention_available is false at the smoke shape")
    dec = jax.jit(lambda q, ck, cv, pos: fd.decode_attention(
        q, ck, cv, pos, h, layer=layer))
    comp, n_mosaic, t_c = lower_compile("decode_attention", dec, q1, ck,
                                        cv, pos, rehearsal=rehearsal)
    a, t_r = run_timed(comp, q1, ck, cv, pos)
    with jax.default_matmul_precision("highest"):
        a_ref = fd.reference_decode_attention(q1.astype(f32), ck_ref,
                                              cv_ref, pos, h)
    report("decode_attention_vector_pos", n_mosaic, t_c, t_r,
           within_tol("decode_attention", a, a_ref))

    qw = jax.random.normal(ks[7], (ns, window, h, dh), jnp.bfloat16)
    check(fd.window_attention_available(qw, ck),
          "window_attention_available is false at the smoke shape")
    win = jax.jit(lambda q, ck, cv, pos: fd.decode_window_attention(
        q, ck, cv, pos, h, layer=layer))
    comp, n_mosaic, t_c = lower_compile("decode_window_attention", win, qw,
                                        ck, cv, pos, rehearsal=rehearsal)
    a, t_r = run_timed(comp, qw, ck, cv, pos)
    with jax.default_matmul_precision("highest"):
        a_ref = fd.reference_window_attention(qw.astype(f32), ck_ref,
                                              cv_ref, pos, h)
    report("decode_window_attention_k5", n_mosaic, t_c, t_r,
           within_tol("decode_window_attention", a, a_ref))
    del ck, cv, ck_ref, cv_ref, a, a_ref

    # fused LSTM through the layer's own entry point
    lb, lt, lf, lh = sz.lstm
    layer_ = LSTM(n_in=lf, n_out=lh, activation="tanh")
    lp = layer_.init_params(jax.random.PRNGKey(22))
    x = jax.random.normal(ks[8], (lb, lt, lf), f32)
    check(fused_lstm_available(x, lh, None, "sigmoid", "tanh"),
          "fused_lstm_available is false at the smoke shape")
    run = jax.jit(lambda p, x: layer_.scan_sequence(p, x)[0])
    comp, n_mosaic, t_c = lower_compile("fused_lstm", run, lp, x,
                                        rehearsal=rehearsal)
    ys, t_r = run_timed(comp, lp, x)
    # an all-ones step mask is ineligible for the kernel: the lax.scan path
    with jax.default_matmul_precision("highest"):
        ys_ref = jax.jit(lambda p, x: layer_.scan_sequence(
            p, x, mask=jnp.ones((lb, lt), f32))[0])(lp, x)
    report("fused_lstm", n_mosaic, t_c, t_r,
           within_tol("fused_lstm", ys, ys_ref))
    return out


# ---------------------------------------------------------------------------
# leg: train
# ---------------------------------------------------------------------------

def model_config(sz: Sizes, **over):
    from deeplearning4j_tpu.models.transformer import TransformerConfig
    return TransformerConfig(
        vocab_size=sz.vocab, d_model=sz.d_model, n_heads=sz.n_heads,
        n_layers=sz.n_layers, max_len=sz.seq, dtype="bfloat16",
        xent_chunk=sz.xent_chunk, **over)


def train_batch(sz: Sizes, batch: int):
    import jax
    import jax.numpy as jnp
    tokens = jax.random.randint(jax.random.PRNGKey(31), (batch, sz.seq), 0,
                                sz.vocab, dtype=jnp.int32)
    return tokens, jnp.roll(tokens, -1, axis=1)


def take_steps(step, params, opt, tokens, targets, n: int = 3):
    """Compile `step` ahead of time, take n steps on one fixed batch, and
    require finite losses with the last below the first."""
    import math
    t0 = time.perf_counter()
    compiled = step.lower(params, opt, tokens, targets).compile()
    t_c = time.perf_counter() - t0
    losses = []
    t0 = time.perf_counter()
    for _ in range(n):
        params, opt, loss = compiled(params, opt, tokens, targets)
        losses.append(float(loss))
    t_r = time.perf_counter() - t0
    check(all(math.isfinite(x) for x in losses), f"losses {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    return compiled, params, opt, losses, t_c, t_r


def leg_train(sz: Sizes, rehearsal: bool) -> dict:
    import jax

    from deeplearning4j_tpu.models.transformer import init_params
    from deeplearning4j_tpu.parallel.megatron import (
        init_adam_state, make_parallel_train_step, shard_params)
    from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh

    cfg = model_config(sz, remat=True)
    mesh = make_mesh(MeshSpec(), devices=jax.devices()[:1])
    params = shard_params(init_params(cfg, jax.random.PRNGKey(30)), cfg,
                          mesh)
    opt = init_adam_state(params)
    step = make_parallel_train_step(cfg, mesh, learning_rate=3e-4)
    tokens, targets = train_batch(sz, sz.train_batch)
    _, params, opt, losses, t_c, t_r = take_steps(step, params, opt, tokens,
                                                  targets)
    say(f"train: {sz.n_layers}L/{sz.d_model}d T={sz.seq} V={sz.vocab} "
        f"batch={sz.train_batch} remat losses={[round(x, 4) for x in losses]}"
        f" compile={t_c:.1f}s run(3 steps)={t_r:.2f}s")
    return {"losses": losses, "batch": sz.train_batch,
            "compile_s": round(t_c, 1), "run_s": round(t_r, 2)}


# ---------------------------------------------------------------------------
# leg: serve
# ---------------------------------------------------------------------------

def make_prompts(sz: Sizes):
    """Seeded prompts; the first two share a prefix of whole pages."""
    import numpy as np
    rng = np.random.default_rng(41)
    lo, hi = sz.prompt_lens
    shared = rng.integers(0, sz.vocab, sz.shared_prefix, dtype=np.int32)
    prompts = []
    for i in range(sz.wave):
        n = int(rng.integers(lo, hi + 1))
        if i < 2:
            n = max(n, sz.shared_prefix + lo)
        p = rng.integers(0, sz.vocab, n, dtype=np.int32)
        if i < 2:
            p[:sz.shared_prefix] = shared
        prompts.append(p)
    return prompts


def scrape(engine) -> dict:
    """The engine's Prometheus scrape, summed per metric name."""
    from deeplearning4j_tpu.observability.export import prometheus_text
    totals: dict = {}
    for line in prometheus_text(engine.registry).splitlines():
        if line.startswith("#") or not line.strip():
            continue
        name, value = line.rsplit(" ", 1)
        name = name.split("{", 1)[0]
        totals[name] = totals.get(name, 0.0) + float(value)
    return totals


def run_wave(engine, prompts, sz: Sizes, timeout_s: float):
    """Submit every prompt, wait for every handle, return the generated
    tokens. Each handle must finish without error with exactly
    ``new_tokens`` in-vocabulary tokens."""
    import numpy as np
    t0 = time.perf_counter()
    handles = [engine.submit(p) for p in prompts]
    deadline = time.monotonic() + timeout_s
    for hd in handles:      # raises the request's error, or TimeoutError
        hd.result(timeout=max(0.0, deadline - time.monotonic()))
    secs = time.perf_counter() - t0
    outs = []
    for hd in handles:
        check(hd.done() and hd.error is None,
              f"request {hd.rid} ended with {hd.error!r}")
        gen = np.asarray(hd.generated)
        check(gen.shape == (sz.new_tokens,),
              f"request {hd.rid} generated {gen.shape[0]} tokens")
        check(bool(np.all((gen >= 0) & (gen < sz.vocab))),
              f"request {hd.rid} produced out-of-vocabulary tokens")
        outs.append(gen)
    return outs, secs


@functools.lru_cache(maxsize=None)
def reference_logits_fn(cfg32):
    """Jitted float32 reference: logits at chosen positions. Cached so the
    waves, which share a shape, share one compile."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.transformer import forward_hidden

    def logits_at(params, tokens, where):
        h = forward_hidden(cfg32, params, tokens)
        h = jnp.take_along_axis(h, where[..., None], axis=1)
        return jnp.matmul(h, params["Wout"])

    return jax.jit(logits_at)


def reference_deficit(cfg, params, prompts, wave, sz: Sizes) -> dict:
    """Teacher-forced check against the repo's reference model: one float32
    `forward_hidden` over prompt + generated tokens (right-padded; the model
    is causal), then for every generated token how far its reference logit
    lies below the best one, in units of the logits' standard deviation.
    The padded length is odd, which the flash kernel cannot tile, so the
    reference runs the model's jnp attention."""
    import jax
    import numpy as np

    cfg32 = dataclasses.replace(cfg, dtype="float32", remat=False)
    tokens = np.zeros((len(prompts), sz.seq - 1), np.int32)
    where = np.zeros((len(prompts), sz.new_tokens), np.int32)
    for i, (p, gen) in enumerate(zip(prompts, wave)):
        full = np.concatenate([p, gen])
        tokens[i, :len(full)] = full
        where[i] = len(p) - 1 + np.arange(sz.new_tokens)
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(
            reference_logits_fn(cfg32)(params, tokens, where))
    chosen = np.take_along_axis(logits, np.stack(wave)[..., None],
                                axis=-1)[..., 0]
    deficit = (logits.max(axis=-1) - chosen) / float(logits.std())
    worst = float(deficit.max())
    check(worst <= REF_TOL,
          f"a generated token scores {worst:.3f} logit-sd below the "
          f"float32 reference's best (limit {REF_TOL})")
    return {"max_deficit_sd": round(worst, 4),
            "tokens_not_reference_argmax": int((deficit > 0).sum()),
            "tokens": int(deficit.size)}


def engine_config(sz: Sizes, **over):
    from deeplearning4j_tpu.serving import EngineConfig
    return EngineConfig(
        mode="continuous", paged=True, page_size=16, prefix_cache=True,
        prefill_chunk=sz.prefill_chunk, pipeline=True, num_slots=sz.slots,
        max_batch_size=sz.slots, max_new_tokens=sz.new_tokens,
        temperature=0.0, **over)


def healthy(totals: dict, what: str) -> None:
    check(totals.get("serving_requests_quarantined_total", 0.0) == 0.0,
          f"{what}: quarantined requests in the scrape")
    check(totals.get("serving_breaker_state", -1.0) == 0.0,
          f"{what}: breaker not closed")
    check(totals.get("serving_decode_step_failures_total", 0.0) == 0.0,
          f"{what}: failed decode steps in the scrape")


def leg_serve(sz: Sizes, rehearsal: bool, mesh=None) -> dict:
    import jax
    import numpy as np

    from deeplearning4j_tpu.models.transformer import init_params
    from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh
    from deeplearning4j_tpu.serving import InferenceEngine

    cfg = model_config(sz)
    if mesh is None:
        mesh = make_mesh(MeshSpec(), devices=jax.devices()[:1])
    params = init_params(cfg, jax.random.PRNGKey(40))
    prompts = make_prompts(sz)
    wave_timeout = 600.0
    out: dict = {}

    engine = InferenceEngine(cfg, mesh, params, engine_config(sz))
    try:
        t0 = time.perf_counter()
        warm = engine.warmup()
        t_warm = time.perf_counter() - t0
        check(warm["jit"] == warm["programs"],
              f"warmup compiled {warm['jit']} of {warm['programs']}")
        engine.start()
        before = scrape(engine)
        wave1, t1 = run_wave(engine, prompts, sz, wave_timeout)
        mid = scrape(engine)
        wave2, t2 = run_wave(engine, prompts, sz, wave_timeout)
        wave3, t3 = run_wave(engine, prompts, sz, wave_timeout)
        after = scrape(engine)
    finally:
        engine.stop(drain=False)
    healthy(after, "plain engine")
    hit = "serving_prefix_shared_tokens_total"
    check(after.get(hit, 0.0) > mid.get(hit, 0.0),
          "the repeated waves recorded no prefix-hit tokens")
    compiles = [s.get("serving_compiles_total", 0.0)
                for s in (before, mid, after)]
    check(compiles[0] == compiles[1] == compiles[2],
          f"compiles after warm-up: {compiles}")
    # wave 1 prefills every prompt from scratch; waves 2 and 3 resume each
    # from its cached pages. Each path must agree with the reference, and
    # the cached path must repeat itself exactly. Whether the two paths
    # pick the same token at every near-tie is recorded, not required.
    ref1 = reference_deficit(cfg, params, prompts, wave1, sz)
    ref2 = reference_deficit(cfg, params, prompts, wave2, sz)
    check(all(np.array_equal(a, b) for a, b in zip(wave2, wave3)),
          "wave 3 differs from wave 2: the prefix-hit path does not repeat")
    same = [bool(np.array_equal(a, b)) for a, b in zip(wave1, wave2)]
    out["plain"] = {
        "programs": warm["programs"], "warmup_s": round(t_warm, 1),
        "wave1_s": round(t1, 2), "wave2_s": round(t2, 2),
        "wave3_s": round(t3, 2),
        "prompt_tokens": int(sum(len(p) for p in prompts)),
        "prefix_hit_tokens_wave1": int(mid.get(hit, 0.0)),
        "prefix_hit_tokens_waves23": int(after.get(hit, 0.0)
                                         - mid.get(hit, 0.0)),
        "compiles_total": int(compiles[2]),
        "wave1_vs_reference": ref1, "wave2_vs_reference": ref2,
        "wave3_equals_wave2": True,
        "wave2_equals_wave1": same,
        "kv_pool_bytes": int(engine.kv_pool_bytes()),
        "param_bytes": int(engine.param_bytes())}
    say(f"serve: plain engine {out['plain']}")
    del engine
    gc.collect()

    # speculative pass: completes with acceptance; exactness is recorded
    spec_prompts = prompts[:sz.spec_requests]
    engine = InferenceEngine(
        cfg, mesh, params,
        engine_config(sz, spec_decode=True, draft="self", spec_k=4,
                      spec_adaptive=False))
    try:
        t0 = time.perf_counter()
        engine.warmup()
        t_warm = time.perf_counter() - t0
        engine.start()
        spec, t_s = run_wave(engine, spec_prompts, sz, wave_timeout)
        totals = scrape(engine)
    finally:
        engine.stop(drain=False)
    healthy(totals, "spec engine")
    drafted = totals.get("serving_spec_drafted_tokens_total", 0.0)
    accepted = totals.get("serving_spec_accepted_tokens_total", 0.0)
    check(drafted > 0 and accepted > 0,
          f"spec acceptance: {accepted} of {drafted} drafted")
    same = [bool(np.array_equal(a, b)) for a, b in zip(spec, wave1)]
    out["spec"] = {"warmup_s": round(t_warm, 1), "run_s": round(t_s, 2),
                   "drafted": int(drafted), "accepted": int(accepted),
                   "vs_reference": reference_deficit(
                       cfg, params, spec_prompts, spec, sz),
                   "equals_plain": same,
                   "tokens_equal_plain": all(same)}
    say(f"serve: spec engine (recorded, not gated: equals_plain) "
        f"{out['spec']}")
    return out


# ---------------------------------------------------------------------------
# four chips (builder-run option)
# ---------------------------------------------------------------------------

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


def bytes_in_use(devices, rehearsal: bool):
    """Per-device memory in use, comparable across devices (the CPU
    backend of the rehearsal reports none)."""
    stats = [d.memory_stats() for d in devices]
    if rehearsal and None in stats:
        return None
    in_use = [int(s["bytes_in_use"]) for s in stats]
    check(min(in_use) > 0 and max(in_use) <= 2 * min(in_use),
          f"per-device bytes in use not comparable: {in_use}")
    return in_use


def spread(name: str, tree, devices, compiled_text: str,
           rehearsal: bool) -> dict:
    """Check that a sharded run really used every device: the arrays'
    device sets, per-device memory in use, collectives in the HLO."""
    import jax
    sets = [frozenset(x.sharding.device_set)
            for x in jax.tree_util.tree_leaves(tree)]
    used = frozenset().union(*sets)
    check(used == frozenset(devices),
          f"{name}: arrays live on {len(used)} of {len(devices)} devices")
    found = sorted(c for c in COLLECTIVES if c in compiled_text)
    check(found, f"{name}: no collective in the compiled HLO")
    facts = {"bytes_in_use": bytes_in_use(devices, rehearsal),
             "collectives": found}
    say(f"four-chips: {name} {facts}")
    return facts


def leg_four_chips(sz: Sizes, rehearsal: bool) -> dict:
    import jax

    from __graft_entry__ import run_composite_steps
    from deeplearning4j_tpu.models.transformer import init_params
    from deeplearning4j_tpu.parallel.fsdp import (init_fsdp_adam_state,
                                                  make_fsdp_train_step,
                                                  shard_params_fsdp)
    from deeplearning4j_tpu.parallel.megatron import (
        init_adam_state, make_parallel_train_step, shard_params)
    from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh
    from deeplearning4j_tpu.serving.engine import resolved_executables

    devices = jax.devices()[:4]
    check(len(devices) == 4, f"need four devices, have {len(jax.devices())}")
    out: dict = {}
    cfg = model_config(sz, remat=True)
    tokens, targets = train_batch(sz, max(sz.train_batch, 4))

    mesh = make_mesh(MeshSpec(data=2, model=2), devices=devices)
    params = shard_params(init_params(cfg, jax.random.PRNGKey(30)), cfg,
                          mesh)
    opt = init_adam_state(params)
    step = make_parallel_train_step(cfg, mesh, learning_rate=3e-4)
    compiled, params, opt, losses, t_c, t_r = take_steps(
        step, params, opt, tokens, targets)
    out["train_data2_model2"] = dict(
        spread("train data=2 model=2", params, devices, compiled.as_text(),
               rehearsal),
        losses=losses, compile_s=round(t_c, 1), run_s=round(t_r, 2))
    del params, opt, compiled
    gc.collect()

    mesh = make_mesh(MeshSpec(data=4), devices=devices)
    params = shard_params_fsdp(init_params(cfg, jax.random.PRNGKey(30)),
                               mesh)
    opt = init_fsdp_adam_state(params)
    step = make_fsdp_train_step(cfg, mesh, learning_rate=3e-4)
    compiled, params, opt, losses, t_c, t_r = take_steps(
        step, params, opt, tokens, targets)
    out["fsdp_data4"] = dict(
        spread("fsdp data=4", params, devices, compiled.as_text(),
               rehearsal),
        losses=losses, compile_s=round(t_c, 1), run_s=round(t_r, 2))
    del params, opt, compiled
    gc.collect()

    mesh = make_mesh(MeshSpec(model=4), devices=devices)
    out["serve_model4"] = leg_serve(sz, rehearsal, mesh=mesh)
    texts = [exe.as_text() for exes in resolved_executables().values()
             for exe in exes]
    found = sorted(c for c in COLLECTIVES if any(c in t for t in texts))
    check(found, "serve model=4: no collective in the compiled programs")
    in_use = bytes_in_use(devices, rehearsal)
    out["serve_model4"]["collectives"] = found
    out["serve_model4"]["bytes_in_use_after"] = in_use
    say(f"four-chips: serve model=4 collectives={found} "
        f"bytes_in_use_after={in_use}")

    run_composite_steps(devices, say=say)
    out["composite_tiny"] = "ok"
    return out


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------

class CacheCounter:
    """Hits and misses of JAX's persistent compilation cache, counted from
    JAX's own monitoring events: what tells a warm start from a cold one."""
    EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
              "/jax/compilation_cache/cache_misses": "misses"}

    def __init__(self):
        import jax.monitoring
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_) -> None:
        field = self.EVENTS.get(event)
        if field:
            setattr(self, field, getattr(self, field) + 1)


def main(argv=None) -> int:
    global _PREFIX
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU, toy sizes, interpreted kernels; proves "
                         "nothing about the chip")
    ap.add_argument("--four-chips", action="store_true",
                    help="instead of the three one-chip legs, spread the "
                         "model over four chips (data=2 x model=2, FSDP "
                         "data=4, serving model=4)")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()

    if args.rehearsal:
        # explicit only: nothing selects this, and every line says so
        os.environ["JAX_PLATFORMS"] = "cpu"
        for name in KERNEL_SWITCHES:
            os.environ[name] = "interpret"
        _PREFIX = "[rehearsal cpu] "
    else:
        for name in KERNEL_SWITCHES:
            if os.environ.get(name) in FORBIDDEN_KERNEL_MODES:
                say(f"refusing to start: {name}={os.environ[name]} keeps "
                    "the kernels off the chip")
                return 2

    import jax
    import jaxlib

    if args.rehearsal and args.four_chips:
        jax.config.update("jax_num_cpu_devices", 4)
    try:
        devices = jax.devices()
    except RuntimeError as e:
        say(f"no accelerator: {e}")
        return 2
    dev = devices[0]
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = "not installed"
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    say(f"device: jax {jax.__version__} jaxlib {jaxlib.__version__} "
        f"libtpu {libtpu_version} platform={dev.platform} "
        f"device_kind={dev.device_kind!r} count={len(devices)}")
    sz = TOY if args.rehearsal else FULL
    if not args.rehearsal:
        if dev.platform != "tpu":
            say(f"no accelerator: JAX's first device is {dev.platform!r} "
                f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}); "
                "this script runs nothing on the CPU")
            return 2
        from deeplearning4j_tpu.util.flops import (chip_peak_bytes_per_s,
                                                   chip_peak_flops)
        peaks = (chip_peak_flops(dev), chip_peak_bytes_per_s(dev))
        if None in peaks:
            say(f"util/flops.py has no peak for device_kind "
                f"{dev.device_kind!r}: {peaks}")
            return 2
        say(f"device: peaks {peaks[0]:.3g} FLOP/s bf16, {peaks[1]:.3g} B/s")

    from deeplearning4j_tpu.util import compile_cache
    cache_dir = compile_cache.enable()
    cache = CacheCounter()
    say(f"compile cache: {cache_dir}")

    legs = ([("four_chips", leg_four_chips)] if args.four_chips else
            [("kernels", leg_kernels), ("train", leg_train),
             ("serve", leg_serve)])
    report, failed = {}, []
    for name, leg in legs:
        t0 = time.perf_counter()
        try:
            report[name] = leg(sz, args.rehearsal)
        except Exception:
            traceback.print_exc()
            failed.append(name)
            say(f"LEG FAILED: {name}")
        say(f"leg {name}: {time.perf_counter() - t0:.1f}s wall")
        gc.collect()

    wall = time.perf_counter() - t_start
    start = "warm" if cache.hits > cache.misses else "cold"
    say(f"compile cache: {cache.hits} hits, {cache.misses} misses "
        f"({start} start); wall {wall:.1f}s")
    summary = {"legs": report, "failed": failed, "device": device,
               "versions": {"jax": jax.__version__,
                            "jaxlib": jaxlib.__version__,
                            "libtpu": libtpu_version},
               "compile_cache": {"dir": cache_dir, "hits": cache.hits,
                                 "misses": cache.misses,
                                 "start": start},
               "wall_s": round(wall, 1), "rehearsal": args.rehearsal}
    say("summary: " + json.dumps(summary))
    if failed:
        say(f"FAILED legs: {failed}")
        return 1
    result = {"ok": True, "device": device}
    if args.rehearsal:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    sys.exit(main())
