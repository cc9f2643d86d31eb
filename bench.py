"""Benchmark: LeNet-MNIST training throughput (examples/sec/chip).

The reference's canonical config (BASELINE.md: MultiLayerNetwork LeNet on
MNIST via fit(DataSetIterator), MultiLayerNetwork.java:947). The reference
publishes no in-tree numbers (BASELINE.json "published": {}), so
vs_baseline is reported against a fixed reference-CPU-backend estimate of
~2,500 examples/sec for this config (DL4J 0.8 nd4j-native class hardware);
the real comparison artifact is the absolute examples/sec/chip trend
across rounds.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Batch 4096: TPU-right sizing — the MXU wants large batched matmuls. One
MNIST epoch (15 x 4096 = 61,440 examples) is staged in HBM once and the
measured program runs EPOCHS passes over it via the nested-scan path
(fit_batched(..., epochs=N)): ~960 optimizer steps in one XLA program,
so per-dispatch host latency amortizes against ~2 ms/step of compute
the way it does in a real multi-epoch run. (The CPU
reference estimate is per-example throughput, which for the reference's
eager per-op dispatch is roughly batch-size-independent.)
"""
from __future__ import annotations

import json
import math
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

_T0 = time.monotonic()           # budget clock for the whole sitting


def _enable_compile_cache() -> None:
    """JAX's persistent compilation cache, where
    deeplearning4j_tpu/util/compile_cache.py puts it. BENCH_CACHE=0
    disables (e.g. to measure cold-compile latency)."""
    if os.environ.get("BENCH_CACHE", "1").lower() in ("0", "false",
                                                      "off", ""):
        return
    from deeplearning4j_tpu.util import compile_cache
    compile_cache.enable()


REFERENCE_CPU_EXAMPLES_PER_SEC = 2500.0
BATCH = 4096
POOL_STEPS = 15          # one staged MNIST epoch: 15 x 4096 = 61,440
EPOCHS = 64              # in-program passes over the pool
REPS = 2                 # best-of reps (r5: 4 -> 2, budget headroom)


def main() -> None:
    from deeplearning4j_tpu.models.zoo import lenet_mnist
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    # bfloat16 activations: MXU-native on TPU
    conf = lenet_mnist(dtype="bfloat16")
    net = MultiLayerNetwork(conf).init()

    # Distinct minibatches staged in HBM once; the measured region is ONE
    # compiled program spanning EPOCHS passes over the pool (nested
    # lax.scan — per-step loop on device, no host dispatch between steps
    # or between passes; SURVEY §3.1's TPU design consequence applied to
    # the whole training run).
    rng = np.random.default_rng(0)
    xs = jnp.asarray(rng.random((POOL_STEPS, BATCH, 784),
                                dtype=np.float32))
    ys = jax.nn.one_hot(
        jnp.asarray(rng.integers(0, 10, (POOL_STEPS, BATCH))), 10)

    # warmup = compile + one full run at the measured shape
    scores = net.fit_batched(xs, ys, epochs=EPOCHS)
    jax.block_until_ready(scores)

    # Best of REPS. The timed region ends with a forced HOST READ of
    # the last per-step score: a device->host transfer cannot return
    # before the program has finished.
    dt = math.inf
    last_score = float("nan")
    for _ in range(REPS):
        t0 = time.perf_counter()
        scores = net.fit_batched(xs, ys, epochs=EPOCHS)
        last_score = float(np.asarray(scores[-1]))
        dt = min(dt, time.perf_counter() - t0)
    if last_score != last_score:
        raise RuntimeError("NaN training score in bench run")

    # MFU from XLA's own cost model — un-gameable, needs no reference
    # estimate (util/flops.py). XLA counts a lax.scan body ONCE
    # regardless of trip count (verified: 1-step and 15-step pools cost
    # the same), so cost a single-step program and scale by the step
    # count explicitly. None on backends with no cost model / unknown
    # peak (e.g. CPU smoke runs).
    from deeplearning4j_tpu.util.flops import mfu
    cost = net.fit_batched_cost(xs[:1], ys[:1], epochs=1)
    step_flops = cost.get("flops")
    # Guard the scan-body-counted-once assumption: if a future XLA cost
    # model starts scaling flops with trip count, scaling by
    # POOL_STEPS*EPOCHS would inflate MFU ~960x. A 2-step pool must cost
    # (approximately) the same as a 1-step pool, else degrade to None
    # (advisor round-2 finding).
    if step_flops and step_flops > 0:
        two = net.fit_batched_cost(xs[:2], ys[:2], epochs=1).get("flops")
        if not two or not (0.5 < two / step_flops < 1.5):
            step_flops = None
    flops = (float(step_flops) * POOL_STEPS * EPOCHS
             if step_flops and step_flops > 0 else None)
    mfu_val = mfu(flops, dt)

    examples_per_sec = BATCH * POOL_STEPS * EPOCHS / dt
    print(json.dumps({
        "metric": "lenet_mnist_train_throughput",
        "value": round(examples_per_sec, 1),
        "unit": "examples/sec/chip",
        # MFU is the honest primary efficiency metric; vs_baseline is a
        # ratio against a fixed reference-CPU ESTIMATE (no published
        # reference numbers exist) — treat it as a footnote.
        "vs_baseline": round(examples_per_sec
                             / REFERENCE_CPU_EXAMPLES_PER_SEC, 3),
        "batch": BATCH,
        "program_tflops": (round(flops / 1e12, 3)
                           if flops is not None else None),
        "mfu": round(mfu_val, 4) if mfu_val is not None else None,
    }), flush=True)


def flagship_lines(which: str) -> int:
    """Append flagship-config JSON lines after the LeNet line so the
    driver-captured BENCH_r{N}.json records them round-over-round
    (VERDICT r2 weak #8). BENCH_FLAGSHIP=0 disables; the default runs
    ALL north-star configs (VERDICT r4 #9): the transformer family —
    d512, the d1024 MFU-ceiling proof point, the V=32768 real-vocab
    row, both KV-cache decode regimes — plus vgg16 and lstm;
    =transformer runs only the transformer family.

    Budget guard (VERDICT r4 #1): BENCH_BUDGET_SEC (default 280)
    bounds the sitting. Configs are NEVER skipped — when the elapsed
    clock passes 60% of the budget, remaining configs degrade to
    reps=1 (same warmup, one timed rep instead of two; the compile
    cache makes the timing itself cheap, so degradation costs only
    best-of-N noise robustness). Lines print eagerly so even a
    timeout captures every completed config. Returns the number of
    configs that printed an ``error`` line."""
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "benchmarks"))
    import flagship
    try:
        budget = float(os.environ.get("BENCH_BUDGET_SEC", "") or 280)
    except ValueError:
        budget = 280.0           # malformed knob must not kill the run
    # six VERDICT-required lines first, the rest after — a timeout
    # truncates the least-critical tail, not the flagship record.
    # word2vec (VERDICT r5 weak #2: first driver-captured w2v row),
    # engine_decode (ISSUE-1: serving-engine overhead vs bare pgen)
    # and engine_decode_metrics (ISSUE-2: observability overhead vs a
    # NULL_REGISTRY engine) ride at the end for the same reason.
    names = ["transformer", "transformer_1024", "transformer_32kvocab",
             "decode", "decode_long"]
    if which != "transformer":
        names += ["vgg16", "lstm", "word2vec", "engine_decode",
                  "engine_decode_metrics", "engine_continuous",
                  "engine_slo", "ckpt_async", "quant_decode",
                  "kv_paged", "spec_decode", "fleet_failover",
                  "chunked_prefill", "disagg", "fleet_obs",
                  "cold_start", "profiling_overhead", "qos_storm",
                  "elastic_train", "constrained_decode"]
    errors = 0
    for n in names:
        elapsed = time.monotonic() - _T0
        reps = 1 if elapsed > 0.6 * budget else 2
        try:
            print(json.dumps(flagship.BENCHES[n](reps=reps)),
                  flush=True)
        except Exception as e:
            errors += 1
            print(json.dumps({"config": n, "error":
                              f"{type(e).__name__}: {e}"[:200]}),
                  flush=True)
    return errors


# ---------------------------------------------------------------------------
# MFU regression gate (ISSUE-18 satellite)
# ---------------------------------------------------------------------------

#: gated line-config name -> flagship BENCHES key (to re-measure when
#: `--check` / `--update-gate` run without a captured-lines file)
GATE_BENCHES = {"transformer_lm_12L512d_T2048": "transformer",
                "elastic_train": "elastic_train",
                "spec_pipeline_4L192d_Ns8_K7": "spec_pipeline",
                "constrained_decode_4L192d_Ns8": "constrained_decode"}

GATE_TOLERANCE = 0.2


def check_gate(lines, baseline, tolerance: float = GATE_TOLERANCE):
    """Compare achieved throughput against BASELINE.json's
    ``flops_gate`` floor: a gated config whose metric drops more than
    ``tolerance`` below its recorded baseline is a failure. A gate
    entry is either a bare number (legacy: gates ``flops_per_sec``) or
    ``{"metric": <line key>, "value": <floor>}`` — the ISSUE-19 spec
    throughput gate uses the dict form with
    ``tokens_per_sec_pipelined_spec``. ``lines`` is the bench output
    (list of per-config dicts); ``baseline`` is the parsed
    BASELINE.json. Returns the list of failure strings — empty means
    the gate passes. Pure function so the gate itself is unit-testable
    without running a single bench."""
    gate = (baseline or {}).get("flops_gate") or {}
    by_config = {ln.get("config"): ln for ln in lines
                 if isinstance(ln, dict) and ln.get("config")}
    failures = []
    for name in sorted(gate):
        want = gate[name]
        metric = "flops_per_sec"
        if isinstance(want, dict):
            metric = want.get("metric", metric)
            want = want.get("value")
        if not want:
            continue                 # null floor: recorded but not gated
        ln = by_config.get(name)
        if ln is None:
            failures.append(f"{name}: gated config missing from the "
                            "bench lines")
            continue
        if "error" in ln:
            failures.append(f"{name}: bench errored: {ln['error']}")
            continue
        got = ln.get(metric)
        if not got:
            failures.append(f"{name}: bench line carries no "
                            f"{metric}")
            continue
        floor = float(want) * (1.0 - float(tolerance))
        if float(got) < floor:
            failures.append(
                f"{name}: {metric} {float(got):.3e} is below the "
                f"gate floor {floor:.3e} (baseline {float(want):.3e}, "
                f"tolerance {tolerance:.0%})")
    return failures


def _baseline_path() -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BASELINE.json")


def _gate_lines(path):
    """Bench lines for the gate: parsed from a captured file when
    given, else measured fresh (gated configs only)."""
    if path is not None:
        lines = []
        with open(path) as f:
            for raw in f:
                raw = raw.strip()
                if not raw:
                    continue
                try:
                    lines.append(json.loads(raw))
                except ValueError:
                    continue         # driver logs interleave non-JSON
        return lines
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "benchmarks"))
    import flagship
    lines = []
    for bench_key in sorted(set(GATE_BENCHES.values())):
        try:
            lines.append(flagship.BENCHES[bench_key](reps=1))
        except Exception as e:
            lines.append({"config": bench_key, "error":
                          f"{type(e).__name__}: {e}"[:200]})
    return lines


def gate_main(argv) -> int:
    """``--check [FILE]`` fails (rc 1) when any gated flagship arm's
    FLOP/s dropped >20% vs BASELINE.json's ``flops_gate``;
    ``--update-gate [FILE]`` records the measured values as the new
    floor."""
    mode = argv[0]
    path = argv[1] if len(argv) > 1 else None
    with open(_baseline_path()) as f:
        baseline = json.load(f)
    lines = _gate_lines(path)
    if mode == "--update-gate":
        gate = dict(baseline.get("flops_gate") or {})
        for ln in lines:
            name = ln.get("config") if isinstance(ln, dict) else None
            if name not in GATE_BENCHES:
                continue
            cur = gate.get(name)
            if isinstance(cur, dict):    # metric-keyed entry: keep the
                metric = cur.get("metric", "flops_per_sec")
                if ln.get(metric):       # metric, refresh the floor
                    gate[name] = {**cur, "value": ln[metric]}
            elif ln.get("flops_per_sec"):
                gate[name] = ln["flops_per_sec"]
        baseline["flops_gate"] = gate
        with open(_baseline_path(), "w") as f:
            json.dump(baseline, f, indent=2)
            f.write("\n")
        print(json.dumps({"gate": "updated", "flops_gate": gate}),
              flush=True)
        return 0
    failures = check_gate(lines, baseline)
    print(json.dumps({"gate": "fail" if failures else "pass",
                      "tolerance": GATE_TOLERANCE,
                      "failures": failures}), flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    import sys
    _argv = sys.argv[1:]
    if _argv and _argv[0] in ("--check", "--update-gate"):
        _enable_compile_cache()
        sys.exit(gate_main(_argv))
    _enable_compile_cache()
    main()
    _fl = os.environ.get("BENCH_FLAGSHIP", "1").lower()
    if _fl not in ("0", "false", "off", ""):
        if flagship_lines("transformer" if _fl == "transformer"
                          else "all"):
            sys.exit(1)
