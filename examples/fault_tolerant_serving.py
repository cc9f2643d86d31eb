"""Fault-tolerant serving of the sharded LM — the engine demo.

Runs the full ISSUE-1 story on the CPU backend with deterministic
fault injection: an `InferenceEngine` over a (data x model) mesh
survives a transient mid-decode failure (retry → byte-identical),
quarantines a poisoned request without hurting its batch peers, sheds
a deadline-blown request while the batch completes, trips + recovers
its circuit breaker, and hot-reloads weights from a checkpoint
directory — printing health() along the way.

ISSUE-2 addendum: everything publishes into ONE observability
registry (engine counters/histograms, a PerformanceListener's
training series, an AsyncDataSetIterator's prefetch gauges), a
`MetricsServer` exports it, and the demo ends by fetching and
printing a real curl-able `/metrics` sample.

ISSUE-6 addendum: the same exporter now also serves `/debugz`, `/slo`
and `/timeline.json` — the demo prints the quarantined request's
flight-recorder trace (retry -> preempted -> quarantined, the
per-request "why"), the windowed TTFT/TPOT/goodput SLO report, and
where to load the Perfetto slot timeline.

On a TPU slice this uses all chips; elsewhere:
  JAX_PLATFORMS=cpu python examples/fault_tolerant_serving.py
"""
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


def main() -> None:
    import jax

    from deeplearning4j_tpu import observability as obs
    from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                       init_params)
    from deeplearning4j_tpu.parallel.failure import ServingFaultInjector
    from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh
    from deeplearning4j_tpu.serving import (DeadlineExceeded,
                                            EngineConfig,
                                            InferenceEngine,
                                            OverloadError,
                                            RequestQuarantined)
    from deeplearning4j_tpu.train.listeners import PerformanceListener
    from deeplearning4j_tpu.util.checkpointing import CheckpointManager

    cfg = TransformerConfig(vocab_size=64, d_model=64, n_heads=4,
                            n_layers=2, max_len=128)
    params = init_params(cfg, jax.random.PRNGKey(0))
    if len(jax.devices()) >= 4:
        mesh = make_mesh(MeshSpec(data=2, model=2))
    else:                     # one chip, or a plain CPU host
        mesh = make_mesh(MeshSpec(data=1, model=1))
    prompt = np.arange(16, dtype=np.int32)

    # one shared registry: engine + training listener + prefetch all
    # publish into it, and the exporter serves it
    registry = obs.default_registry()
    inj = ServingFaultInjector(fail_at=[1])      # one transient fault
    eng = InferenceEngine(
        cfg, mesh, params,
        EngineConfig(decode_chunk=4, max_new_tokens=16,
                     backoff_base_s=0.001, breaker_failure_threshold=3,
                     breaker_cooldown_s=0.2),
        fault_injector=inj, registry=registry)
    eng.set_listeners(PerformanceListener(frequency=1, report=False,
                                          registry=registry))
    exporter = obs.MetricsServer(registry, port=0, health=eng.health,
                                 ready=eng.ready, debug=eng.debugz,
                                 slo=eng.slo_report,
                                 timeline=eng.timeline)
    print(f"[metrics] exporter at {exporter.url}/metrics "
          "(healthz/readyz/debugz/slo/timeline.json wired to the "
          "engine)")

    # 1. transient fault: retried, completes
    h = eng.submit(prompt)
    eng.run_pending()
    print(f"[transient] completed after {eng.stats['retries']} retry; "
          f"tokens={h.result().shape[0]}")

    # 2. poisoned request quarantined; co-batched peer completes
    bad = eng.submit(prompt)
    good = eng.submit(prompt)
    inj.poison_requests.add(bad.rid)
    eng.run_pending()
    try:
        bad.result()
    except RequestQuarantined as e:
        print(f"[quarantine] {e}")
    print(f"[quarantine] peer status={good.status}")
    # the flight recorder kept the per-request forensics: the
    # quarantined request's own lifecycle, ready for /debugz
    print(f"[trace] bad request lifecycle: {bad.trace.kinds()}")
    print(f"[trace] peer lifecycle:        {good.trace.kinds()}")

    # 3. deadline shed mid-decode (injected host stall)
    inj.delay_at[eng._step_counter + 1] = 0.1
    doomed = eng.submit(prompt, deadline_s=0.05)
    peer = eng.submit(prompt)
    eng.run_pending()
    try:
        doomed.result()
    except DeadlineExceeded as e:
        print(f"[deadline] {e}")
    print(f"[deadline] peer decoded {peer.result().shape[0] - 16} "
          "tokens")

    # 4. load shedding + breaker
    try:
        for _ in range(200):
            eng.submit(prompt)
    except OverloadError as e:
        print(f"[overload] {e}")
    eng.run_pending()
    print(f"[health] {eng.health()}")

    # 5. hot weight reload from a checkpoint directory
    ckpt = tempfile.mkdtemp(prefix="serving_ckpt_")
    mgr = CheckpointManager(ckpt, use_orbax=False)
    mgr.save_tree(params, step=7)
    step = eng.reload_weights(mgr)
    print(f"[reload] weights hot-reloaded from step {step}; "
          f"ready={eng.ready()}")

    # 6. input pipeline: a few batches through AsyncDataSetIterator
    # publish prefetch_* series into the SAME registry the engine and
    # listener already feed
    from deeplearning4j_tpu.datasets.iterators import (
        AsyncDataSetIterator, DataSet, ExistingDataSetIterator)
    batches = [DataSet(np.zeros((4, 8), np.float32),
                       np.zeros((4, 2), np.float32)) for _ in range(6)]
    n = sum(1 for _ in AsyncDataSetIterator(
        ExistingDataSetIterator(batches), queue_size=2,
        registry=registry))
    print(f"[prefetch] {n} batches through the async prefetcher")

    # 7. scrape the exporter exactly like `curl <url>/metrics` would:
    # one end-to-end run produced serving, training, AND prefetch
    # series on one endpoint
    from urllib.request import urlopen
    text = urlopen(f"{exporter.url}/metrics", timeout=5).read().decode()
    lines = text.splitlines()
    keep = ("serving_requests", "serving_decode_step_seconds_count",
            "serving_batch_size_count", "training_", "prefetch_")
    sample = [l for l in lines
              if not l.startswith("#") and l.startswith(keep)]
    print(f"[metrics] GET /metrics -> {len(lines)} lines; sample:")
    for line in sample:
        print(f"  {line}")

    # 8. the serving introspection endpoints (ISSUE-6): the windowed
    # SLO report and the Perfetto-loadable slot timeline
    import json
    rep = json.loads(urlopen(f"{exporter.url}/slo",
                             timeout=5).read().decode())
    print(f"[slo] window={rep['window']} goodput={rep['goodput']:.2f} "
          f"ttft_p50={rep['ttft_p50_ms']}ms "
          f"ttft_p99={rep['ttft_p99_ms']}ms "
          f"tpot_p99={rep['tpot_p99_ms']}ms")
    tl = json.loads(urlopen(f"{exporter.url}/timeline.json",
                            timeout=5).read().decode())
    print(f"[timeline] GET /timeline.json -> "
          f"{len(tl['traceEvents'])} trace events (load in "
          "https://ui.perfetto.dev: one lane per slot + queue lane)")
    dbg = json.loads(urlopen(f"{exporter.url}/debugz",
                             timeout=5).read().decode())
    print(f"[debugz] breaker={dbg['breaker']} "
          f"queue_depth={dbg['queue_depth']} "
          f"recent_events={dbg['recorder_events']}")
    exporter.stop()


if __name__ == "__main__":
    main()
