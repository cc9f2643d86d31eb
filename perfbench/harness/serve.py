"""Drives a serving cell: `InferenceEngine.submit` on a started engine, the
client reading tokens as the engine commits them.

The stream of requests starts `ramp_s` before the window opens, so that
slots, queue and page pool are at their running level when timing starts.
Requests in flight when the window opens count for the rate from that
moment and for no latency; latencies are of requests due inside the window,
timed from when they were due. After the window closes nothing more is sent;
what is due is drained (a minute at most), then judged.
"""
from __future__ import annotations

import gc
import heapq
import queue
import shutil
import threading
import time
from pathlib import Path
from typing import Dict, List

import numpy as np

from perfbench.harness import stats, traffic as traffic_mod

now = time.perf_counter


class Client:
    """The client's side of the engine's listener protocol: after every
    scheduling round it looks at each request it has outstanding and stamps
    what is new with its own clock. It also samples the engine's gauges."""

    GAUGES = ("serving_slot_occupancy", "serving_kv_pages_used",
              "serving_kv_pages_free", "serving_queue_depth")

    def __init__(self):
        self.lock = threading.Lock()
        self.out: Dict[int, list] = {}      # rid -> [handle, Served, seen]
        self.done = queue.SimpleQueue()
        self.samples: List[tuple] = []

    def track(self, handle, served) -> None:
        with self.lock:
            self.out[handle.rid] = [handle, served, 0]

    def iteration_done(self, engine, index, latency) -> None:
        t = now()
        with self.lock:
            items = list(self.out.values())
        for item in items:
            handle, served, seen = item
            n = int(handle.generated.shape[0])
            if n > seen:
                served.commits.append((t, n - seen, seen))
                item[2] = n
            if handle.done():
                served.finished = t
                if handle.error is not None:
                    served.error = repr(handle.error)
                with self.lock:
                    self.out.pop(handle.rid, None)
                self.done.put((served, handle))
        reg = engine.registry
        self.samples.append(
            (t,) + tuple(float(reg.get(g).value) for g in self.GAUGES))


def _engine_config(traffic: dict, rehearse: bool):
    from deeplearning4j_tpu.serving import EngineConfig
    kw = dict(traffic["engine"])
    if rehearse:
        kw.update(num_slots=4, max_batch_size=4, kv_pages=96,
                  prefill_chunk=32)
    return EngineConfig(**kw)


def shrink_traffic(t: dict) -> dict:
    """The rehearsal's traffic: an eighth of every length."""
    t = dict(t)
    for k in ("prompt_len", "output_len", "user_len", "answer_len"):
        if k in t:
            t[k] = {kk: (max(2, v // 8) if kk in ("median", "min", "max")
                         else v) for kk, v in t[k].items()}
    for k in ("system_len", "context_cap"):
        if k in t:
            t[k] = t[k] // 8
    t["check"] = dict(t["check"], pad_to=256, min_tokens=20)
    return t


class Tracer(threading.Thread):
    """Traces [start_at, start_at + seconds] of the host's clock from a
    thread of its own, so that neither the engine's loop nor the sender
    waits for the profiler. The host annotation `perfbench_window` marks the
    traced window on the trace's own clock."""

    def __init__(self, directory: Path, start_at: float, seconds: float):
        super().__init__(name="perfbench-tracer", daemon=True)
        self.directory, self.start_at, self.seconds = (directory, start_at,
                                                       seconds)
        self.t_a = self.t_b = None
        self.error = None

    def run(self) -> None:
        import jax
        try:
            time.sleep(max(0.0, self.start_at - 1.5 - now()))
            jax.profiler.start_trace(str(self.directory))
            try:
                time.sleep(max(0.0, self.start_at - now()))
                with jax.profiler.TraceAnnotation("perfbench_window"):
                    self.t_a = now()
                    time.sleep(self.seconds)
                    self.t_b = now()
            finally:
                jax.profiler.stop_trace()
        except Exception as e:       # reported by the caller, which fails
            self.error = e


def build(cell, s, tr: dict, seed: int, rehearse: bool, split: dict):
    """The engine, warmed, its weights made on the device from the seed in
    the serving layout."""
    import jax

    from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh
    from deeplearning4j_tpu.parallel.serving import serving_param_specs
    from deeplearning4j_tpu.serving import InferenceEngine
    from jax.sharding import NamedSharding

    ref = cell.reference()
    t = now()
    cfg = cell.program_config(s)
    mesh = make_mesh(MeshSpec(), devices=jax.devices()[:cell.chips])
    shardings = jax.tree_util.tree_map(
        lambda sp: NamedSharding(mesh, sp), serving_param_specs(cfg),
        is_leaf=lambda x: not isinstance(x, dict))
    params = jax.block_until_ready(
        ref.make_init(s, shardings)(ref.seed_key(seed)))
    split["weights"] = now() - t

    t = now()
    engine = InferenceEngine(cfg, mesh, params, _engine_config(tr, rehearse))
    del params
    engine.warmup(buckets=[])
    split["trace_lower_compile_or_load"] = now() - t
    return engine, mesh


def drive(engine, gen, tr: dict, seconds: float, ramp_s: float,
          on_open=None, tracer_dir=None) -> dict:
    """Offers the generator's stream to a started engine: `ramp_s` of it
    before the window, `seconds` of window, then the drain. Returns the
    client's log and what was sampled on the way."""
    client = Client()
    engine.set_listeners(client)
    stream0 = now()
    t0 = stream0 + ramp_s
    t1 = t0 + seconds
    tracer = None
    if tracer_dir is not None:
        shutil.rmtree(tracer_dir, ignore_errors=True)
        tracer = Tracer(tracer_dir, t0, min(float(tr["trace_s"]), seconds))
        tracer.start()

    log: List[stats.Served] = []
    reqs: Dict[int, traffic_mod.Request] = {}
    handles: Dict[int, object] = {}
    heap: list = []

    def schedule(rs) -> None:
        for r in rs:
            heapq.heappush(heap, (stream0 + r.due_s, r.seq, r))

    schedule(gen.initial(ramp_s + seconds))
    opened = False
    while True:
        tnow = now()
        if not opened and tnow >= t0:
            opened = True
            if on_open is not None:
                on_open(tnow, stream0)
        if tnow >= t1:
            break
        while True:
            try:
                served, handle = client.done.get_nowait()
            except queue.Empty:
                break
            schedule(gen.on_finish(reqs[served.seq], served.finished
                                   - stream0, handle.generated))
        if heap and heap[0][0] <= tnow:
            due, _, r = heapq.heappop(heap)
            sv = stats.Served(seq=r.seq, due=due, prompt_len=len(r.prompt),
                              max_new=r.max_new)
            log.append(sv)
            reqs[r.seq] = r
            try:
                h = engine.submit(r.prompt, max_new_tokens=r.max_new)
            except Exception as e:           # a refused request has failed
                sv.error = repr(e)
                continue
            sv.sent = now()
            handles[r.seq] = h
            client.track(h, sv)
            continue
        wake = min(t1, heap[0][0] if heap else t1)
        if not opened:
            wake = min(wake, t0)
        try:
            item = client.done.get(timeout=max(0.0, wake - now()))
            client.done.put(item)
        except queue.Empty:
            pass
    closed = now()

    # drain: nothing more is sent. An open loop waits for what was due; a
    # closed loop's callers stop, and what they have outstanding is
    # withdrawn (stats.judged says why).
    count = tr.get("count", "due")
    if count == "finished":
        for sv in log:
            if sv.finished is None and sv.error is None and \
                    engine.cancel(handles[sv.seq]):
                sv.withdrawn = True
    deadline = t1 + float(tr["drain_s"])
    while now() < deadline:
        if not any(sv.finished is None and sv.error is None
                   for sv in stats.judged(log, t0, t1, count)):
            break
        time.sleep(0.02)
    if tracer is not None:
        tracer.join(timeout=120)
        if tracer.error is not None or tracer.t_b is None:
            raise SystemExit(f"perfbench: tracing failed: {tracer.error!r}")

    queue_wait = []
    for sv in stats.judged(log, t0, t1, count):
        h = handles.get(sv.seq)
        if h is None:
            continue
        q, a = h.trace.first_ts("queued"), h.trace.first_ts("admitted")
        if q is not None and a is not None:
            queue_wait.append((a - q) * 1e3)
    engine.set_listeners()
    return {"log": log, "t0": t0, "t1": t1, "closed": closed,
            "samples": client.samples, "queue_wait_ms": queue_wait,
            "tracer": tracer, "reqs": reqs,
            "answers": {seq: np.asarray(h.generated, np.int32)
                        for seq, h in handles.items()}}


def pick_sample(log, t0, t1, reqs, answers, k: int, seed: int,
                count: str = "due"):
    """What the check compares: a sample, drawn from the seed, of the
    requests the window finished, the longest in it."""
    done_ok = [sv for sv in stats.judged(log, t0, t1, count)
               if not stats.failed(sv)]
    if not done_ok:
        return []
    rng = np.random.default_rng([int(seed), 0xC4EC])
    k = min(k, len(done_ok))
    longest = max(done_ok, key=lambda sv: sv.prompt_len + sv.n_out)
    rest = [sv for sv in done_ok if sv is not longest]
    picks = [longest] + [rest[i] for i in
                         rng.choice(len(rest), k - 1, replace=False)]
    return [(reqs[sv.seq].prompt, answers[sv.seq]) for sv in picks]


def run(cell, args, ctx) -> dict:
    """One run of a serving cell. `ctx` carries the process's start time
    and the compile counter; returns the raw material that run.py turns
    into the result line."""
    ref = cell.reference()
    s = ctx["sizes"]
    tr = shrink_traffic(cell.traffic) if args.rehearse else cell.traffic
    split = ctx["split"]
    t = now()
    import deeplearning4j_tpu.serving  # noqa: F401  (timed: PERF.md says why)
    split["import_program"] = now() - t
    engine, mesh = build(cell, s, tr, args.seed, args.rehearse, split)
    engine.start()
    gen = traffic_mod.SERVING_KINDS[tr["kind"]](tr, s.vocab_size, args.seed)
    ramp_s = float(tr["ramp_s"]) * (0.25 if args.rehearse else 1.0)

    def on_open(tnow, stream0):
        ctx["compiles"].mark("open")
        split["ramp"] = tnow - stream0
        ctx["setup_s"] = tnow - ctx["t_start"]

    d = drive(engine, gen, tr, args.seconds, ramp_s, on_open,
              ctx["out_dir"] / f"trace-{cell.name}" if args.trace else None)
    ctx["compiles"].marks["close"] = d["closed"]
    engine.stop(drain=False)
    t0, t1, log = d["t0"], d["t1"], d["log"]
    count = tr.get("count", "due")
    sample = pick_sample(log, t0, t1, d["reqs"], d["answers"],
                         int(tr["check"]["sample"]), args.seed, count)
    peak = max(int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for dev in mesh.devices.flat)
    del engine
    gc.collect()

    t = now()
    compared = check_served(ref, s, sample, tr["check"], args.seed)
    ctx["reference_s"] = now() - t

    summary = stats.serving_summary(log, t0, t1, count)
    return {"kind": "serving", "log": log, "t0": t0, "t1": t1,
            "summary": summary, "engine_settings": tr["engine"],
            "series": {"ttft_ms": summary["ttft_ms"],
                       "tpot_ms": summary["tpot_ms"],
                       "lateness_ms": summary["lateness_ms"],
                       "queue_wait_ms": d["queue_wait_ms"]},
            "samples": d["samples"], "tracer": d["tracer"],
            "memory_peak_bytes": peak, "compared": compared, "sizes": s,
            "traffic": tr}


def check_served(ref, s, sample, check: dict, seed: int,
                 precision: str = "f32") -> dict:
    """The plain reference once over each sampled prompt with its served
    tokens: the widest gap by which a served token's logit lies below the
    reference's best at its position, in units of the logits' standard
    deviation. With `precision` set lower the same code reads the control:
    the gap of the token that the lower precision puts first."""
    import jax
    import jax.numpy as jnp

    limit = float(check["limits"]["worst_gap_sd"])
    if not sample:
        return {"worst_gap_sd": {"value": float("inf"), "limit": limit},
                "tokens_compared": {"value": 0, "limit": 1}}
    pad_to = int(check["pad_to"])
    max_out = max(len(g) for _, g in sample)
    tokens = np.zeros((len(sample), pad_to), np.int32)
    where = np.zeros((len(sample), max_out), np.int32)
    served = np.zeros((len(sample), max_out), np.int32)
    valid = np.zeros((len(sample), max_out), bool)
    for i, (p, g) in enumerate(sample):
        full = np.concatenate([p, g])
        tokens[i, :len(full)] = full
        where[i, :len(g)] = len(p) - 1 + np.arange(len(g))
        served[i, :len(g)] = g
        valid[i, :len(g)] = True

    def gaps(params, tokens, where, served, valid):
        with jax.default_matmul_precision("highest"):
            best = ref.logits_at(s, params, tokens, where, "f32")
            if precision != "f32":
                low = ref.logits_at(s, params, tokens, where, precision)
                served = jnp.argmax(low, axis=-1)
        sd = jnp.std(best)
        chosen = jnp.take_along_axis(best, served[..., None], axis=-1)[..., 0]
        gap = (jnp.max(best, axis=-1) - chosen) / sd
        gap = jnp.where(valid, gap, 0.0)
        return jnp.max(gap), jnp.sum((gap > 0) & valid)

    params = ref.make_init(s)(ref.seed_key(seed))
    worst, off = jax.jit(gaps)(params, tokens, where, served, valid)
    n = int(valid.sum())
    return {"worst_gap_sd": {"value": float(worst), "limit": limit},
            "tokens_compared": {"value": n,
                                "limit": int(check["min_tokens"])},
            "tokens_not_reference_best": {"value": int(off), "limit": n}}


def is_correct(compared: dict) -> bool:
    return (compared["worst_gap_sd"]["value"]
            <= compared["worst_gap_sd"]["limit"]
            and compared["tokens_compared"]["value"]
            >= compared["tokens_compared"]["limit"])
