"""Continuous profiling & cost attribution for the serving engine.

The serving stack can say how fast it went (SLO layer, round 11) and
where the time went across the fleet (distributed traces, round 18) —
but not how fast it COULD have gone, nor who spent the FLOPs. This
module (ISSUE-15) is that accounting layer, three instruments in one:

- **Per-program device accounting** (`EngineProfiler`). Every compiled
  serving program's XLA cost analysis (FLOPs + bytes accessed per
  invocation — the same un-gameable compiler numbers util/flops.py
  uses for training MFU) lands in a per-engine cost table when the
  program is resolved (jit-compiled, AOT-cache-loaded, or in-memory
  hit — warmup() therefore completes the table before traffic). The
  tick loop attributes each tick's device-busy interval to the
  programs dispatched that tick, proportionally to their analytic
  FLOPs, yielding ``serving_program_device_seconds_total{program}``,
  ``serving_program_flops_total{program}`` /
  ``serving_program_bytes_total{program}``, achieved FLOP/s and
  bytes/s, a live ``serving_mfu`` gauge (windowed achieved FLOP/s over
  the chip's peak — 0 when the chip's peak is unknown, e.g. CPU
  containers), and a per-program ROOFLINE classification: arithmetic
  intensity (FLOPs/byte) against the chip's ridge point
  (peak FLOP/s ÷ peak bytes/s) says whether each program is compute-
  or memory-bound — decode chunks live far left of the ridge, big
  prefill buckets to its right.
- **Per-tenant cost metering** (`TenantMeter`). ``submit(tenant=...)``
  threads a tenant label through the request lifecycle; every token a
  request actually COMPUTES (prefilled prompt tokens — prefix-cache
  hits and migrated chains excluded, the round-19
  serving_prefill_tokens_total semantics — plus committed decode
  tokens) bills ``tokens x the per-token analytic cost`` of the
  program that computed them into
  ``serving_request_cost_flops_total{tenant}`` /
  ``serving_request_cost_bytes_total{tenant}`` /
  ``serving_tenant_tokens_total{tenant,kind}``. The tenant label set
  is CARDINALITY-BOUNDED: the first ``top_n`` distinct tenants get
  their own label, everyone later folds into ``"other"`` — a hostile
  tenant-id stream cannot explode the scrape
  (observability/federation.check_cardinality guards the federated
  merge; tests/test_profiling.py hammers exactly that). Per-request
  bills accumulate on the handle (``handle.cost_flops``), so
  ``sum(per-request bills) == the counter`` by construction — the
  fleet cost report's exactness contract.
- **On-demand capture** (`ProfileCapture`). ``/profilez?seconds=N``
  (observability/export.MetricsServer) starts one bounded
  ``jax.profiler`` trace into a configured directory — single-flight
  (a second capture while one runs gets 503), 503 when unsupported
  (no directory configured, or no jax.profiler) — so "what was the
  device doing during that spike" is one curl away, per replica or
  router-fanned (`serving/fleet.Router.profilez`).

Disable-by-injection mirrors the rest of the observability substrate:
`NULL_PROFILER` makes every call a no-op.
"""
from __future__ import annotations

import logging
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

log = logging.getLogger("deeplearning4j_tpu")

_perf = time.perf_counter

#: Tenant label under which every tenant past the top-N bound (and
#: requests submitted without a tenant= when fold_default is set) is
#: aggregated — the scrape-side cardinality backstop.
OTHER_TENANT = "other"

#: Default tenant label for requests submitted without ``tenant=`` —
#: unattributed traffic is still metered, just not per-customer.
DEFAULT_TENANT = "default"


def cost_from_compiled(compiled) -> dict:
    """{'flops': float, 'bytes': float} from a compiled executable's
    XLA cost analysis — {} when the backend offers no estimate (some
    PJRT plugins raise UNIMPLEMENTED; availability over purity, the
    caller's table simply stays rate-less for that program)."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return {}
    if not ca:
        return {}
    out = {}
    f = ca.get("flops")
    b = ca.get("bytes accessed")
    if f is not None and f >= 0:
        out["flops"] = float(f)
    if b is not None and b >= 0:
        out["bytes"] = float(b)
    return out


def roofline(flops: float, bytes_: float,
             peak_flops: Optional[float],
             peak_bytes_per_s: Optional[float]) -> dict:
    """Roofline classification of one program: arithmetic intensity
    (FLOPs per byte accessed) against the chip's ridge point
    (peak FLOP/s ÷ peak bytes/s). Left of the ridge the roofline's
    slanted (bandwidth) roof binds — memory-bound; right of it the
    flat (compute) roof does. "unknown" when either peak is unknown
    (CPU containers) or the program has no byte estimate."""
    intensity = (flops / bytes_) if bytes_ and bytes_ > 0 else None
    ridge = (peak_flops / peak_bytes_per_s
             if peak_flops and peak_bytes_per_s else None)
    if intensity is None or ridge is None:
        bound = "unknown"
    elif intensity >= ridge:
        bound = "compute"
    else:
        bound = "memory"
    return {"intensity_flops_per_byte": (round(intensity, 3)
                                         if intensity is not None
                                         else None),
            "ridge_flops_per_byte": (round(ridge, 3)
                                     if ridge is not None else None),
            "bound": bound}


class TenantMeter:
    """Per-tenant analytic cost counters with a top-N + "other"
    cardinality bound.

    Prometheus counter children are immutable once created, so the
    bound is enforced at label-assignment time: the first ``top_n``
    distinct tenant ids seen get their own label; every later id maps
    to ``"other"``. Host-side per-tenant totals are kept for the SAME
    bounded id set (ranking and reports never resurrect a folded
    tenant), so a hostile stream of unique ids costs one dict entry —
    the "other" row — not one series each.
    """

    def __init__(self, registry, top_n: int = 8):
        self.top_n = max(1, int(top_n))
        self._lock = threading.Lock()
        self._labels: Dict[str, str] = {}
        self._totals: Dict[str, dict] = {}
        self._folded = 0
        self._m_flops = registry.counter(
            "serving_request_cost_flops",
            "Analytic FLOPs billed to requests, by tenant (tokens "
            "actually computed x the per-token XLA cost of the "
            "program that computed them; prefix-cache hits and "
            "migrated KV bill only the tokens recomputed)",
            labelnames=("tenant",))
        self._m_bytes = registry.counter(
            "serving_request_cost_bytes",
            "Analytic bytes accessed billed to requests, by tenant",
            labelnames=("tenant",))
        self._m_tokens = registry.counter(
            "serving_tenant_tokens",
            "Tokens computed for requests, by tenant and kind "
            "(prefill = prompt tokens this engine prefilled, decode "
            "= committed generated tokens)",
            labelnames=("tenant", "kind"))

    def label_for(self, tenant: Optional[str]) -> str:
        t = DEFAULT_TENANT if tenant is None else str(tenant)
        with self._lock:
            lab = self._labels.get(t)
            if lab is None:
                if len(self._labels) < self.top_n:
                    lab = t
                else:
                    lab = OTHER_TENANT
                    self._folded += 1
                self._labels[t] = lab
            return lab

    def bill(self, tenant: Optional[str], flops: float, bytes_: float,
             tokens: int, kind: str) -> str:
        """Record one bill; returns the (bounded) label used."""
        lab = self.label_for(tenant)
        if flops:
            self._m_flops.labels(lab).inc(flops)
        if bytes_:
            self._m_bytes.labels(lab).inc(bytes_)
        if tokens:
            self._m_tokens.labels(lab, kind).inc(tokens)
        with self._lock:
            cell = self._totals.setdefault(
                lab, {"flops": 0.0, "bytes": 0.0,
                      "prefill_tokens": 0, "decode_tokens": 0})
            cell["flops"] += flops
            cell["bytes"] += bytes_
            cell[f"{kind}_tokens"] = (cell.get(f"{kind}_tokens", 0)
                                      + int(tokens))
        return lab

    def report(self) -> dict:
        """Per-tenant bill ranked by FLOPs, plus the fold accounting
        (how many distinct ids landed in "other")."""
        with self._lock:
            totals = {t: dict(v) for t, v in self._totals.items()}
            distinct = len(self._labels)
            folded = self._folded
        ranked = sorted(totals.items(),
                        key=lambda kv: -kv[1]["flops"])
        return {"top_n": self.top_n,
                "distinct_tenants_seen": distinct,
                "bills_folded_to_other": folded,
                "tenants": {t: {
                    "flops": v["flops"], "bytes": v["bytes"],
                    "prefill_tokens": v["prefill_tokens"],
                    "decode_tokens": v["decode_tokens"]}
                    for t, v in ranked}}


class EngineProfiler:
    """Per-engine device accounting: program cost table, per-tick
    device-time attribution, live MFU, roofline report, and the tenant
    meter. One instance per engine (injected like recorder/slo);
    enabled is True — `NULL_PROFILER` is the off switch.

    ``peak_flops`` / ``peak_bytes_per_s`` default to the chip tables
    in util/flops.py (None on CPU → MFU reports 0 and rooflines read
    "unknown"); tests inject synthetic peaks to pin classifications.
    """

    enabled = True

    def __init__(self, registry, *,
                 peak_flops: Optional[float] = None,
                 peak_bytes_per_s: Optional[float] = None,
                 tenant_top_n: int = 8,
                 window_s: float = 60.0):
        from deeplearning4j_tpu.util.flops import (chip_peak_bytes_per_s,
                                                   chip_peak_flops)
        self.registry = registry
        if peak_flops is None:
            try:
                peak_flops = chip_peak_flops()
            except Exception:
                peak_flops = None
        if peak_bytes_per_s is None:
            try:
                peak_bytes_per_s = chip_peak_bytes_per_s()
            except Exception:
                peak_bytes_per_s = None
        self.peak_flops = peak_flops
        self.peak_bytes_per_s = peak_bytes_per_s
        self.window_s = float(window_s)
        self._lock = threading.Lock()
        # program -> {"flops": per-invocation, "bytes": per-invocation,
        #             "tokens": tokens one invocation computes}
        self._table: Dict[str, dict] = {}
        # open tick state: labels dispatched since tick_begin (None =
        # no open tick: resolutions outside the tick loop — warmup,
        # batch mode — are recorded in the table but not attributed);
        # _last_labels backs commit-only drain ticks (see tick_end)
        self._tick_labels: Optional[List[str]] = None
        self._last_labels: List[str] = []
        self._window: deque = deque(maxlen=4096)   # (t, flops, bytes,
        #                                             busy_s)
        self.meter = TenantMeter(registry, top_n=tenant_top_n)
        self._m_invocations = registry.counter(
            "serving_program_invocations",
            "Compiled-program dispatches, by program",
            labelnames=("program",))
        self._m_device_seconds = registry.counter(
            "serving_program_device_seconds",
            "Device-busy seconds attributed to each program "
            "(tick busy intervals split across the tick's dispatches "
            "proportionally to their analytic FLOPs)",
            labelnames=("program",))
        self._m_flops = registry.counter(
            "serving_program_flops",
            "Analytic FLOPs dispatched, by program (XLA cost "
            "analysis x invocations)", labelnames=("program",))
        self._m_bytes = registry.counter(
            "serving_program_bytes",
            "Analytic bytes accessed dispatched, by program",
            labelnames=("program",))
        registry.gauge(
            "serving_mfu",
            "Model-FLOPs utilization over the recent window: achieved "
            "analytic FLOP/s / chip peak (0 when the chip peak is "
            "unknown, e.g. CPU)").set_function(lambda: self.mfu())
        registry.gauge(
            "serving_achieved_flops_per_second",
            "Analytic FLOP/s achieved over the recent window"
            ).set_function(lambda: self.achieved()[0])
        registry.gauge(
            "serving_achieved_bytes_per_second",
            "Analytic bytes/s accessed over the recent window"
            ).set_function(lambda: self.achieved()[1])

    # -- cost table ----------------------------------------------------
    def record_program(self, label: str, cost: Optional[dict],
                       tokens: Optional[int]) -> None:
        """Install (or refresh) one program's per-invocation cost.
        Idempotent; a rate-less entry (backend without cost analysis)
        still counts invocations and device seconds."""
        with self._lock:
            ent = self._table.setdefault(
                label, {"flops": 0.0, "bytes": 0.0, "tokens": 0,
                        "invocations": 0, "device_seconds": 0.0})
            if cost:
                ent["flops"] = float(cost.get("flops", 0.0))
                ent["bytes"] = float(cost.get("bytes", 0.0))
            if tokens:
                ent["tokens"] = int(tokens)

    def has_program(self, label: str) -> bool:
        with self._lock:
            return label in self._table

    def token_cost(self, label: Optional[str]) -> Tuple[float, float]:
        """(flops, bytes) one token costs under ``label``'s program —
        per-invocation cost over the tokens one invocation computes.
        (0, 0) for unknown programs (batch-mode generate has no fixed
        geometry to cost)."""
        if label is None:
            return 0.0, 0.0
        with self._lock:
            ent = self._table.get(label)
            if ent is None or not ent["tokens"]:
                return 0.0, 0.0
            return (ent["flops"] / ent["tokens"],
                    ent["bytes"] / ent["tokens"])

    # -- per-tick attribution ------------------------------------------
    def tick_begin(self) -> None:
        self._tick_labels = []

    def dispatched(self, label: str) -> None:
        """One compiled-call dispatch (the engine's _resolve_program
        funnel). Only attributed when a tick is open — warmup
        resolutions and batch-mode calls update the table, not the
        attribution."""
        if self._tick_labels is not None:
            self._tick_labels.append(label)

    def tick_end(self, busy_s: float) -> None:
        """Close the tick: attribute its device-busy interval across
        the dispatched programs proportionally to their analytic
        FLOPs (equal split when no program has a rate), advance the
        per-program counters, and push the tick into the MFU
        window. A commit-only tick (the pipelined loop's drain tail:
        it syncs the PREVIOUS tick's dispatches without issuing new
        ones) attributes its busy interval to the previous tick's
        label mix — attribution conserves the engine's busy total."""
        labels, self._tick_labels = self._tick_labels, None
        busy_s = max(0.0, float(busy_s))
        if not labels:
            if busy_s <= 0.0 or not self._last_labels:
                return
            labels = list(self._last_labels)
            dispatched = False
        else:
            self._last_labels = list(labels)
            dispatched = True
        with self._lock:
            weights = [max(0.0, self._table.get(l, {}).get("flops",
                                                           0.0))
                       for l in labels]
            total_w = sum(weights)
            if total_w <= 0:
                weights = [1.0] * len(labels)
                total_w = float(len(labels))
            tick_flops = tick_bytes = 0.0
            for lab, w in zip(labels, weights):
                ent = self._table.setdefault(
                    lab, {"flops": 0.0, "bytes": 0.0, "tokens": 0,
                          "invocations": 0, "device_seconds": 0.0})
                share = busy_s * w / total_w
                ent["device_seconds"] += share
                if share:
                    self._m_device_seconds.labels(lab).inc(share)
                if not dispatched:
                    continue     # drain tail: time only, no new work
                ent["invocations"] += 1
                tick_flops += ent["flops"]
                tick_bytes += ent["bytes"]
                self._m_invocations.labels(lab).inc()
                if ent["flops"]:
                    self._m_flops.labels(lab).inc(ent["flops"])
                if ent["bytes"]:
                    self._m_bytes.labels(lab).inc(ent["bytes"])
        self._window.append((_perf(), tick_flops, tick_bytes, busy_s))

    # -- derived rates -------------------------------------------------
    def achieved(self, window_s: Optional[float] = None
                 ) -> Tuple[float, float]:
        """(FLOP/s, bytes/s) achieved over the recent window —
        analytic work dispatched over wall time elapsed."""
        w = self.window_s if window_s is None else float(window_s)
        now = _perf()
        pts = [p for p in self._window if now - p[0] <= w]
        if not pts:
            return 0.0, 0.0
        elapsed = max(now - pts[0][0], 1e-9)
        return (sum(p[1] for p in pts) / elapsed,
                sum(p[2] for p in pts) / elapsed)

    def mfu(self, window_s: Optional[float] = None) -> float:
        """Live MFU: windowed achieved FLOP/s over the chip peak. 0.0
        when the peak is unknown (the gauge must still scrape)."""
        if not self.peak_flops:
            return 0.0
        return self.achieved(window_s)[0] / self.peak_flops

    # -- tenant billing ------------------------------------------------
    def bill_tokens(self, handle, label: Optional[str], tokens: int,
                    kind: str) -> None:
        """Bill ``tokens`` computed under ``label``'s program to the
        handle's tenant, and accumulate the same amounts on the handle
        (sum of per-request bills == the counters, by construction)."""
        if tokens <= 0:
            return
        fl_rate, by_rate = self.token_cost(label)
        flops = fl_rate * tokens
        bytes_ = by_rate * tokens
        tenant = getattr(handle, "tenant", None)
        self.meter.bill(tenant, flops, bytes_, tokens, kind)
        handle.cost_flops = getattr(handle, "cost_flops", 0.0) + flops
        handle.cost_bytes = getattr(handle, "cost_bytes", 0.0) + bytes_

    # -- reports -------------------------------------------------------
    def program_report(self) -> dict:
        """The per-program accounting table: per-invocation analytic
        cost, totals, achieved rates, and the roofline verdict."""
        with self._lock:
            table = {l: dict(v) for l, v in self._table.items()}
        out = {}
        for lab, ent in sorted(table.items()):
            dev = ent["device_seconds"]
            inv = ent["invocations"]
            row = {"flops_per_invocation": ent["flops"],
                   "bytes_per_invocation": ent["bytes"],
                   "tokens_per_invocation": ent["tokens"],
                   "invocations": inv,
                   "device_seconds": dev,
                   "flops_total": ent["flops"] * inv,
                   "bytes_total": ent["bytes"] * inv,
                   "achieved_flops_per_s": (
                       round(ent["flops"] * inv / dev, 1)
                       if dev > 0 else None),
                   "achieved_bytes_per_s": (
                       round(ent["bytes"] * inv / dev, 1)
                       if dev > 0 else None)}
            row.update(roofline(ent["flops"], ent["bytes"],
                                self.peak_flops,
                                self.peak_bytes_per_s))
            out[lab] = row
        return out

    def report(self) -> dict:
        """The `/profilez`-adjacent `profile_report()` body: peaks,
        live MFU, achieved rates, per-program rooflines, per-tenant
        bills."""
        fl, by = self.achieved()
        return {"peak_flops": self.peak_flops,
                "peak_bytes_per_s": self.peak_bytes_per_s,
                "ridge_flops_per_byte": (
                    round(self.peak_flops / self.peak_bytes_per_s, 3)
                    if self.peak_flops and self.peak_bytes_per_s
                    else None),
                "mfu": round(self.mfu(), 6),
                "achieved_flops_per_s": round(fl, 1),
                "achieved_bytes_per_s": round(by, 1),
                "programs": self.program_report(),
                "tenant_costs": self.meter.report()}


class NullProfiler:
    """No-op twin: disable profiling by injection, never by if-guards."""

    enabled = False
    peak_flops = None
    peak_bytes_per_s = None

    def record_program(self, label, cost, tokens) -> None:
        pass

    def has_program(self, label) -> bool:
        return True          # suppress re-capture work at call sites

    def token_cost(self, label):
        return 0.0, 0.0

    def tick_begin(self) -> None:
        pass

    def dispatched(self, label) -> None:
        pass

    def tick_end(self, busy_s) -> None:
        pass

    def achieved(self, window_s=None):
        return 0.0, 0.0

    def mfu(self, window_s=None) -> float:
        return 0.0

    def bill_tokens(self, handle, label, tokens, kind) -> None:
        pass

    def program_report(self) -> dict:
        return {}

    def report(self) -> dict:
        return {"enabled": False}


NULL_PROFILER = NullProfiler()


class ProfileCapture:
    """Single-flight on-demand `jax.profiler` capture — the
    ``/profilez?seconds=N`` endpoint's backend.

    ``capture(seconds)`` starts one bounded trace into the configured
    directory and returns ``(http_status, body_dict)``:

    - 200: capture started; a daemon timer stops it after ``seconds``
      (bounded by ``max_seconds`` so a fat-fingered query cannot
      profile for an hour).
    - 503: unsupported (no directory configured / jax.profiler
      unavailable) or BUSY (single-flight: one capture at a time —
      two overlapping traces corrupt each other's TensorBoard dirs).
    - 400: unparseable/non-positive seconds.
    """

    def __init__(self, directory: Optional[str],
                 max_seconds: float = 60.0):
        self.directory = str(directory) if directory else None
        self.max_seconds = float(max_seconds)
        self._lock = threading.Lock()
        self._active_until: Optional[float] = None
        self.captures = 0

    @staticmethod
    def supported() -> bool:
        try:
            import jax.profiler
            return (hasattr(jax.profiler, "start_trace")
                    and hasattr(jax.profiler, "stop_trace"))
        except Exception:
            return False

    @property
    def active(self) -> bool:
        with self._lock:
            return (self._active_until is not None
                    and _perf() < self._active_until + 5.0)

    def capture(self, seconds: float) -> Tuple[int, dict]:
        try:
            seconds = float(seconds)
        except (TypeError, ValueError):
            return 400, {"error": f"unparseable seconds {seconds!r}"}
        if seconds <= 0:
            return 400, {"error": "seconds must be > 0"}
        seconds = min(seconds, self.max_seconds)
        if self.directory is None:
            return 503, {"error": "profiler capture unsupported: no "
                                  "profile_dir configured"}
        if not self.supported():
            return 503, {"error": "profiler capture unsupported: "
                                  "jax.profiler unavailable"}
        with self._lock:
            if (self._active_until is not None
                    and _perf() < self._active_until):
                return 503, {"error": "capture already in progress",
                             "remaining_s": round(
                                 self._active_until - _perf(), 3)}
            import jax.profiler
            try:
                jax.profiler.start_trace(self.directory)
            except Exception as e:
                return 503, {"error": f"start_trace failed: "
                                      f"{type(e).__name__}: {e}"}
            self._active_until = _perf() + seconds
            self.captures += 1

        def _stop():
            time.sleep(seconds)
            import jax.profiler as jp
            try:
                jp.stop_trace()
            except Exception:
                log.exception("profiler stop_trace failed")
            finally:
                with self._lock:
                    self._active_until = None

        threading.Thread(target=_stop, daemon=True,
                         name="profilez-capture").start()
        return 200, {"started": True, "seconds": seconds,
                     "directory": self.directory,
                     "capture": self.captures}
