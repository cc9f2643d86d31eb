"""Reduces a profiler trace (`.xplane.pb`) to what the readers need.

Only `jax.profiler.ProfileData` is used. A TPU trace holds one plane per
chip (`/device:TPU:n`) whose lines include `XLA Modules` (one event per
execution of a compiled program) and `XLA Ops` (one event per operation run),
and host planes whose lines are threads. The traced window is the host
annotation `perfbench_window`; device events are clipped to it.

Everything is returned in seconds. Operations are keyed by a name that
survives renumbering: the HLO opcode and result shape where the event
carries them, otherwise the event's name with its trailing number dropped.
"""
from __future__ import annotations

import bisect
import functools
import re
from pathlib import Path
from typing import Dict, List, Optional, Tuple

WINDOW = "perfbench_window"
_SHAPE = re.compile(r"(?P<dt>[a-z]+\d*)\[(?P<dims>[\d,]*)\]")
_NUM = re.compile(r"[.\-_]\d+$")


def find_xplane(directory: Path) -> Path:
    files = sorted(Path(directory).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return files[-1]


@functools.lru_cache(maxsize=None)
def op_key(name: str) -> str:
    """`%fusion.12 = bf16[32,16,128]{...} fusion(...)` ->
    `fusion_bf16_32_16_128_` (a tuple result gives its first part's shape);
    a Pallas kernel gets the prefix `tpu_custom_call:`; `fusion.12` ->
    `fusion`."""
    head, sep, rest = name.partition(" = ")
    base = _NUM.sub("", head.split(" ")[0].lstrip("%"))
    if not sep:
        return base
    sh = _SHAPE.search(rest)
    key = base
    if sh:
        key = f"{base}_{sh.group('dt')}_{sh.group('dims').replace(',', '_')}_"
    if "tpu_custom_call" in rest:
        key = "tpu_custom_call:" + key
    return key


def union_seconds(intervals: List[Tuple[float, float]]) -> float:
    total, end = 0.0, -1.0
    for a, b in sorted(intervals):
        if b <= end:
            continue
        total += b - max(a, end)
        end = b
    return total


def gaps(intervals: List[Tuple[float, float]], w0: float, w1: float):
    """Idle intervals inside [w0, w1]."""
    out, end = [], w0
    for a, b in sorted(intervals):
        if a > end:
            out.append((end, a))
        end = max(end, b)
    if w1 > end:
        out.append((end, w1))
    return out


class Reduced:
    """window_s; per device: busy_s, ops {key: seconds}, op_events
    [(key, name, start, dur)], modules [(name, start, dur)]; idle gaps
    named by what the host was doing."""

    def __init__(self):
        self.window_s = 0.0
        self.devices: List[dict] = []
        self.idle_gaps: Dict[str, float] = {}

    @property
    def busy_s(self) -> float:
        if not self.devices:
            return 0.0
        return sum(d["busy_s"] for d in self.devices) / len(self.devices)

    def ops_total(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for d in self.devices:
            for k, v in d["ops"].items():
                out[k] = out.get(k, 0.0) + v / len(self.devices)
        return out

    def op_seconds(self, pattern: str) -> Optional[float]:
        """Seconds of the operations whose key or name matches, a device's
        mean; None where nothing matches."""
        rx = re.compile(pattern)
        hit = [dur for d in self.devices
               for key, name, _, dur in d["op_events"]
               if rx.search(key) or rx.search(name)]
        return sum(hit) / len(self.devices) if hit else None

    def module_runs(self, pattern: str, device: int = 0):
        rx = re.compile(pattern)
        if not self.devices:
            return []
        return [(st, dur) for name, st, dur in self.devices[device]["modules"]
                if rx.search(name)]

    def breakdown(self, n: int = 10) -> dict:
        ops = sorted(self.ops_total().items(), key=lambda kv: -kv[1])[:n]
        idle = sorted(self.idle_gaps.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in idle]}


def _line_events(line):
    for ev in line.events:
        yield ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9


def reduce(path: Path, gap_count: int = 200) -> Reduced:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    out = Reduced()
    host_events: List[Tuple[float, float, str]] = []
    window = None
    device_planes = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            device_planes.append(plane)
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for name, st, dur in _line_events(line):
                if name == WINDOW:
                    window = (st, st + dur)
                elif dur > 0:
                    host_events.append((st, st + dur, name))
    if window is None:
        raise ValueError(f"no {WINDOW!r} annotation in {path}")
    w0, w1 = window
    out.window_s = w1 - w0

    def clip(st, dur):
        a, b = max(st, w0), min(st + dur, w1)
        return (a, b) if b > a else None

    all_busy = []
    for plane in sorted(device_planes, key=lambda p: p.name):
        dev = {"name": plane.name, "ops": {}, "op_events": [],
               "modules": [], "busy_s": 0.0}
        busy = []
        for line in plane.lines:
            if line.name == "XLA Ops":
                for name, st, dur in _line_events(line):
                    c = clip(st, dur)
                    if c is None:
                        continue
                    busy.append(c)
                    key = op_key(name)
                    dev["op_events"].append((key, name, c[0], c[1] - c[0]))
            elif line.name == "XLA Modules":
                for name, st, dur in _line_events(line):
                    if st >= w0 and st + dur <= w1:
                        dev["modules"].append((name, st, dur))
        # operations can nest (a loop holds its body): time is given to
        # the innermost, so that the parts add up to the busy time
        evs = sorted(dev["op_events"], key=lambda e: (e[2], -e[3]))
        stack: list = []
        self_time = [e[3] for e in evs]
        for i, (_, _, st, dur) in enumerate(evs):
            while stack and evs[stack[-1]][2] + evs[stack[-1]][3] <= st:
                stack.pop()
            if stack:
                self_time[stack[-1]] -= dur
            stack.append(i)
        for (key, _, _, _), t in zip(evs, self_time):
            dev["ops"][key] = dev["ops"].get(key, 0.0) + max(t, 0.0)
        dev["busy_s"] = union_seconds(busy)
        all_busy.append(busy)
        out.devices.append(dev)

    if all_busy:
        idle = sorted(gaps(all_busy[0], w0, w1),
                      key=lambda g: g[0] - g[1])[:gap_count]
        host_events.sort()
        starts = [e[0] for e in host_events]
        for a, b in idle:
            mid, best = (a + b) / 2, None
            hi = bisect.bisect_right(starts, mid)
            for st, en, name in host_events[max(0, hi - 4000):hi]:
                if en >= mid and (best is None or en - st < best[0]):
                    best = (en - st, name)
            name = op_key(best[1]) if best else "unattributed"
            out.idle_gaps[name] = out.idle_gaps.get(name, 0.0) + (b - a)
    return out
