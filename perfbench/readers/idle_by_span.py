"""Of the seconds device 0 sat idle in the traced window (the gaps
between its operations), the share whose gap's midpoint lies inside one of
the program's spans, in percent. A gap outside every span is a part of the
loop that has none. Idle seconds by innermost span are printed to standard
error.

The reduced trace counts its seconds from the profile's start and does not
keep when that was, so the spans (on `perf_counter`) are laid beside it by
the traced window, which both sides have: it opens at the tracer's `t_a`
and at the first clipped operation. Where the device was idle at the
window's edges, that idle time (the window's length less the operations'
extent) cannot be told head from tail and is split evenly; it is printed."""
import bisect
import sys

from perfbench.harness import xplane
from perfbench.readers_spans import snapshot, window


def read(run, args):
    red, snap = run.get("trace"), snapshot(run)
    w = window(run, dict(args, window="traced"))
    if red is None or not red.devices or snap is None or w is None:
        return None
    ops = [(st, st + dur) for _, _, st, dur in red.devices[0]["op_events"]]
    spans = sorted((s for s in snap.spans if s.end > w[0] and s.start < w[1]
                    and s.end > s.start), key=lambda s: s.start)
    if not ops or not spans:
        return None
    starts = [s.start for s in spans]
    by_id = {s.id: s for s in spans}

    def innermost(t):
        """The span that holds `t` and started last: spans of one thread
        nest, so it is the last to start before `t` or one it lies in."""
        i = bisect.bisect_right(starts, t) - 1
        s = spans[i] if i >= 0 else None
        while s is not None and not s.start <= t < s.end:
            s = by_id.get(s.parent_id)
        return s.name if s else "(no span)"

    lo, hi = min(a for a, _ in ops), max(b for _, b in ops)
    at_edges = max(0.0, red.window_s - (hi - lo))
    shift = w[0] - (lo - at_edges / 2)      # host's clock = trace's + shift
    by_name = {}
    for a, b in xplane.gaps([(a + shift, b + shift) for a, b in ops],
                            w[0], w[0] + red.window_s):
        name = innermost((a + b) / 2)
        by_name[name] = by_name.get(name, 0.0) + (b - a)
    idle = sum(by_name.values())
    if idle <= 0:
        return None
    print(f"perfbench: idle {at_edges:.6f} s at the traced window's edges",
          file=sys.stderr)
    for name, sec in sorted(by_name.items(), key=lambda kv: -kv[1]):
        print(f"perfbench: idle {sec:.6f} s inside {name}", file=sys.stderr)
    return 100.0 * (idle - by_name.get("(no span)", 0.0)) / idle
