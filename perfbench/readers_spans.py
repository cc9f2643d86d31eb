"""What the span readers share: the program's span records
(`deeplearning4j_tpu.observability.tracing`: id, parent_id, name, start,
end, tick, rid, args; `perf_counter` times) cut to a run's window.

A program without the ring (one older than the spans) gives None
everywhere, and the metric is left out of the line.
"""


def snapshot(run):
    """The ring's records and clock anchor; `run["span_snapshot"]` where a
    test hands one in."""
    snap = run.get("span_snapshot")
    if snap is not None:
        return snap
    try:
        from deeplearning4j_tpu.observability import tracing
        return tracing.default_spans().snapshot()
    except (ImportError, AttributeError):
        return None


def window(run, args):
    """(a, b) on the host's clock: the measured window, or with
    `"window": "traced"` the part of it the profiler traced."""
    if args.get("window", "run") == "traced":
        tracer = run.get("tracer")
        if tracer is None or tracer.t_b is None:
            return None
        return tracer.t_a, tracer.t_b
    return run["t0"], run["t1"]


def spans_in(run, args):
    """The records that start inside the window, oldest first; None where
    there is no ring, no window, or the window holds no record at all (a
    program whose loop makes no spans reads nothing, not zero)."""
    snap, w = snapshot(run), window(run, args)
    if snap is None or w is None:
        return None
    got = [s for s in snap.spans if w[0] <= s.start < w[1]]
    return got or None


def named(spans, args):
    """Those called `args["name"]` whose args hold all of `where`."""
    where = args.get("where", {})
    return [s for s in spans if s.name == args["name"]
            and all(s.args.get(k) == v for k, v in where.items())]
