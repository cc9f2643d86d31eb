"""A percentile `q`, over the spans called `name` that start inside the
window, of the span's length less that of its descendants called `less` (a
name or a list of names), in ms: a scheduling round without the one wait on
the device is the host's own work in it."""
from perfbench.harness import stats
from perfbench.readers_spans import named, spans_in


def read(run, args):
    spans = spans_in(run, args)
    if spans is None:
        return None
    own = {s.id: s.end - s.start for s in named(spans, args)}
    if not own:
        return None
    less = args["less"]
    less = {less} if isinstance(less, str) else set(less)
    parent = {s.id: s.parent_id for s in spans}
    for s in spans:
        if s.name not in less:
            continue
        up = s.parent_id
        while up is not None and up not in own:
            up = parent.get(up)
        if up is not None:
            own[up] -= s.end - s.start
    return stats.percentile([v * 1e3 for v in own.values()],
                            float(args.get("q", 50)))
