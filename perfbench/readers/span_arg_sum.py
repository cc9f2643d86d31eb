"""The sum of one numeric arg over the spans called `name` that start
inside the window."""
from perfbench.readers_spans import named, spans_in


def read(run, args):
    spans = spans_in(run, args)
    if spans is None:
        return None
    return float(sum(s.args.get(args["arg"], 0) for s in named(spans, args)))
