"""How many spans or marks called `name` (their args matching `where`)
start inside the window; with `"distinct": "tick"` how many scheduling
rounds hold at least one. Nought is a reading: the loop ran and made
none."""
from perfbench.readers_spans import named, spans_in


def read(run, args):
    spans = spans_in(run, args)
    if spans is None:
        return None
    hit = named(spans, args)
    if args.get("distinct") == "tick":
        return float(len({s.tick for s in hit}))
    return float(len(hit))
