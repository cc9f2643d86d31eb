"""Seconds inside the spans called `part` (a name or a list of names)
over seconds inside the spans called `whole`, both starting inside the
window, in percent."""
from perfbench.readers_spans import spans_in


def read(run, args):
    spans = spans_in(run, args)
    if spans is None:
        return None
    part = args["part"]
    part = {part} if isinstance(part, str) else set(part)
    inside = sum(s.end - s.start for s in spans if s.name in part)
    whole = sum(s.end - s.start for s in spans if s.name == args["whole"])
    return 100.0 * inside / whole if whole > 0 else None
