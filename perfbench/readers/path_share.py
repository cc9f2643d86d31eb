"""Device seconds of the operations whose name path holds `args.name`
anywhere, over the device's busy seconds in the traced window, in percent.
Any name the program gives: unlike `scope_share`, which classifies by the
names of `harness/scope_names.json` alone, this reads a name outside that
vocabulary too (the gated short convolution's `shortconv` and
`shortconv.conv`, which the scope table counts under the enclosing
`layers` or `lead`).

The paths are `harness/scopes.py`'s own: each operation's self time
(`self_times`), named through the compiled step's text (`step_hlo`,
`index_from_hlo`; an operation without a path borrows its neighbour's,
`resolve`). The step is lowered once a run, and the index kept in `run` for
the other metrics of this reader. None where the run has no reduced trace
or no index, or where no operation of the program carries the name: a
program without it, such as a parent tree, reads nothing."""
import sys

from perfbench.harness import scopes


def _index(run):
    if run.get("scope_index") is not None:
        return run["scope_index"]
    if "path_index" not in run:
        run["path_index"] = None
        if {"cell", "sizes", "traffic", "rows", "seq"} <= set(run):
            try:
                text = scopes.step_hlo(run)
            except Exception as e:  # the metric is left out, the run stands
                print(f"perfbench: path index not made: {e!r}",
                      file=sys.stderr)
                text = None
            if text is not None:
                run["path_index"] = scopes.index_from_hlo(text)
    return run["path_index"]


def read(run, args):
    red = run.get("trace")
    if red is None or not red.devices or red.busy_s <= 0:
        return None
    index = _index(run)
    name = args["name"]
    if index is None or not any(op.path and name in scopes.names_in(op.path)
                                for op in index.values()):
        return None
    used_by = scopes.users(index)
    holds = {}                          # an event's text -> holds the name
    seconds = 0.0
    for dev in red.devices:
        for (_, text, _, _), sec in scopes.self_times(dev["op_events"]):
            if text not in holds:
                got = scopes.parse_op(text, "")
                op = index.get(got[0]) if got else None
                path = scopes.resolve(index, got[0], used_by) \
                    if op is not None and op.head == got[1].head else None
                holds[text] = name in scopes.names_in(path or "")
            if holds[text]:
                seconds += sec
    return 100.0 * seconds / len(red.devices) / red.busy_s
