"""How often the operation `op` runs inside one execution of the program
`module`: the median over the executions inside the traced window, on
device 0. A kernel that a rematerialised forward runs again counts twice.
The median, because the window's last execution can be one that the end of
the profile cut short, with part of its operations. `op` is matched against
an operation's key only: its full name also holds its operands' names."""
import bisect
import re
import statistics


def read(run, args):
    red = run.get("trace")
    if red is None or not red.devices:
        return None
    runs = sorted(red.module_runs(args["module"]))
    starts = [st for st, _ in runs]
    calls = [0] * len(runs)
    rx = re.compile(args["op"])
    for key, _, st, _ in red.devices[0]["op_events"]:
        if rx.search(key):
            i = bisect.bisect_right(starts, st) - 1
            if i >= 0 and st < runs[i][0] + runs[i][1]:
                calls[i] += 1
    return float(statistics.median(calls)) if any(calls) else None
