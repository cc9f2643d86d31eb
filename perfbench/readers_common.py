"""What several readers share: picking a compiled program's executions out
of the reduced trace. The serving programs carry no stable name (both are
`jit_run`), so a program is picked by its module name and, where that is not
enough, by an operation it holds or lacks."""
import bisect
import re


def program_runs(run, args):
    """[(start_s, duration_s)] of the executions, on device 0, of the
    program that `args` describes: `module` (regex on the module's name),
    `has_op` / `lacks_op` (regex on an operation inside it)."""
    red = run.get("trace")
    if red is None or not red.devices:
        return []
    runs = red.module_runs(args.get("module", "."))
    for key, want in (("has_op", True), ("lacks_op", False)):
        if key not in args:
            continue
        rx = re.compile(args[key])
        marks = sorted(st for k, name, st, _ in red.devices[0]["op_events"]
                       if rx.search(k) or rx.search(name))

        def holds(st, dur):
            i = bisect.bisect_left(marks, st)
            return i < len(marks) and marks[i] < st + dur
        runs = [(st, d) for st, d in runs if holds(st, d) == want]
    return runs
