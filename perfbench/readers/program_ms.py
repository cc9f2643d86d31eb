"""Device time of one compiled program, from the trace's `XLA Modules`
line: the median duration of its executions inside the traced window, in
ms, divided by `steps` (the decode steps in one decode program)."""
import statistics

from perfbench.readers_common import program_runs


def read(run, args):
    runs = program_runs(run, args)
    if not runs:
        return None
    return (statistics.median(d for _, d in runs) * 1e3
            / float(args.get("steps", 1)))
