"""A kernel's share of its roofline: the least seconds a step the chip needs
for the kernel's required work (the cell's arch, `count`) times the steps
the traced window holds, over the device seconds of the operations that
match `op` in that window, in percent.

The steps are the traced window over the step's period (start of one
execution of the program `module` to the start of the next, as `train_mfu`
takes it), not the executions the trace lists: the window's edges cut two
executions, whose operations are in the seconds, so a window that lists
four executions and one that lists five of the same steps read the same. A
recomputed forward adds to the seconds and not to the work."""
from perfbench.readers_common import program_runs


def read(run, args):
    red = run.get("trace")
    if red is None or red.window_s <= 0:
        return None
    runs = sorted(program_runs(run, args))
    seconds = red.op_seconds(args["op"])
    if len(runs) < 2 or not seconds:
        return None
    fn = run["cell"].count(args["count"])
    period = (runs[-1][0] - runs[0][0]) / (len(runs) - 1)
    least = fn(run["sizes"], run["rows"] // run["chips"], run["seq"],
               run["device_kind"])
    return 100.0 * least * (red.window_s / period) / seconds
