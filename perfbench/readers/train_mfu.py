"""Required forward + backward FLOPs of a step (no recomputation) over the
step's period on the device (start of one execution of the step program to
the start of the next, idle gaps included) times chips times peak."""
from perfbench.harness import arith
from perfbench.readers_common import program_runs


def read(run, args):
    runs = sorted(program_runs(run, args))
    if len(runs) < 2:
        return None
    period = (runs[-1][0] - runs[0][0]) / (len(runs) - 1)
    flops = run["cell"].count("train_flops_per_step")(
        run["sizes"], run["rows"], run["seq"])
    peak = arith.peaks(run["device_kind"])["flops_per_s"]
    return 100.0 * flops / (period * peak * run["chips"])
