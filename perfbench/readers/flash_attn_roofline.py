"""The attention kernels' share of their roofline over the traced steps:
the least time the chip needs for the causal attention of those steps,
forward and backward (the cell's arch), over the kernels' device time. A
recomputed forward adds to the time and not to the work."""
from perfbench.readers_common import program_runs


def read(run, args):
    red = run.get("trace")
    if red is None:
        return None
    seconds = red.op_seconds(args["kernel"])
    steps = len(program_runs(run, args))
    if not seconds or not steps:
        return None
    least = steps * run["cell"].count("flash_train_roofline_s")(
        run["sizes"], run["rows"] // run["chips"], run["seq"],
        run["device_kind"])
    return 100.0 * least / seconds
