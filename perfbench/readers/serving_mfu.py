"""Model FLOPs of the tokens that the traced window processed (counted by
the benchmark's client, arithmetic in the cell's arch) over the device time
of the program that processed them times the chip's peak; with no program
named, over the whole traced window (the whole step's share). Tokens are
stamped at commit, one scheduling round after their program ran, so a window
of a dozen rounds is off by up to one round in twelve."""
from perfbench.harness import arith, stats
from perfbench.readers_common import program_runs


def read(run, args):
    red, tracer = run.get("trace"), run.get("tracer")
    if red is None or tracer is None:
        return None
    work = stats.decode_and_prefill_work(run["log"], tracer.t_a, tracer.t_b)
    s, forward_flops = run["sizes"], run["cell"].count("forward_flops")
    flops = 0.0
    if args["tokens"] in ("decode", "all"):
        flops += forward_flops(s, work["decode_tokens"],
                               work["decode_rows"])
    if args["tokens"] in ("prefill", "all"):
        flops += forward_flops(s, work["prefill_tokens"],
                               work["prefill_pairs"])
    if "module" in args or "has_op" in args:
        seconds = sum(d for _, d in program_runs(run, args))
    else:
        seconds = red.window_s
    if flops <= 0 or seconds <= 0:
        return None
    peak = arith.peaks(run["device_kind"])["flops_per_s"]
    return 100.0 * flops / (seconds * peak * run["cell"].chips)
