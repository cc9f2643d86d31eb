"""Model FLOPs (the cell's arch) of the tokens that the program's own
dispatch spans scheduled in the traced window, over the device time of the
program `module` there times the chip's peak; with no program named, over
the whole traced window. The tokens are those of the program that ran, not
of the commit one scheduling round later (readers/serving_mfu.py)."""
from perfbench.harness import arith
from perfbench.readers_spans import spans_in


def read(run, args):
    red = run.get("trace")
    spans = spans_in(run, dict(args, window="traced"))
    if red is None or spans is None:
        return None
    s, forward_flops = run["sizes"], run["cell"].count("forward_flops")
    flops = 0.0
    for sp in spans:
        a = sp.args
        if (sp.name == "engine.dispatch.decode"
                and args["tokens"] in ("decode", "all")):
            flops += forward_flops(s, a.get("decode_tokens", 0),
                                   a.get("decode_rows", 0))
        elif (sp.name == "engine.dispatch.prefill"
                and args["tokens"] in ("prefill", "all")):
            flops += forward_flops(s, a.get("prefill_tokens", 0),
                                   a.get("prefill_pairs", 0))
    if "module" in args:
        seconds = sum(d for _, d in red.module_runs(args["module"]))
    else:
        seconds = red.window_s
    if flops <= 0 or seconds <= 0:
        return None
    peak = arith.peaks(run["device_kind"])["flops_per_s"]
    return 100.0 * flops / (seconds * peak * run["cell"].chips)
