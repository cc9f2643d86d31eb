"""The decode-attention kernel's share of its roofline: the least time the
chip needs to read each live K and V row once for the decode tokens that the
traced window committed (the cell's arch), over the kernel's device time
there. Tokens are stamped one scheduling round after their step ran."""
from perfbench.harness import stats


def read(run, args):
    red, tracer = run.get("trace"), run.get("tracer")
    if red is None or tracer is None:
        return None
    seconds = red.op_seconds(args["kernel"])
    work = stats.decode_and_prefill_work(run["log"], tracer.t_a, tracer.t_b)
    if not seconds or not work["decode_rows"]:
        return None
    least = run["cell"].count("decode_attn_roofline_s")(
        run["sizes"], work["decode_rows"], run["device_kind"])
    return 100.0 * least / seconds
