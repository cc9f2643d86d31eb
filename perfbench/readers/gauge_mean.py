"""Mean over the window of an engine gauge, sampled by the benchmark's
client after every scheduling round, as a share of `of`: a constant from the
traffic file's engine settings, or the sum of the named gauges."""
from perfbench.harness.serve import Client


def read(run, args):
    samples = [s for s in run.get("samples", ())
               if run["t0"] < s[0] <= run["t1"]]
    if not samples:
        return None
    col = {g: i + 1 for i, g in enumerate(Client.GAUGES)}
    shares = []
    for s in samples:
        if "of_engine_setting" in args:
            whole = float(run["engine_settings"][args["of_engine_setting"]])
        else:
            whole = sum(s[col[g]] for g in args["of_gauges"])
        if whole > 0:
            shares.append(s[col[args["gauge"]]] / whole)
    if not shares:
        return None
    return 100.0 * sum(shares) / len(shares)
