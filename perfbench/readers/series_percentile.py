"""A percentile of one of the run's series (client-side times in ms, or the
flight recorder's queue waits), over every request due in the window."""
from perfbench.harness import stats


def read(run, args):
    values = run.get("series", {}).get(args["series"])
    if not values:
        return None
    return stats.percentile(values, float(args["q"]))
