"""One number from the newest trace-time mark called `name` in the
program's span ring, wherever in the run it was made (a mark is made when a
program is traced, in set-up): the arg `num` over the product of the args
`over`, times the product of the args `times`. No ring, no such mark or a
missing arg: nothing."""
from perfbench.readers_spans import snapshot


def read(run, args):
    snap = snapshot(run)
    if snap is None:
        return None
    marks = [s for s in snap.spans if s.name == args["name"]]
    if not marks:
        return None
    got = marks[-1].args
    try:
        value = float(got[args["num"]])
        for k in args.get("over", []):
            value /= float(got[k])
        for k in args.get("times", []):
            value *= float(got[k])
    except (KeyError, ZeroDivisionError):
        return None
    return value
