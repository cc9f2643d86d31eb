"""Device seconds of the operations that match `op` (`Reduced.op_seconds`:
anchor the pattern at the key, as `^tpu_custom_call:flash_fwd_`), over the
device's busy seconds in the traced window, in percent: what part of the
chip's working time one kernel takes. Both sides hold the executions that
the window's edges cut, so a window of three and two half steps reads as
one of four would."""


def read(run, args):
    red = run.get("trace")
    if red is None or red.busy_s <= 0:
        return None
    seconds = red.op_seconds(args["op"])
    return 100.0 * seconds / red.busy_s if seconds else None
