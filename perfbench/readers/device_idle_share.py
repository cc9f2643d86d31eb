"""One minus the union of the device's operation intervals over the traced
window, a mean over the chips used."""


def read(run, args):
    red = run.get("trace")
    if red is None or red.window_s <= 0 or red.busy_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
