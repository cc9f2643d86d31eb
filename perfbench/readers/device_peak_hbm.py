"""`memory_stats()["peak_bytes_in_use"]` of the fullest chip, read after
the window and before the reference runs."""


def read(run, args):
    peak = run.get("memory_peak_bytes")
    return float(peak) if peak else None
