"""Compilations inside the measured window, as `jax.monitoring` reports
them. Must read 0: every shape is warmed in set-up."""


def read(run, args):
    return float(run["compile_in_window"])
