"""Backend compiles inside the measured window (jax.monitoring)."""


def reduce(run, spec):
    return run["compiles_in_window"]
