"""XLA backend compiles (``jax.monitoring``) inside the traced window;
the programs' names are in the result's ``window.compiled_in_window``."""


def read(run):
    return run.facts.get("compiles_in_window")
