"""XLA compile seconds of the run's set-up, persistent-cache reads
included (``jax.monitoring`` backend-compile events)."""


def read(r):
    return r["compile"]["seconds"]
