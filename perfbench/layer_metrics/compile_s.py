"""Seconds of XLA compilation (or of loading from the persistent cache)
during set-up, summed over ``jax.monitoring``'s backend-compile events."""


def read(artifacts):
    return artifacts["setup"].get("compile_s")
