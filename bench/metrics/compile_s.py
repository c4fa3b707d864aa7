"""compile_s: seconds JAX spent compiling or loading programs from the
persistent cache before the window opened (``jax.monitoring``
backend-compile durations).  Moves setup_s."""


def read(run):
    return run.compile_setup_s
