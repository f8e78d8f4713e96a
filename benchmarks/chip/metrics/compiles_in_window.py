"""Engine step loop: executables JAX compiled, or loaded from its
persistent cache, while the window ran (its monitoring's backend-compile
events).  Warm-up compiles every shape the window meets, so any count here
is a shape the warm-up did not foresee."""


def read(run):
    return float(len(run.compiles))
