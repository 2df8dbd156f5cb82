"""setup_s: seconds from the process's start to the window's opening:
starting JAX, making the weights, building and compiling the engine,
and bringing the traffic to steady state (host clock)."""


def read(run):
    return run.setup_s
