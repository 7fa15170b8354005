"""setup_s: seconds from the process's start to the window's: imports,
JAX's start, traffic and data from the seed, the server, round 0 over the
whole fleet, the warm rounds and the warm-up of every summary shape."""


def read(obs):
    return obs["setup_s"]
