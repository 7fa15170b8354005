"""window_compiles: backend compiles (persistent-cache hits included) inside
the window, from JAX's monitoring events; there should be none."""


def read(obs):
    return obs["window_compiles"]
