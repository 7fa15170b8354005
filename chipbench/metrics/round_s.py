"""round_s: the window's seconds over the rounds completed in it."""


def read(obs):
    return obs["window_s"] / obs["rounds"] if obs["rounds"] else None
