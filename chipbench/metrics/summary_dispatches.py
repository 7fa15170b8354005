"""summary_dispatches: the summary engine's jitted dispatches per window
round (``BatchStats.dispatches``)."""


def read(obs):
    return obs["dispatches"] / obs["rounds"] if obs["rounds"] else None
