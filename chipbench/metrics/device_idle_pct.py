"""device_idle_pct: 100 * (1 - busy / window) over the traced part of the
window, busy being the union of the device's op intervals."""


def read(obs):
    t = obs["trace"]
    if t is None or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
