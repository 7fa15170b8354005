"""round_p95_layer_s: 95th percentile of the window's per-round wall
seconds, over every round of the window.  Per-layer: in the cells whose
windows hold 200 rounds, the slow tail is the online maintainer's
split-merge rounds, which run their full pass or not by the clustering a
seed's data gives, so the seed moves it more than the runs do."""
import numpy as np


def read(obs):
    d = obs["durations"]
    return float(np.percentile(d, 95)) if len(d) else None
