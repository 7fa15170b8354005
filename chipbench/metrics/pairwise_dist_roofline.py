"""pairwise_dist_roofline: the pairwise_dist kernel's share of its roofline in the traced
part of the window (chipbench/roofline.py, kernel_costs/pairwise_dist.py).
Nothing when the trace holds no call of it."""
from chipbench.roofline import kernel_share


def read(obs):
    return kernel_share(obs, "pairwise_dist")
