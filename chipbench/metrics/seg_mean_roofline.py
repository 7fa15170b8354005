"""seg_mean_roofline: the seg_mean kernel's share of its roofline in the traced
part of the window (chipbench/roofline.py, kernel_costs/seg_mean.py).
Nothing when the trace holds no call of it."""
from chipbench.roofline import kernel_share


def read(obs):
    return kernel_share(obs, "seg_mean")
