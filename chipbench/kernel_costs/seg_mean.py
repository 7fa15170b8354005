"""``seg_mean``: the per-label mean of feature rows [R, H] into label
slots.  One add per feature element, R * H operations; the label-offset
one-hot a kernel may build is not counted, so the share reads the same
work whatever implements it."""
import math


def ops(result, operands):
    _dtype, feats = operands[0]
    return math.prod(feats)
