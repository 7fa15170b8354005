"""``pairwise_dist``: squared distances of rows [N, D] to centroids
[K, D], a multiply and an add per (row, centroid, dimension): 2 N K D
operations."""


def ops(result, operands):
    (_, (n, d)), (_, (k, _d)) = operands[:2]
    return 2 * n * k * d
