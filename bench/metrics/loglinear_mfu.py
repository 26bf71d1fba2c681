"""The whole query's share of the chip's bf16 peak: the nominal work per
θ (centroid scores, the probed clusters' members at n / n_clusters rows
each, and the k + l rows Algorithms 2 and 3 score), times the θ processed
per second in the traced window, over the peak."""
from harness import counts


def read(ctx):
    if ctx.peaks is None or not ctx.theta_per_s:
        return None
    g = ctx.geometry
    flops = counts.loglinear_theta_flops(g["n"], g["d"], g["n_clusters"],
                                         g["n_probe"], g["k"], g["l"])
    return 100.0 * flops * ctx.theta_per_s / ctx.peaks["bf16_flops"]
