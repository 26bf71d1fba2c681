"""Operations and bytes of each kernel and program, from shapes.

These are the yardstick of every roofline and utilization share: they
count the work the algorithm needs for a call, never more, so a share
read against them cannot pass 100% unless a time leaves out work. Each
function states what it counts.
"""
from __future__ import annotations

import math


# ------------------------------------------------------------ decoder model
def layer_matmul_params(d: int, f: int, h: int, kv: int, hd: int) -> int:
    """Weights one Llama-style layer multiplies each token by."""
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f


def decode_token_flops(m: dict, ctx: int) -> float:
    """Model FLOPs of one decoded token at context ``ctx`` (positions it
    attends to): the layers' matmuls, attention over the context (QK and
    PV), and the exact-equivalent head 2·V·d."""
    lp = layer_matmul_params(m["d"], m["f"], m["h"], m["kv"], m["hd"])
    per_layer = 2 * lp + 4 * m["h"] * m["hd"] * ctx
    return m["layers"] * per_layer + 2 * m["v"] * m["d"]


def decoded_contexts(results) -> list[int]:
    """Context of every decoded token of the served requests. A request's
    first token comes from prefill; its j-th token after that (j >= 1) is
    decoded with the prompt and j earlier tokens in its cache, so it
    attends to ``prompt_len + j`` positions."""
    out = []
    for r in results:
        if r.status == "ok":
            out.extend(r.prompt_len + j for j in range(1, len(r.tokens)))
    return out


def flash_decode_work(m: dict, ctx_sum: int, tokens: int,
                      kv_itemsize: int = 2) -> tuple[float, float]:
    """(flops, bytes) that decode attention needs over all layers for
    ``tokens`` decoded tokens whose contexts sum to ``ctx_sum``: each
    token reads its own context's K and V once, reads its query and
    writes its float32 output."""
    kv_pos = 2 * m["kv"] * m["hd"] * kv_itemsize
    flops = 4 * m["h"] * m["hd"] * ctx_sum
    bytes_ = ctx_sum * kv_pos + tokens * m["h"] * m["hd"] * (kv_itemsize + 4)
    return m["layers"] * flops, m["layers"] * bytes_


# ------------------------------------------------ paper's log-linear query
def loglinear_theta_flops(n: int, d: int, n_clusters: int, n_probe: int,
                          k: int, l: int) -> float:
    """Nominal work per θ: centroid scores, the probed clusters' members
    (⌈n / n_clusters⌉ each), and the k + l rows Algorithms 2 and 3 score."""
    return 2.0 * d * (n_clusters + n_probe * math.ceil(n / n_clusters)
                      + k + l)
