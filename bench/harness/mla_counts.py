"""Operations and bytes of a decoder of DeepSeek-V3's block (latent
attention, expert layers) at decode, from shapes: the yardsticks of
``mla_decode_roofline`` and ``decode_step_roofline.mla``. Like
``counts.py``, they count the work the algorithm needs, never more.

Attention (:func:`mla_decode_work`), per decoded token and layer, over
its context of ``ctx`` positions: the latent rows ``[c_kv | k_pe]``
(``r + rope`` values) read once, as keys and, their first ``r`` columns,
as values; the query (``h`` heads of ``r + rope``, in the cache's dtype)
read and the latent output (``h`` heads of ``r``, float32) written;
``2·h·ctx·(r + rope)`` FLOPs for the scores and ``2·h·ctx·r`` for the
weighted sum.

The whole decode step (:func:`decode_step_work`): every weight the step
multiplies by, read once a position (:func:`decode_weight_bytes`), the
live latent cache and each token's new latent row, and the tokens'
embedding rows; the FLOPs of the matmuls each token passes through,
attention over its context and the exact head.
"""
from __future__ import annotations


def mla_decode_work(m: dict, ctx_sum: int, tokens: int,
                    itemsize: int = 2) -> tuple[float, float]:
    """(flops, bytes) over all layers for ``tokens`` decoded tokens whose
    contexts sum to ``ctx_sum``; ``m`` holds the reference's ``dims``."""
    lat = m["r"] + m["rope"]
    flops = 2 * m["h"] * ctx_sum * (lat + m["r"])
    bytes_ = (ctx_sum * lat * itemsize
              + tokens * m["h"] * (lat * itemsize + m["r"] * 4))
    return m["layers"] * flops, m["layers"] * bytes_


def _attn_params(m: dict) -> int:
    """W_Q, W_DKV, W_UKV and W_O of one layer."""
    d, h, r = m["d"], m["h"], m["r"]
    return (d * h * (m["nope"] + m["rope"]) + d * (r + m["rope"])
            + r * h * (m["nope"] + m["vd"]) + h * m["vd"] * d)


def decode_weight_bytes(m: dict, itemsize: int = 2,
                        head_itemsize: int = 4) -> int:
    """Bytes of every weight one decode position reads: each layer's
    attention and norms (float32), the dense layers' MLP, each expert
    layer's router (and its float32 selection bias), this chip's held
    experts and the shared expert, the final norm and the output head's
    table (the exact top-k scores every row). Every held expert is
    counted: at a few dozen live tokens, each choosing ``topk`` of
    ``experts``, nearly every held expert receives one. The input
    embedding is not here: a position reads only its tokens' rows."""
    d = m["d"]
    norms = 4 * (m["r"] + 2 * d)
    attn = m["layers"] * (itemsize * _attn_params(m) + norms)
    dense = m["dense_layers"] * itemsize * 3 * d * m["f"]
    expert = (itemsize * (d * m["experts"]
                          + 3 * d * m["ef"] * m["held"]
                          + 3 * d * m["shared_f"])
              + 4 * m["experts"])
    return (attn + dense + m["moe_layers"] * expert + 4 * d
            + head_itemsize * m["v"] * d)


def decode_step_work(m: dict, ctx_sum: int, tokens: int, positions: int,
                     itemsize: int = 2,
                     head_itemsize: int = 4) -> tuple[float, float]:
    """(flops, bytes) of ``positions`` decode positions that decode
    ``tokens`` tokens whose contexts sum to ``ctx_sum``. A token's expert
    FLOPs count the held experts it is expected to choose, ``topk ·
    held / experts`` of them."""
    d = m["d"]
    lat = m["r"] + m["rope"]
    bytes_ = (positions * decode_weight_bytes(m, itemsize, head_itemsize)
              + m["layers"] * (ctx_sum + tokens) * lat * itemsize
              + tokens * d * itemsize)
    chosen = m["topk"] * m["held"] / m["experts"]
    per_token = (m["layers"] * _attn_params(m)
                 + m["dense_layers"] * 3 * d * m["f"]
                 + m["moe_layers"] * (d * m["experts"]
                                      + 3 * d * m["ef"] * chosen
                                      + 3 * d * m["shared_f"])
                 + m["v"] * d)
    attn_flops, _ = mla_decode_work(m, ctx_sum, tokens, itemsize)
    return 2 * tokens * per_token + attn_flops, bytes_
