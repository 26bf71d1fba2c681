"""Model FLOPs of every token decoded in the traced window (this chip's
layers, attention over each token's context, and the exact-equivalent
head 2·V·d), over the window's length times the chip's bf16 peak."""
from harness import counts


def read(ctx):
    if ctx.peaks is None or not ctx.window_s:
        return None
    flops = sum(counts.decode_token_flops(ctx.m, c)
                for c in counts.decoded_contexts(ctx.results))
    return 100.0 * flops / (ctx.window_s * ctx.peaks["bf16_flops"])
