"""Roofline share of the decode attention kernel (``flash_decode`` on
MLA's latent cache, ``models/attention.mla_decode``): the least time the
chip needs for the latent bytes and FLOPs of every decoded token's own
context (``harness/mla_counts.py``), over the kernel's device time in the
trace."""
from harness import counts, mla_counts, peaks, trace as tr

KERNEL = "flash_decode"


def read(ctx):
    if ctx.peaks is None:
        return None
    ev = tr.op_events(ctx.events, tr.kernel_match(KERNEL))
    if not ev:
        return None
    c = counts.decoded_contexts(ctx.results)
    flops, bytes_ = mla_counts.mla_decode_work(ctx.m, sum(c), len(c))
    t = sum(e.dur_ns for e in ev) / 1e9
    return 100.0 * peaks.least_time_s(flops, bytes_, ctx.peaks) / t
