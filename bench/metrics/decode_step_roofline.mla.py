"""Roofline share of the whole decode step of a latent-attention expert
decoder (``jit_decode_step``): the least time the chip needs for the
step's weights read once a position, the live latent cache and the
FLOPs of every decoded token (``harness/mla_counts.decode_step_work``,
the trunk in bfloat16 and the head in float32, as the cell holds them),
over the decode program's device time in the trace."""
from harness import counts, mla_counts, peaks, trace as tr


def read(ctx):
    if ctx.peaks is None:
        return None
    ev = tr.module_events(ctx.events, "jit_decode_step")
    if not ev:
        return None
    c = counts.decoded_contexts(ctx.results)
    positions = len(ev) * ctx.scfg.decode_window
    flops, bytes_ = mla_counts.decode_step_work(ctx.m, sum(c), len(c),
                                                positions)
    t = sum(e.dur_ns for e in ev) / 1e9
    return 100.0 * peaks.least_time_s(flops, bytes_, ctx.peaks) / t
