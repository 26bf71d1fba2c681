"""Device time of one execution of the prefill program (``jit_prefill_step``,
the batched prefill that admits requests), averaged over the trace."""
from harness import trace as tr


def read(ctx):
    ev = tr.module_events(ctx.events, "jit_prefill_step")
    return sum(e.dur_ns for e in ev) / len(ev) / 1e6 if ev else None
