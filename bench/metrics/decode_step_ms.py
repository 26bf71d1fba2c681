"""Device time of the decode program (``jit_decode_step``) per decoded
position: its executions' total time over executions × tokens per
dispatch (every live slot decodes one token per position)."""
from harness import trace as tr


def read(ctx):
    ev = tr.module_events(ctx.events, "jit_decode_step")
    if not ev:
        return None
    return sum(e.dur_ns for e in ev) / (len(ev) * ctx.scfg.decode_window) / 1e6
