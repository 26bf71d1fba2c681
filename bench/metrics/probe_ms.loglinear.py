"""Device time of one execution of the top-k probe program
(``jit_loglinear_probe``: centroid scores, the probed clusters' members,
top-k selection), averaged over the traced window."""
from harness import trace as tr


def read(ctx):
    ev = tr.module_events(ctx.events, "jit_loglinear_probe")
    return sum(e.dur_ns for e in ev) / len(ev) / 1e6 if ev else None
