"""Device time of one execution of the estimator program
(``jit_loglinear_estimate``: Algorithm 3's log Z-hat and Algorithm 2's
sample for a batch of θ), averaged over the traced window."""
from harness import trace as tr


def read(ctx):
    ev = tr.module_events(ctx.events, "jit_loglinear_estimate")
    return sum(e.dur_ns for e in ev) / len(ev) / 1e6 if ev else None
