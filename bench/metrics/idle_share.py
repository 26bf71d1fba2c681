"""Share of the traced window in which no operation ran on the device:
1 - busy / window, busy being the union of op intervals. One reader for
every cell kind: ``idle_share.serve`` and ``idle_share.loglinear`` are
this metric split by the end-to-end metric it moves."""
from harness import trace as tr


def read(ctx):
    if not tr.device_planes(ctx.events) or not ctx.window_s:
        return None
    return 100.0 * (1.0 - tr.busy_s(ctx.events) / ctx.window_s)
