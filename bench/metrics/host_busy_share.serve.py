"""Share of the traced window in which the serving engine's host loop was
busy: the union of its ``serve.admit``, ``serve.prefill``, ``serve.decode``
and ``serve.emit`` spans (not ``serve.sync``, the wait for the device, nor
``serve.idle``) over the window."""
from harness import trace as tr

BUSY = ("serve.admit", "serve.prefill", "serve.decode", "serve.emit")


def read(ctx):
    spans = [(e.start_ns, e.end_ns) for e in ctx.events
             if e.plane.startswith("/host:") and e.name in BUSY]
    if not spans or not ctx.window_s:
        return None
    busy = sum(e - s for s, e in tr.union_ns(spans))
    return 100.0 * busy / (ctx.window_s * 1e9)
