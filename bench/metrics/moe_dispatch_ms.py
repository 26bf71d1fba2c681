"""Device self time of the expert layers' routing and dispatch alone (ops
under ``moe.route``: sigmoid scores, the biased top-k and the gates; and
``moe.dispatch``: the sort and scatter to the held experts and the
combine), without their matmuls, in the decode program
(``jit_decode_step``) per decoded position."""
from harness import scopes


def read(ctx):
    return scopes.per_execution_ms(ctx, "jit_decode_step",
                                   ("moe.route", "moe.dispatch"),
                                   per=ctx.scfg.decode_window)
