"""Device self time of the expert layers (ops under the ``moe.route``,
``moe.dispatch``, ``moe.experts`` and ``moe.shared`` scopes of
``models/moe.py``) in the decode program (``jit_decode_step``), per
decoded position, normalised like ``decode_step_ms``."""
from harness import scopes

SCOPES = ("moe.route", "moe.dispatch", "moe.experts", "moe.shared")


def read(ctx):
    return scopes.per_execution_ms(ctx, "jit_decode_step", SCOPES,
                                   per=ctx.scfg.decode_window)
