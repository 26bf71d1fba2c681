"""Device self time of the trunk's attention (ops under the ``attention``
scope: each layer's norm, projections, KV cache write, layout copies and
``flash_decode``) in the decode program (``jit_decode_step``), per decoded
position, normalised like ``decode_step_ms``."""
from harness import scopes


def read(ctx):
    return scopes.per_execution_ms(ctx, "jit_decode_step",
                                   ("attention",), per=ctx.scfg.decode_window)
