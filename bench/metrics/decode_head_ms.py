"""Device self time of the amortized head (ops under the ``head`` scope:
the sampler's top-k, tail and certificate) in the decode program
(``jit_decode_step``), per decoded position, normalised like
``decode_step_ms``."""
from harness import scopes


def read(ctx):
    return scopes.per_execution_ms(ctx, "jit_decode_step", ("head",),
                                   per=ctx.scfg.decode_window)
