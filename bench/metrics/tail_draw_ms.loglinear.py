"""Device self time of the tail draws (ops under the ``tail.draw`` scope:
sorting S, the uniform complement draws, Algorithm 2's Poisson count and
heights) per execution of the estimator program
(``jit_loglinear_estimate``)."""
from harness import scopes


def read(ctx):
    return scopes.per_execution_ms(ctx, "jit_loglinear_estimate",
                                   ("tail.draw",))
