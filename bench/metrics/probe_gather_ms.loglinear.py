"""Device self time of the probe's member gather and scores (ops under the
``ivf.members`` scope) per execution of the top-k probe program
(``jit_loglinear_probe``)."""
from harness import scopes


def read(ctx):
    return scopes.per_execution_ms(ctx, "jit_loglinear_probe",
                                   ("ivf.members",))
