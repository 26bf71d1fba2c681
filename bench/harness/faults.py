"""Faults planted under the timed path, each of which a cell's comparison
has to catch (``correct`` false). The CPU tests plant them in tiny cells;
``bench/calibrate.py --fault <name>`` reads them on the chip at a cell's
own size, where a fault's reading is the upper end of a limit.

Each is a context manager that patches the program's module attributes
the runners look up when they build their programs, and restores them.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp


@contextlib.contextmanager
def _patched(module, name: str, wrap):
    real = getattr(module, name)
    setattr(module, name, wrap(real))
    try:
        yield
    finally:
        setattr(module, name, real)


def argmax_sampler():
    """Algorithm 2 returns the top-k's best id, unperturbed: a sampler
    that always answers the mode."""
    import repro.core as core

    def wrap(real):
        def sample(key, topk, n, score_fn, **kw):
            res = real(key, topk, n, score_fn, **kw)
            best = topk.ids[jnp.argmax(topk.values)].astype(jnp.int32)
            return res._replace(index=best)
        return sample
    return _patched(core, "sample_fixed_b", wrap)


def dropped_tail():
    """Algorithm 3 leaves out the tail's (n - k)/l-weighted sum: log Ẑ is
    the log-sum-exp of the top-k's scores alone."""
    import repro.core as core

    def wrap(real):
        def estimate(key, topk, n, score_fn, **kw):
            pe = real(key, topk, n, score_fn, **kw)
            y_s = score_fn(topk.ids.astype(jnp.int32)).astype(jnp.float32)
            return pe._replace(log_z=jax.nn.logsumexp(y_s))
        return estimate
    return _patched(core, "partition_estimate", wrap)


def altered_log_z():
    """log Ẑ altered where it is produced (off by one)."""
    import repro.core as core

    def wrap(real):
        def estimate(*a, **k):
            pe = real(*a, **k)
            return pe._replace(log_z=pe.log_z + jnp.float32(1.0))
        return estimate
    return _patched(core, "partition_estimate", wrap)


def altered_tokens():
    """The decode loop's emitted tokens altered where they are produced."""
    from repro.launch import steps

    def wrap(make):
        def made(*a, **k):
            core = make(*a, **k)

            def loop(*args):
                cache, state, toks, oks, emitted, widths = core(*args)
                return cache, state, (toks + 1) % 64, oks, emitted, widths
            return loop
        return made
    return _patched(steps, "make_decode_loop_step", wrap)


def stale_cache():
    """A decode step that returns the cache it was given, unchanged."""
    from repro.launch import steps

    def wrap(make):
        def made(*a, **k):
            core = make(*a, **k)

            def loop(params, cache, *rest):
                _, state, toks, oks, emitted, widths = core(params, cache,
                                                            *rest)
                return cache, state, toks, oks, emitted, widths
            return loop
        return made
    return _patched(steps, "make_decode_loop_step", wrap)


FAULTS = {f.__name__: f for f in (argmax_sampler, dropped_tail,
                                  altered_log_z, altered_tokens, stale_cache)}
