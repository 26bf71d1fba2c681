"""Share of decode slots that produced a token: tokens decoded (all
served tokens less each request's first, which prefill samples) over
decode dispatches × slots × tokens per dispatch, from ``Server.stats``."""


def read(ctx):
    st, s = ctx.stats, ctx.scfg
    slots = st["decode_dispatches"] * s.batch_slots * s.decode_window
    if not slots:
        return None
    admitted = sum(1 for r in ctx.results if r.status == "ok" and r.tokens)
    return 100.0 * (st["tokens"] - admitted) / slots
