"""Share of the positions the prefill programs computed that were padding:
100 × (1 − prompt tokens / (batch_slots × bucket, summed over prefill
dispatches)), from ``Server.stats``."""


def read(ctx):
    positions = ctx.stats.get("prefill_positions")
    if not positions:
        return None
    return 100.0 * (1.0 - ctx.stats["prefill_tokens"] / positions)
