"""95th percentile of the admission-queue wait (due -> prefill dispatch)
over the window's served requests, from ``RequestResult.queue_time_s``."""
import numpy as np


def read(ctx):
    q = [r.queue_time_s for r in ctx.results if r.status == "ok"]
    return 1e3 * float(np.percentile(q, 95)) if q else None
