"""95th percentile of the time to first token over every request scheduled
in the window, in ms: from when a request was due to when its first token
reached the client through ``on_tokens`` (host clock).  A request that
never delivered a token counts as waiting until the run stopped following
it.  The same quantity the end-to-end ``ttft_p95_ms`` would be; read here,
in the traced run, because a p95 over one window's hundred requests swings
too far from seed to seed to hold a bound (PERF.md)."""
import numpy as np


def read(ctx):
    if not ctx.ttft_s:
        return None
    return float(np.percentile(np.asarray(ctx.ttft_s, float), 95)) * 1e3
