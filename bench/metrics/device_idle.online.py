"""Share of the traced window in which no op ran on the device, in %:
1 - (union of the device's op intervals) / window."""


def read(ctx):
    return None if ctx.trace is None else ctx.idle_pct()
