"""Share of the traced window in which the engine was admitting requests,
in %: the union of the program's ``engine.admit`` spans over the window.
While an admit runs, decoding waits on prompt processing.  The spans are
the program's own step spans, which it writes into the profiler trace on
the device ops' clock; None where the trace holds none (no ``engine.step``
span), as with a program that writes no step spans."""
from bench import trace as T

STEP = "engine.step"
ADMIT = "engine.admit"


def read(ctx):
    if ctx.trace is None or ctx.hi <= ctx.lo:
        return None
    host = ctx.trace.host
    if not any(e.name == STEP for e in host):
        return None
    admits = [e for e in host if e.name == ADMIT]
    return 100.0 * T.busy_ns(admits, ctx.lo, ctx.hi) / (ctx.hi - ctx.lo)
