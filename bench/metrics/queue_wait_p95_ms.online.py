"""95th percentile of the engine's own submit-to-admit waits
(``engine.queue_wait_s``, kept by the program), in ms."""


def read(ctx):
    p95_s, count = ctx.queue_wait
    if not count:
        return None
    return p95_s * 1e3
