"""The paged-decode kernel's share of its roofline, in %: the least time
the work the decoded tokens require could take (their live-context K and V
plus q and o against HBM bandwidth, or their attention FLOPs against peak,
whichever is larger; the cell's block's ``decode_attn``) over the summed
device time of the kernel's events."""
from bench import work

# the Pallas kernel's custom call (kernels/paged_attention.py), named in the
# trace after its jitted wrapper kernels.ops.paged_decode_attention
KERNEL = r"paged_decode_attention(\.\d+)?$"


def read(ctx):
    if ctx.trace is None or not ctx.decode_ctx:
        return None
    busy = ctx.kernel_seconds(KERNEL)
    if not busy:
        return None
    least = 0.0
    for c in ctx.decode_ctx:
        least += work.least_seconds(
            *ctx.block.decode_attn(ctx.spec, c, ctx.counters), ctx.peak)
    return 100.0 * least / busy
