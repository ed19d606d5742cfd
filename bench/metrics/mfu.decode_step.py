"""Model FLOPs the tokens decoded in the traced window require, over the
device time of the decode-loop programs in the trace times the chip's peak
bf16 FLOP/s, in %.  What a token decoded at context c requires is the
cell's block's ``decode_flops`` (for a dense block, 2 x (layer matmul
parameters + head) + attention over c positions)."""

# the engine's jitted K-step decode loop on the paged kernel path, as the
# trace's XLA Modules line names it
PROGRAM = r"jit_paged_loop_fn\("


def read(ctx):
    if ctx.trace is None or not ctx.decode_ctx:
        return None
    busy = ctx.module_seconds(PROGRAM)
    if not busy:
        return None
    flops = sum(ctx.block.decode_flops(ctx.spec, c, ctx.counters)
                for c in ctx.decode_ctx)
    return 100.0 * flops / (busy * ctx.peak["bf16_flops_per_s"])
