"""Model FLOPs of the images completed in the window (2 x multiply-adds of
every conv and dense layer), over window x chips x the chip's bf16 peak,
in percent.  Read in a traced run, so it sits beside the kernels'
rooflines it bounds."""
from bench import flops


def read(ctx):
    if ctx.peaks is None or ctx.reduced is None:
        return None
    done = sum(1 for s in ctx.requests
               if s.ok and s.t_done is not None
               and ctx.t_open <= s.t_done <= ctx.t_close)
    work = flops.model_flops_per_image(ctx.net) * done
    return 100.0 * work / (ctx.seconds * ctx.chips
                           * ctx.peaks.flops_for(ctx.operand))
