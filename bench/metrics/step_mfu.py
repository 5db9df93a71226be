"""The whole step's share of the chip's peak: model FLOPs of the traced
steps over the traced stretch's length times the peak of the device."""


def read(ctx):
    if ctx.steps <= 0 or ctx.window_s <= 0:
        return None
    return 100.0 * ctx.step_flops * ctx.steps / (ctx.window_s *
                                                 ctx.peak_flops)
