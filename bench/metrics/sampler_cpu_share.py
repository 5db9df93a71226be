"""CPU time of the sampler's thread (``SamplingProfiler.cpu_seconds``)
over the window, as a share of the window's length.  The whole window and
not the traced stretch: the thread's CPU clock advances in scheduler
ticks."""


def read(ctx):
    if ctx.sampler_cpu_s is None or ctx.sampler_window_s <= 0:
        return None
    return 100.0 * ctx.sampler_cpu_s / ctx.sampler_window_s
