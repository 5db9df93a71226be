"""Mean time of one ``CentralService.process()`` cycle in the traced
stretch."""


def read(ctx):
    cycles = ctx.timings["service_process"]
    if not cycles:
        return None
    return 1e3 * sum(cycles) / len(cycles)
