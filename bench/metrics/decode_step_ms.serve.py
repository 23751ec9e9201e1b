"""Device time per decode step (``models/`` decode programs, one per cache
capacity): the mean duration of their executions in the traced window, in
ms."""

from bench.xtrace import module_mean_ms


def read(ctx):
    return module_mean_ms(ctx.devices[0], "jit_serve_decode_")
