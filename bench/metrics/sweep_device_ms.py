"""Device time per call of the compiled sweep (``core/jaxsim.py``
``_sweep``; a decision or a sweep, as the mix calls it): the mean duration
of its program's executions in the traced window, in ms."""

from bench.xtrace import module_mean_ms


def read(ctx):
    return module_mean_ms(ctx.devices[0], "jit__sweep")
