"""Share of the traced window in which no operation ran on the device, in
percent: 1 - (union of the device's op intervals / window), over the chips
the cell uses."""


def read(ctx):
    busy = sum(d.busy_s() for d in ctx.devices) / len(ctx.devices)
    return 100.0 * (1.0 - busy / ctx.devices[0].window_s)
