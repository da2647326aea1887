"""device.idle_pct.stream: 1 - (union of the device's busy intervals /
traced window), in %, averaged over the chips used."""


def read(ctx):
    return None if ctx.trace is None else ctx.trace.idle_pct()
