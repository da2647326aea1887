"""setup_s: seconds from process start to the end of warm-up (imports,
device start, input generation, compilation or compile-cache loads)."""


def read(ctx):
    return ctx.setup_s
