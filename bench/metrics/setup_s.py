"""Process start to the first due request: model build, quantization,
warm-up and, where the cache misses, compilation."""


def read(ctx):
    return ctx.setup_s
