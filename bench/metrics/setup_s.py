"""Seconds from process start to the window's opening: data, pipeline runs,
slab uploads, warm-up and, in a first run, compiles (host clock)."""


def read(ctx):
    return ctx.setup_s
