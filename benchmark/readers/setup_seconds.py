"""Process start to the go signal: JAX init, native modules, cluster,
load, flush, warm-up. The reference's check is after the window and is
not in it."""


def read(args: dict, ctx: dict):
    return ctx["setup_s"]
