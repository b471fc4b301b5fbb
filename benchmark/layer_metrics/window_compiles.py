"""First calls of a device program inside the window (devmon's
``jit_compiles``). Anything but 0 means the warm-up missed a shape."""


def read(ctx, variant=None):
    if "devmon.jit_compiles" not in ctx.delta:
        return None
    return ctx.delta["devmon.jit_compiles"]
