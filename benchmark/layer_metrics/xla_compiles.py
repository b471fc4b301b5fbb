"""Backend compiles inside the window that the persistent compile
cache did not serve, from ``utils/devmon``'s ``jax.monitoring``
listener. Anything but 0 means the warm-up missed a program. A compile
counts for the variant (``ec``, ``crush``) whose ``jit_call`` it
happened under, and for both where it happened under none."""


def read(ctx, variant=None):
    try:
        from ceph_tpu.utils.devmon import compile_events, devmon
    except ImportError:                  # a program from before it
        return None
    devmon()                             # the listener is the singleton's
    if ctx.setup_s is None or ctx.window_s is None:
        return None
    t0 = (ctx.t_start + ctx.setup_s) * 1e9
    t1 = t0 + ctx.window_s * 1e9
    setup, window = [], []
    for at_ns, fun, seconds, cached, program in compile_events():
        family = program.partition("_")[0]
        if cached or family not in (variant, "other"):
            continue
        (window if t0 <= at_ns <= t1 else setup).append(
            f"{program}:{fun}:{seconds:.2f}s")
    ctx.log(f"xla_compiles.{variant}: {len(window)} uncached in the "
            f"window {window[:8]}, {len(setup)} outside it {setup[:8]}")
    return len(window)
