"""Profiler harness: jax.profiler traces + MFU reporting.

TPU analog of the reference's tracing stack (SURVEY.md §5.1: PerfCounters
+ LTTng/Blkin spans): ``trace()`` wraps ``jax.profiler.trace`` (Perfetto/
TensorBoard-readable) around a benchmark region. A profiler that will
not start is an error: a caller that asked for a trace must not get a
silent run without one. MFU numbers come from ceph_tpu.utils.roofline
and are embedded in every benchmark record, not here.
"""

from __future__ import annotations

import contextlib

from ceph_tpu.utils.logging import get_logger

log = get_logger("prof")


@contextlib.contextmanager
def trace(log_dir: str | None):
    """Profile the enclosed region into log_dir (None = no-op).

    View with TensorBoard or ui.perfetto.dev. Only the process that
    holds the chip can trace it.
    """
    if not log_dir:
        yield
        return
    import jax

    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        log.dout(1, "profile written", dir=log_dir)


def annotate(name: str):
    """Named sub-region (TraceAnnotation) for kernel attribution."""
    import jax

    return jax.profiler.TraceAnnotation(name)
