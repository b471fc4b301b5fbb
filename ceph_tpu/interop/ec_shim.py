"""Python half of the ``libec_jax.so`` reverse shim.

The forward bridge (``interop.native``) lets Python call the C++ EC
runtime; this module is the opposite direction — the native plugin
registry dlopens ``libec_jax.so`` (built from
``native/ec/plugin_jax_shim.cc``), which embeds a CPython interpreter
and calls these functions, so the native ``ec_bench`` harness can drive
the flagship TPU plugin through the exact ``__erasure_code_init``
contract every other plugin uses (ref: the role of
src/erasure-code/ErasureCodePlugin.cc __erasure_code_init; SURVEY.md §7
step 6).

Buffers cross the boundary as memoryviews over the caller's chunk
arrays — no copies on input; one ndarray assignment on output.

Platform: the embedded interpreter imports this module before touching
jax, and the first thing it does is pin ``jax_platforms`` (default
``cpu``; override with CEPH_TPU_SHIM_PLATFORM=tpu to let the native
harness drive the real chip). The embedded interpreter is a second
process: a chip belongs to one process at a time, so unless told
otherwise ec_bench keeps off it.
"""

from __future__ import annotations

import os


def _pin_platform() -> None:
    # Only pin when WE are the embedded interpreter (plugin_jax_shim.cc
    # sets the marker just before importing this module, and only when
    # it called Py_Initialize itself). A host Python process that loads
    # the shim in-process keeps its own platform choice.
    if os.environ.get("CEPH_TPU_EMBEDDED_SHIM") != "1":
        return
    import jax
    try:
        jax.config.update(
            "jax_platforms", os.environ.get("CEPH_TPU_SHIM_PLATFORM", "cpu"))
    except Exception:
        pass  # backends already initialized — keep whatever is live


_pin_platform()


def create(profile: str):
    """profile "k=8 m=3 technique=..." -> ErasureCodeInterface instance."""
    from ceph_tpu.ec.registry import factory
    prof = profile.strip() or "k=2 m=2"
    if "plugin=" not in prof:
        prof = "plugin=jax " + prof
    return factory(prof)


def encode(h, data_mv, parity_mv, chunk: int) -> int:
    import numpy as np
    data = np.frombuffer(data_mv, dtype=np.uint8).reshape(h.k, chunk)
    parity = h.encode_chunks(data)
    np.frombuffer(parity_mv, dtype=np.uint8).reshape(h.m, chunk)[:] = parity
    return 0


def decode(h, avail, want, chunks_mv, out_mv, chunk: int) -> int:
    import numpy as np
    chunks = np.frombuffer(chunks_mv, dtype=np.uint8).reshape(
        len(avail), chunk)
    got = h.decode_chunks(list(want),
                          {a: chunks[i] for i, a in enumerate(avail)})
    out = np.frombuffer(out_mv, dtype=np.uint8).reshape(len(want), chunk)
    for i, w in enumerate(want):
        out[i] = got[w]
    return 0
