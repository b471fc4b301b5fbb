"""Readback-anchored device timing.

Why this exists: round 1 timed a wall-clock loop around a
``block_until_ready()`` that, on the platform of that time, returned once
the *dispatch* was acknowledged — it timed an enqueue, not the work, and
shipped a 1242x-impossible number. An anchor that cannot lie is data
dependency: make the host read back a value that cannot exist until every
step has run.

On a local chip ``block_until_ready`` does wait for execution (PR 22,
``chip_smoke.py``'s ``kernels`` phase: one warm 8+3 encode launch took
3.34 ms to ready against a 2.45 ms slope step — the difference is the
dispatch floor the slope subtracts). The method stays for what it still
buys: the constant dispatch + readback term cancels, and steps cannot
overlap.

Methodology (used by every benchmark in this repo):

1. The timed region is ONE jitted program: ``lax.fori_loop`` over S steps,
   where each step's input depends on the previous step's *full* output
   (the caller's ``step`` folds an xor-reduction of its output back into
   its carry — full, so XLA cannot dead-code-eliminate any lane).
2. The program returns a scalar derived from the final carry; the host
   timer stops only after ``np.asarray`` of that scalar — an RPC readback
   that cannot complete before execution.
3. Per-step time is the SLOPE between two step counts S_lo and S_hi:
   ``(t(S_hi) - t(S_lo)) / (S_hi - S_lo)``. The constant term (RPC floor,
   dispatch, readback, the once-per-call reduction) cancels; it is also
   reported as ``overhead_s`` so the reader can see the floor being
   subtracted (~2 ms per call on a local v5e, PR 22).

ref: replaces the wall-clock loop of
src/test/erasure-code/ceph_erasure_code_benchmark.cc (ErasureCodeBench::run),
which is sound for synchronous single-process C++ but not for an
asynchronous device.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable

import numpy as np

import jax


@dataclass
class ChainedTiming:
    seconds_per_step: float
    overhead_s: float            # constant term: dispatch + readback + anchor
    steps: tuple[int, int]
    totals_s: dict[int, float]   # best-of-reps total wall time per step count
    reps: int
    anchor_value: int = 0        # the scalar actually read back (proof of life)
    method: str = "chained_fori_loop_slope_readback"
    steps_executed: int = 0      # total device steps run incl. warmup
    timed_region_s: float = 0.0  # wall time of the timed (best-of) calls

    def as_dict(self) -> dict[str, Any]:
        return {
            "seconds_per_step": self.seconds_per_step,
            "overhead_s": round(self.overhead_s, 6),
            "slope_steps": list(self.steps),
            "totals_s": {str(k): round(v, 6) for k, v in self.totals_s.items()},
            "reps": self.reps,
            "steps_executed": self.steps_executed,
            "method": self.method,
        }


def xor_anchor(x: jax.Array) -> jax.Array:
    """Reduce an array to one scalar via xor — cheap, order-independent,
    consumes every lane (nothing upstream can be eliminated)."""
    import jax.numpy as jnp

    flat = x.reshape(-1)
    if flat.dtype == jnp.uint8:
        return jax.lax.reduce(flat, np.uint8(0), jax.lax.bitwise_xor, (0,))
    i32 = flat.astype(jnp.int32)
    return jax.lax.reduce(i32, np.int32(0), jax.lax.bitwise_xor, (0,))


def measure_chained(step: Callable[[Any], Any], carry0: Any,
                    anchor: Callable[[Any], jax.Array],
                    *, steps: tuple[int, int] = (2, 10),
                    reps: int = 3) -> ChainedTiming:
    """Time ``step`` (carry -> carry) with the chained-slope method.

    ``step`` MUST thread a dependency on its full previous output through
    the carry (see module docstring); ``anchor`` maps the final carry to a
    scalar that transitively depends on every step.
    """
    lo, hi = steps
    if not (0 < lo < hi):
        raise ValueError(f"need 0 < lo < hi, got {steps}")

    def make(n: int):
        @jax.jit
        def loop(carry):
            out = jax.lax.fori_loop(0, n, lambda i, c: step(c), carry)
            return anchor(out)
        return loop

    loops = {n: make(n) for n in (lo, hi)}
    value = 0
    executed = 0
    region = 0.0
    for n in (lo, hi):                      # compile + warm
        value = int(np.asarray(loops[n](carry0)))
        executed += n
    totals: dict[int, float] = {}
    for n in (lo, hi):
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            r = loops[n](carry0)
            value = int(np.asarray(r))      # readback anchor
            dt = time.perf_counter() - t0
            best = min(best, dt)
            executed += n
            region += dt
        totals[n] = best
    per_step = (totals[hi] - totals[lo]) / (hi - lo)
    if per_step <= 0:
        # Timer noise swamped the slope (tiny workload): fall back to the
        # hi-count total divided by steps — still readback-anchored, just
        # without floor subtraction (reported method says so).
        return ChainedTiming(totals[hi] / hi, 0.0, (lo, hi), totals, reps,
                             value, "chained_fori_loop_total_readback",
                             executed, region)
    overhead = totals[lo] - lo * per_step
    return ChainedTiming(per_step, overhead, (lo, hi), totals, reps, value,
                         steps_executed=executed, timed_region_s=region)


