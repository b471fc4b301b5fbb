#!/usr/bin/env python3
"""Drive a rehearsal run of a cell with the timed path broken
underneath, for test_faults.py: the harness's look for a chip is
skipped (``--rehearsal``), the rest of the run is the real one, and
``correct`` has to come out false.

    python benchmark/tests/faulty_run.py <fault> --workload ... --seed ...
"""

import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))


def sweep_answer_altered():
    """One placement moves from one device to another in what a sweep
    returns."""
    from ceph_tpu.crush.tester import CrushTester
    real = CrushTester.test

    def test(self, *a, **kw):
        res = real(self, *a, **kw)
        counts = res.device_counts.copy()
        hot = int(counts.argmax())
        counts[hot] -= 1
        counts[(hot + 1) % len(counts)] += 1
        res.device_counts = counts
        return res
    CrushTester.test = test


def sweep_half_left_out():
    """Half of the inputs are mapped and the counts doubled."""
    from ceph_tpu.crush.tester import CrushTester
    real = CrushTester.test

    def test(self, rule, num_rep, min_x=0, max_x=1023, **kw):
        half = min_x + (max_x - min_x + 1) // 2 - 1
        res = real(self, rule, num_rep, min_x, half, **kw)
        res.device_counts = res.device_counts * 2
        return res
    CrushTester.test = test


def sweep_no_exchange():
    """The psum that closes a sharded sweep is left out: every device
    keeps its own shard's counts."""
    import jax
    jax.lax.psum = lambda x, axis_name, **kw: x


def write_state_unchanged():
    """One OSD's store takes the transactions of the benchmark's
    objects and changes nothing."""
    from ceph_tpu.os_.objectstore import MemStore
    real = MemStore.queue_transaction
    first = []

    def queue_transaction(self, t):
        if not first:
            first.append(self)
        touches = any("benchmark_data_" in str(part) for op in t.ops
                      for part in op if isinstance(part, str))
        if self is first[0] and touches:
            return
        return real(self, t)
    MemStore.queue_transaction = queue_transaction


def encode_answer_altered():
    """The device program's parity comes back with one byte flipped."""
    import numpy as np
    from ceph_tpu.ec.jax_plugin import ErasureCodeJax
    real = ErasureCodeJax.encode_batch_with_crc

    def encode_batch_with_crc(self, data):
        parity, crcs = real(self, data)
        parity = np.array(parity)
        parity[0, 0, 0] ^= 0x5A
        return parity, crcs
    ErasureCodeJax.encode_batch_with_crc = encode_batch_with_crc


def read_answer_altered():
    """A read returns its bytes with one flipped."""
    from ceph_tpu.rados import IoCtx
    real = IoCtx.read

    async def read(self, oid, *a, **kw):
        data = bytearray(await real(self, oid, *a, **kw))
        if data:
            data[len(data) // 2] ^= 0x01
        return bytes(data)
    IoCtx.read = read


FAULTS = {f.__name__: f for f in (
    sweep_answer_altered, sweep_half_left_out, sweep_no_exchange,
    write_state_unchanged, encode_answer_altered, read_answer_altered)}

if __name__ == "__main__":
    t0 = time.perf_counter()
    FAULTS[sys.argv[1]]()
    from harness.runner import main
    sys.exit(main(sys.argv[2:] + ["--rehearsal"], t_start=t0))
