#!/usr/bin/env python3
"""``faulty_run.py`` for the replicated cell: a rehearsal run with the
replicated backend broken underneath; ``correct`` has to come out false.

    python benchmark/tests/faulty_run_rep.py <fault> --workload ... --seed ...
"""

import asyncio
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))


def replica_acks_before_commit():
    """A replica answers the primary at once and commits 0.3 s later:
    writes are acknowledged with the primary's copy alone stored."""
    from ceph_tpu.osd.messages import MOSDRepOpReply
    from ceph_tpu.osd.pg import PG
    real = PG.handle_rep_op

    def handle_rep_op(self, m):
        asyncio.ensure_future(m.conn.send_message(MOSDRepOpReply(
            tid=m.tid, result=0, pgid=self.cid, from_osd=self.osd.whoami)))
        asyncio.get_event_loop().call_later(0.3, real, self, m)
    PG.handle_rep_op = handle_rep_op


def replica_bytes_altered():
    """A replica stores the object with one byte flipped."""
    from ceph_tpu.os_.objectstore import OP_WRITE, Transaction
    from ceph_tpu.osd.pg import PG
    real = PG._apply_rep_op

    def _apply_rep_op(self, m, span):
        t = Transaction.decode(m.txn)
        for i, op in enumerate(t.ops):
            if op[0] == OP_WRITE and op[4]:
                data = bytearray(op[4])
                data[len(data) // 2] ^= 0x01
                t.ops[i] = op[:4] + (bytes(data),)
        m.txn = t.encode()
        return real(self, m, span)
    PG._apply_rep_op = _apply_rep_op


FAULTS = {f.__name__: f for f in (
    replica_acks_before_commit, replica_bytes_altered)}

if __name__ == "__main__":
    t0 = time.perf_counter()
    FAULTS[sys.argv[1]]()
    from harness.runner import main
    sys.exit(main(sys.argv[2:] + ["--rehearsal"], t_start=t0))
