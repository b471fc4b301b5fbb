"""Proc backend: supervised multi-process cluster + wire-delivered
live config.

Every proc-backend phase shares ONE spawned cluster (spawn-to-healthy
costs seconds of real process startup; respawning per-test would blow
the tier-1 budget), sequenced inside a single event loop because the
supervisor's watcher tasks belong to it:

  1. live `ceph config set` lands TYPED inside every remote OSD
     process without a restart; `config rm` restores the default
  2. per-entity beats per-type beats global across real processes
  3. proc_storm: SIGKILL an OSD, the lead mon and the active mgr under
     continuing writer load (zero errors, bit-identical reads,
     supervisor restarts observed, mgr telemetry re-populates), plus
     the SIGSTOP/SIGCONT gray pass (OSD_SLOW trips, then heals)

ref: src/test/test_c2c.cc has no analog — this is qa/tasks/thrashosds
semantics pointed at real PIDs.
"""

import asyncio

import pytest

from ceph_tpu.cluster.vstart import Cluster
from ceph_tpu.sim.thrasher import Thrasher


def run(coro):
    return asyncio.run(coro)


async def _wait(pred, timeout=30.0):
    t0 = asyncio.get_event_loop().time()
    while True:
        if await pred():
            return
        if asyncio.get_event_loop().time() - t0 > timeout:
            raise TimeoutError
        await asyncio.sleep(0.25)


async def _osd_cfg(c, osd_id: int, name: str):
    out = await c.daemon_command(f"osd.{osd_id}", "config show")
    return out.get(name)


def test_proc_cluster_storm_and_live_config():
    async def go():
        # grace must exceed the OSD_SLOW confirm window: a SIGSTOPped
        # OSD that gets marked DOWN first never shows as slow
        c = Cluster(n_mons=3, n_osds=3, n_mgrs=2,
                    mgr_modules=["prometheus"],
                    config={"osd_heartbeat_grace": 10.0},
                    backend="proc")
        assert c.backend == "proc"
        await c.start()
        try:
            assert c.spawn_to_healthy_s is not None
            await c.client.pool_create("t", pg_num=16, size=3)

            # the mon acks the command when the epoch commits; this
            # client's subscription may deliver it a moment later
            async def sees_pool():
                om = c.client.monc.osdmap
                return om is not None and any(
                    p.name == "t" for p in om.pools.values())
            await _wait(sees_pool, timeout=60.0)
            io = await c.client.open_ioctx("t")

            # -- 1: live config lands typed, no restart ----------------
            pids = {n: ch.pid for n, ch in c.children.items()
                    if n.startswith("osd.")}
            await c.config_set("osd", "osd_max_backfills", "7")

            async def landed():
                for i in range(3):
                    if await _osd_cfg(c, i, "osd_max_backfills") != 7:
                        return False
                return True
            await _wait(landed)
            assert pids == {n: ch.pid for n, ch in c.children.items()
                            if n.startswith("osd.")}, \
                "config delivery must not restart daemons"

            # -- 2: most-specific wins across process boundaries -------
            await c.config_set("osd.0", "osd_max_backfills", "3")

            async def split():
                return (await _osd_cfg(c, 0, "osd_max_backfills") == 3
                        and await _osd_cfg(
                            c, 1, "osd_max_backfills") == 7)
            await _wait(split)

            # -- rm restores the boot-time value (key absent) ----------
            await c.config_rm("osd.0", "osd_max_backfills")
            await c.config_rm("osd", "osd_max_backfills")

            async def restored():
                for i in range(3):
                    v = await _osd_cfg(c, i, "osd_max_backfills")
                    if v not in (None, 1):
                        return False
                return True
            await _wait(restored)

            # -- 3: the storm (SIGKILLs + SIGSTOP gray pass) -----------
            th = Thrasher(c, seed=7, write_timeout=30.0)
            summary = await th.proc_storm(io, settle_timeout=180.0,
                                          gray=True)
            assert summary["acked_writes"] > 0
            assert summary["failed_writes"] == 0
            assert sum(summary["restarts"].values()) >= 2
            assert summary["mgr_failover"] is not None
        finally:
            await c.stop()
    run(go())


def test_live_config_set_inproc():
    """The SAME wire-delivered config path, in-process backend: set a
    registered knob centrally, every OSD's runtime layer follows typed
    with no restart; rm restores the default."""
    async def go():
        c = Cluster(n_mons=1, n_osds=2)
        await c.start()
        try:
            ret, rs, _ = await c.client.mon_command(
                {"prefix": "config set", "who": "osd",
                 "name": "osd_max_backfills", "value": "5"})
            assert ret == 0, rs

            async def landed():
                return all(o.config.get("osd_max_backfills") == 5
                           for o in c.osds)
            await _wait(landed, timeout=15.0)
            ret, _, out = await c.client.mon_command(
                {"prefix": "config get", "who": "osd",
                 "name": "osd_max_backfills"})
            assert ret == 0 and out == b"5"
            ret, rs, _ = await c.client.mon_command(
                {"prefix": "config rm", "who": "osd",
                 "name": "osd_max_backfills"})
            assert ret == 0, rs

            async def restored():
                return all(o.config.get("osd_max_backfills") in (None, 1)
                           for o in c.osds)
            await _wait(restored, timeout=15.0)
            # a bogus value for a registered option is refused upfront
            ret, rs, _ = await c.client.mon_command(
                {"prefix": "config set", "who": "osd",
                 "name": "osd_max_backfills", "value": "not-an-int"})
            assert ret == -22
        finally:
            await c.stop()
    run(go())
