"""A heartbeat's RTT runs from the moment that ping went out, not from
the start of the ping round: what the sends before it took is not the
peer's."""

import asyncio

import pytest

from ceph_tpu.cluster.vstart import Cluster
from ceph_tpu.osd.messages import MOSDPing

HELD = 0.4          # seconds the round's first send is held


@pytest.mark.parametrize("n_osds", [3, 4])
def test_a_ping_is_stamped_when_it_is_sent(n_osds):
    async def go():
        c = await Cluster(n_mons=1, n_osds=n_osds, config={
            "osd_heartbeat_interval": 0.2, "osd_heartbeat_grace": 20.0,
            "osd_stats_interval": 5.0}).start()
        try:
            osd = c.osds[0]
            real = osd.hb_msgr.send_message
            loop = asyncio.get_running_loop()
            sent = []                    # (peer, stamp, the loop's time)

            async def send_message(m, addr, peer):
                if isinstance(m, MOSDPing) and m.from_osd == 0 \
                        and peer.startswith("osd."):
                    sent.append((peer, m.stamp, loop.time()))
                    if peer == "osd.1":
                        await asyncio.sleep(HELD)
                return await real(m, addr, peer)
            osd.hb_msgr.send_message = send_message
            while sum(p == f"osd.{n_osds - 1}" for p, _, _ in sent) < 2:
                await asyncio.sleep(0.05)
            osd.hb_msgr.send_message = real
            first = {}
            for peer, stamp, at in sent:
                # the stamp is the send's own moment, whatever the
                # round's earlier sends took
                assert at - stamp < HELD / 4, (peer, at - stamp)
                first.setdefault(peer, stamp)
            assert first[f"osd.{n_osds - 1}"] - first["osd.1"] >= HELD * 0.9
            await asyncio.sleep(0.3)
            # and so the round trip of no peer pinged after it holds
            # the time of that send (osd.1's own ping was the one held)
            later = {o: r for o, r in osd._peer_rtt.items() if o != 1}
            assert later and max(later.values()) < HELD / 2, later
        finally:
            await c.stop()
    asyncio.run(go())
