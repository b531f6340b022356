"""Tier-1 owner-failover smoke: 5 real nodes on loopback, one SIGKILL.

The full proof (`bench.py --failover`) soaks traffic and gates the
availability/loss/lag numbers; THIS smoke pins the structural
properties in tier-1 so a regression fails CI, not a bench round
later:

- five NakamaServer processes (2 owner shards + a warm standby + 2
  frontends) boot with `cluster.shards` and converge to all-peers-up;
- cross-shard matchmaking: pool-keyed 1v1 pairs split across the two
  frontends match through BOTH owners' pools (the rendezvous map is
  the router);
- SIGKILL of owner shard o1: the standby observes lease expiry,
  promotes IN PLACE (same process — epoch bump on the shard map, no
  restart), and holds the replicated tickets;
- a fresh pair on the dead shard's pool matches on the promoted
  owner.

Subprocess-isolated like test_cluster_smoke: SIGKILL is the test, and
each node must be its own process — that IS the subsystem under test.
Children run `bench.py --cluster-node` (the same node runner the
failover bench uses, so the lab and the proof cannot drift)."""

from __future__ import annotations

import asyncio
import os
import signal
import tempfile
import time

import bench


def test_failover_five_nodes_cross_shard_kill_promote():
    asyncio.run(asyncio.wait_for(_smoke(), timeout=220))


async def _smoke():
    import aiohttp

    base_dir = tempfile.mkdtemp(prefix="failover-smoke-")
    shards = ["o1", "o2"]
    pools = bench._failover_pools(shards)  # shard -> pool name
    lease = dict(lease_ms=400, lease_grace_ms=800,
                 heartbeat_ms=200, down_after_ms=1200)
    o1 = bench._ClusterNode(
        "o1", "device_owner", "", [], base_dir,
        db=os.path.join(base_dir, "o1.db"), shards=shards, **lease,
    )
    o2 = bench._ClusterNode(
        "o2", "device_owner", "", [], base_dir,
        db=os.path.join(base_dir, "o2.db"), shards=shards, **lease,
    )
    sb = bench._ClusterNode(
        "sb", "standby", "", [], base_dir,
        db=os.path.join(base_dir, "sb.db"), shards=shards,
        standby_of="o1", **lease,
    )
    f1 = bench._ClusterNode("f1", "frontend", "", [], base_dir,
                            shards=shards, **lease)
    f2 = bench._ClusterNode("f2", "frontend", "", [], base_dir,
                            shards=shards, **lease)
    nodes = {n.name: n for n in (o1, o2, sb, f1, f2)}
    for n in nodes.values():
        n.spec["peers"] = [
            f"{p.name}=127.0.0.1:{p.bus_port}"
            for p in nodes.values()
            if p is not n
        ]
        n.spawn()
    clients = []
    try:
        async with aiohttp.ClientSession() as http:
            for n in nodes.values():
                await n.wait_healthy(http)
            await bench._cluster_wait_converged(
                http, list(nodes.values()), timeout=30.0
            )

            # ---- cross-shard matchmaking: one pair per shard --------
            pairs = []
            for i, shard in enumerate(shards):
                a = await bench._WsClient(f"a{i}").open(
                    http, f1.base, f"smoke-fo-a{i}-0001"
                )
                b = await bench._WsClient(f"b{i}").open(
                    http, f2.base, f"smoke-fo-b{i}-0001"
                )
                clients += [a, b]
                pairs.append((a, b, pools[shard]))
            lat, hung = await bench._failover_match_rounds(
                pairs, 1, timeout=20.0
            )
            assert hung == 0 and len(lat) == 4, (lat, hung)
            # The forwarded ids carry their origin node: the seam.
            assert any(
                t.endswith(".f1") for c in clients
                for t in c.acked_tickets
            )

            # ---- pooled tickets on the doomed shard, then SIGKILL ---
            b0 = clients[1]  # on f2
            for j in range(2):
                await b0.send(
                    {
                        "matchmaker_add": {
                            "query": f"+properties.never:zz{j}",
                            "min_count": 2,
                            "max_count": 2,
                            "string_properties": {
                                "pool": pools["o1"], "mode": f"aa{j}",
                            },
                        }
                    }
                )
                assert (
                    await b0.recv_until("matchmaker_ticket", 15.0)
                ) is not None
            # Forwards + replication land: wait for the standby's shadow
            # pool to hold both tickets (a fixed sleep loses the race on a
            # loaded host, and the kill below then proves nothing).
            landed = time.perf_counter() + 15.0
            while time.perf_counter() < landed:
                shadow = await bench._cluster_console(http, sb)
                if shadow.get("matchmaker_tickets", 0) >= 2:
                    break
                await asyncio.sleep(0.25)
            pre = await bench._cluster_console(http, o1)
            assert pre["matchmaker_tickets"] >= 2
            sb_pid = sb.proc.pid
            o1.kill(signal.SIGKILL)

            # ---- standby promotes in place within lease + grace -----
            deadline = time.perf_counter() + 20.0
            promoted = False
            while time.perf_counter() < deadline and not promoted:
                snap = await bench._cluster_console(http, sb)
                fo = snap.get("failover") or {}
                sh = (snap.get("shards") or {}).get("o1", {})
                promoted = (
                    fo.get("promoted") is True
                    and sh.get("node") == "sb"
                )
                if not promoted:
                    await asyncio.sleep(0.25)
            assert promoted, "standby never promoted"
            # Same process: a lease takeover, not a restart.
            assert sb.proc.pid == sb_pid and sb.proc.poll() is None
            # The replicated never-match tickets survived onto the
            # promoted owner's pool (zero acknowledged-ticket loss).
            snap = await bench._cluster_console(http, sb)
            assert snap["matchmaker_tickets"] >= 2, snap

            # ---- a fresh pair on the dead shard matches -------------
            c = await bench._WsClient("hc").open(
                http, f1.base, "smoke-fo-heal-0001"
            )
            d = await bench._WsClient("hd").open(
                http, f2.base, "smoke-fo-heal-0002"
            )
            clients += [c, d]
            lat2, hung2 = await bench._failover_match_rounds(
                [(c, d, pools["o1"])], 1, timeout=25.0
            )
            assert hung2 == 0 and len(lat2) == 2, (lat2, hung2)

            for cl in clients:
                await cl.close()
    finally:
        for n in nodes.values():
            n.stop()
