"""Console admin API tests: own auth + lockout, status, redacted config,
account browse/ban, storage browse, runtime info, API explorer
(reference server/console.go, console_authenticate.go:73,
console_api_explorer.go)."""

import asyncio
import json

import aiohttp
import pytest

from fixtures import quiet_logger

from nakama_tpu.config import Config
from nakama_tpu.server import NakamaServer


async def make_server(modules=None):
    config = Config()
    config.socket.port = 0
    server = NakamaServer(
        config, quiet_logger(), runtime_modules=modules or []
    )
    await server.start()
    return server


class Console:
    def __init__(self, server):
        self.base = f"http://127.0.0.1:{server.console_port}"
        self.http = aiohttp.ClientSession()
        self.token = ""

    async def close(self):
        await self.http.close()

    async def login(self, username="admin", password="password"):
        status, body = await self.call(
            "POST",
            "/v2/console/authenticate",
            body={"username": username, "password": password},
        )
        if status == 200:
            self.token = body["token"]
        return status, body

    async def call(self, method, path, body=None):
        headers = (
            {"Authorization": f"Bearer {self.token}"} if self.token else {}
        )
        async with self.http.request(
            method, self.base + path, json=body, headers=headers
        ) as resp:
            return resp.status, await resp.json()


async def test_console_auth_and_lockout():
    server = await make_server()
    console = Console(server)
    try:
        status, _ = await console.call("GET", "/v2/console/status")
        assert status == 401

        status, out = await console.login("admin", "wrong")
        assert status == 401
        status, out = await console.login()
        assert status == 200 and out["role"] == 1

        status, status_body = await console.call(
            "GET", "/v2/console/status"
        )
        assert status == 200
        assert status_body["name"] == server.config.name
        assert "sessions" in status_body

        # Repeated failures lock the account out.
        for _ in range(10):
            await console.login("admin", "wrong")
        status, out = await console.login("admin", "wrong")
        assert status in (401, 429)
    finally:
        await console.close()
        await server.stop(0)


async def test_console_config_redaction_and_runtime():
    def init_module(ctx, logger, nk, initializer):
        initializer.register_rpc("ping", lambda c, p: "pong")

    server = await make_server([init_module])
    console = Console(server)
    try:
        await console.login()
        status, config = await console.call("GET", "/v2/console/config")
        assert status == 200
        assert config["session"]["encryption_key"] == "<redacted>"
        assert config["socket"]["server_key"] == "<redacted>"
        assert config["console"]["password"] == "<redacted>"
        assert config["matchmaker"]["interval_sec"] == 15

        status, rt = await console.call("GET", "/v2/console/runtime")
        assert rt["loaded"] is True and rt["rpcs"] == ["ping"]

        # API explorer invokes the rpc as console.
        status, out = await console.call(
            "POST", "/v2/console/api/endpoints/rpc/ping"
        )
        assert status == 200 and out["payload"] == "pong"
    finally:
        await console.close()
        await server.stop(0)


async def test_console_accounts_storage_and_ban():
    server = await make_server()
    console = Console(server)
    try:
        from nakama_tpu.core import authenticate as core_auth
        from nakama_tpu.core.storage import StorageOpWrite
        from nakama_tpu.core import storage as core_storage

        uid, _, _ = await core_auth.authenticate_device(
            server.db, "device-console-1", "watched", True
        )
        await core_storage.storage_write_objects(
            server.db,
            None,
            [
                StorageOpWrite(
                    collection="saves", key="s1", user_id=uid,
                    value='{"hp": 3}',
                )
            ],
        )
        await console.login()
        status, users = await console.call(
            "GET", "/v2/console/account?filter=watched"
        )
        assert status == 200
        assert users["users"][0]["username"] == "watched"

        status, account = await console.call(
            "GET", f"/v2/console/account/{uid}"
        )
        assert account["user"]["username"] == "watched"
        assert account["wallet"] == {}

        status, objs = await console.call(
            "GET", f"/v2/console/storage?user_id={uid}"
        )
        assert [o["key"] for o in objs["objects"]] == ["s1"]
        status, obj = await console.call(
            "GET", f"/v2/console/storage/saves/s1/{uid}"
        )
        assert json.loads(obj["value"]) == {"hp": 3}

        # Ban kills sessions and blocks re-auth.
        token = server.issue_session(uid, "watched")
        status, _ = await console.call(
            "POST", f"/v2/console/account/{uid}/ban"
        )
        assert status == 200
        assert not server.session_cache.is_valid_session(uid, "whatever")
        with pytest.raises(core_auth.AuthError):
            await core_auth.authenticate_device(
                server.db, "device-console-1", None, False
            )
        status, _ = await console.call(
            "POST", f"/v2/console/account/{uid}/unban"
        )
        uid2, _, _ = await core_auth.authenticate_device(
            server.db, "device-console-1", None, False
        )
        assert uid2 == uid
    finally:
        await console.close()
        await server.stop(0)


async def _tick_on_device_backend(
    tickets: int, pipelined: bool, interval_sec: int = 15
):
    """A started server on the device backend (small exact kernel) after
    one `process()` over `tickets` wildcard 1v1 tickets, and its console."""
    from nakama_tpu.matchmaker import MatchmakerPresence
    from nakama_tpu.matchmaker.tpu import TpuBackend

    config = Config()
    config.socket.port = 0
    config.matchmaker.pool_capacity = 4096
    config.matchmaker.big_pool_threshold = 1 << 30  # small exact kernel
    config.matchmaker.interval_pipelining = pipelined
    config.matchmaker.interval_sec = interval_sec
    server = NakamaServer(config, quiet_logger())
    backend = TpuBackend(config.matchmaker, quiet_logger())
    server.matchmaker.backend = backend
    backend.attach(server.matchmaker.store, server.matchmaker.tracing)
    await server.start()
    for i in range(tickets):
        p = MatchmakerPresence(user_id=f"u{i}", session_id=f"s{i}")
        server.matchmaker.add([p], p.session_id, "", "*", 2, 2, 1, {}, {})
    server.matchmaker.process()
    return server, Console(server)


async def test_console_matchmaker_breadcrumbs():
    """Device-backend breadcrumbs surface through the console (SURVEY §5
    per-interval timing observability)."""
    # Synchronous interval: the breadcrumb assertions below need one
    # process() to dispatch AND deliver (the pipelined default delivers
    # mid-gap, one interval later).
    server, console = await _tick_on_device_backend(2, pipelined=False)
    try:
        await console.login()
        status, out = await console.call("GET", "/v2/console/matchmaker")
        assert status == 200
        assert out["backend"] == "TpuBackend"
        assert out["intervals"], "expected at least one breadcrumb"
        crumb = out["intervals"][-1]
        assert crumb["actives"] == 2
        assert crumb["matched_entries"] == 2
        assert "dispatch_s" in crumb and "collect_s" in crumb
    finally:
        await console.close()
        await server.stop(0)


async def test_console_matchmaker_delivery_row_counts_tokens():
    """A pipelined cohort's delivery row reaches the console with the
    publish callback's counts on it (README "Reading the records")."""
    server, console = await _tick_on_device_backend(4, pipelined=True)
    try:
        await console.login()
        for _ in range(400):
            status, out = await console.call("GET", "/v2/console/matchmaker")
            assert status == 200
            rows = [r for r in out["deliveries"] if "publish_lag_s" in r]
            if rows:
                break
            await asyncio.sleep(0.05)
        (row,) = rows
        assert row["matches"] == row["publish_matches"] == 2
        assert row["publish_tokens"] == 2 and row["publish_envelopes"] == 4
        assert row["publish_route_calls"] == 2  # one a match
        assert row["publish_gc_collections"] == 0
        assert row["publish_token_s"] > 0.0
    finally:
        await console.close()
        await server.stop(0)


async def test_console_matchmaker_shows_gap_passes_and_the_cpu_split():
    """The interval loop's gap pass is a `kind: "gap"` crumb among
    `intervals`, and a delivered cohort's row carries the CPU beside the
    wall of its delivery call and of its assembly (README "Reading the
    records", steps 2 and 6)."""
    server, console = await _tick_on_device_backend(
        4, pipelined=True, interval_sec=1
    )
    try:
        await console.login()
        for _ in range(400):
            status, out = await console.call("GET", "/v2/console/matchmaker")
            assert status == 200
            gaps = [c for c in out["intervals"] if c.get("kind") == "gap"]
            rows = [r for r in out["deliveries"] if "publish_lag_s" in r]
            if gaps and rows:
                break
            await asyncio.sleep(0.05)
        gap = gaps[0]
        assert gap["shed"] is False and gap["wake_late_s"] >= 0.0
        assert gap["_pc_start"] <= gap["_pc_end"]
        for key in ("count_s", "drain_s", "gc_s", "flush_s", "cpu_s"):
            assert gap[key] >= 0.0, (key, gap)
        assert "actives" not in gap and "in_flight" in gap
        (row,) = rows
        wall = row["publish_cpu_s"] + row["publish_offcpu_s"]
        assert 0.0 < wall <= row["publish_lag_s"] - row["accept_lag_s"]
        for key in ("publish_other_cpu_s", "publish_invol_switches",
                    "publish_minor_faults", "assemble_cpu_s",
                    "assemble_offcpu_s", "gap_in_flight_s"):
            assert key in row, (key, row)
    finally:
        await console.close()
        await server.stop(0)


async def test_prometheus_scrape_endpoint():
    # Dedicated internal listener; console mux stays auth-only and the
    # default (port 0) serves no exposition at all (reference
    # server/metrics.go semantics).
    config = Config()
    config.socket.port = 0
    config.metrics.prometheus_port = -1  # ephemeral
    server = NakamaServer(config, quiet_logger())
    await server.start()
    console = Console(server)
    try:
        url = f"http://127.0.0.1:{server.console.metrics_port}/metrics"
        async with console.http.get(url) as resp:
            assert resp.status == 200
            text = await resp.text()
        assert "nakama_sessions" in text
        async with console.http.get(console.base + "/metrics") as resp:
            assert resp.status == 404  # not on the console mux
    finally:
        await console.close()
        await server.stop(0)

    disabled = await make_server()
    console2 = Console(disabled)
    try:
        assert disabled.console.metrics_port is None
        async with console2.http.get(console2.base + "/metrics") as resp:
            assert resp.status == 404
    finally:
        await console2.close()
        await disabled.stop(0)


async def test_console_storage_write_import_and_account_edit():
    """VERDICT r2 #5 done-criterion: the console drives a storage
    import + account edit round-trip (reference
    console_storage_import.go, console_account.go UpdateAccount)."""
    server = await make_server()
    console = Console(server)
    try:
        await console.login()
        # Create a user via the server's own auth core.
        from nakama_tpu.core import authenticate as core_auth

        user_id, _, _ = await core_auth.authenticate_device(
            server.db, "console-edit-dev-01", "edituser", True
        )

        # --- account edit + wallet replacement
        status, _ = await console.call(
            "POST", f"/v2/console/account/{user_id}",
            body={"display_name": "Edited Name",
                  "metadata": {"tier": "gold"},
                  "wallet": {"coins": 250}},
        )
        assert status == 200
        status, acct = await console.call(
            "GET", f"/v2/console/account/{user_id}"
        )
        assert acct["user"]["display_name"] == "Edited Name"
        assert acct["wallet"] == {"coins": 250}

        # --- wallet ledger view
        await server.wallets.update_wallets(
            [{"user_id": user_id, "changeset": {"coins": 10},
              "metadata": {"why": "t"}}]
        )
        status, w = await console.call(
            "GET", f"/v2/console/account/{user_id}/wallet"
        )
        assert status == 200
        assert w["wallet"]["coins"] == 260
        assert len(w["ledger"]) == 1

        # --- single storage write + read-back + delete
        status, ack = await console.call(
            "POST", "/v2/console/storage",
            body={"collection": "cfg", "key": "motd",
                  "user_id": "", "value": {"text": "hi"}},
        )
        assert status == 200 and ack["version"]
        # System-owned ("" user_id) objects aren't path-addressable —
        # browse via the list endpoint.
        status, listing = await console.call(
            "GET", "/v2/console/storage?collection=cfg"
        )
        assert any(o["key"] == "motd" for o in listing["objects"])

        # --- JSON import lands atomically
        import_rows = [
            {"collection": "imp", "key": f"k{i}", "user_id": user_id,
             "value": {"i": i}}
            for i in range(5)
        ]
        import aiohttp as _aiohttp

        async with console.http.post(
            console.base + "/v2/console/storage/import",
            data=json.dumps(import_rows),
            headers={"Authorization": f"Bearer {console.token}"},
        ) as resp:
            assert resp.status == 200
            assert (await resp.json())["imported"] == 5

        # --- CSV import
        csv_text = (
            "collection,key,user_id,value\n"
            f"impcsv,a,{user_id},\"{{\"\"x\"\": 1}}\"\n"
            f"impcsv,b,{user_id},\"{{\"\"x\"\": 2}}\"\n"
        )
        async with console.http.post(
            console.base + "/v2/console/storage/import",
            data=csv_text,
            headers={
                "Authorization": f"Bearer {console.token}",
                "Content-Type": "text/csv",
            },
        ) as resp:
            assert resp.status == 200, await resp.text()
            assert (await resp.json())["imported"] == 2

        status, listing = await console.call(
            "GET", "/v2/console/storage?collection=impcsv"
        )
        assert len(listing["objects"]) == 2

        # --- storage delete
        status, _ = await console.call(
            "DELETE", f"/v2/console/storage/imp/k0/{user_id}"
        )
        assert status == 200
        status, listing = await console.call(
            "GET", f"/v2/console/storage?collection=imp"
        )
        assert len(listing["objects"]) == 4
    finally:
        await console.close()
        await server.stop(0)


async def test_console_groups_users_and_ui():
    server = await make_server()
    console = Console(server)
    try:
        await console.login()
        # Group browse reflects core-created groups.
        from nakama_tpu.core import authenticate as core_auth

        uid, _, _ = await core_auth.authenticate_device(
            server.db, "console-group-dev", "groupuser", True
        )
        await server.groups.create(uid, "Console Guild")
        status, groups = await console.call(
            "GET", "/v2/console/group"
        )
        assert status == 200
        assert any(g["name"] == "Console Guild" for g in groups["groups"])
        gid = groups["groups"][0]["id"]
        status, members = await console.call(
            "GET", f"/v2/console/group/{gid}/member"
        )
        assert status == 200 and len(members["group_users"]) == 1

        # Console-user management: admin creates, new user logs in with
        # its role enforced (maintainer can write, readonly cannot).
        status, _ = await console.call(
            "POST", "/v2/console/user",
            body={"username": "ops1", "password": "longenough",
                  "role": 4},
        )
        assert status == 200
        ops = Console(server)
        try:
            status, _ = await ops.login("ops1", "longenough")
            assert status == 200
            status, _ = await ops.call(
                "POST", "/v2/console/storage",
                body={"collection": "x", "key": "y", "user_id": "",
                      "value": {}},
            )
            assert status == 403  # readonly blocked from writes
            status, _ = await ops.call(
                "POST", "/v2/console/user",
                body={"username": "ops2", "password": "longenough"},
            )
            assert status == 403  # non-admin cannot manage users
        finally:
            await ops.close()
        status, users = await console.call("GET", "/v2/console/user")
        assert [u["username"] for u in users["users"]] == ["ops1"]
        status, _ = await console.call(
            "DELETE", "/v2/console/user/ops1"
        )
        assert status == 200

        # Embedded UI serves at /.
        async with console.http.get(console.base + "/") as resp:
            assert resp.status == 200
            text = await resp.text()
            assert "nakama-tpu console" in text
    finally:
        await console.close()
        await server.stop(0)


async def test_console_channel_browse_delete_and_record_delete():
    """Console message browse/delete + leaderboard record delete
    (reference console.proto ListChannelMessages/DeleteChannelMessages/
    DeleteLeaderboardRecord)."""
    server = await make_server()
    console = Console(server)
    try:
        await console.login()
        from nakama_tpu.core import authenticate as core_auth

        uid, _, _ = await core_auth.authenticate_device(
            server.db, "console-chan-dev", "chanuser", True
        )
        # Seed a room message + a leaderboard record via the cores.
        from nakama_tpu.realtime import Stream, StreamMode
        from nakama_tpu.core.channel import stream_to_channel_id

        stream = Stream(StreamMode.CHANNEL, label="ops-room")
        channel_id = stream_to_channel_id(stream)
        msg = await server.channels.message_send(
            channel_id, {"text": "hi"}, sender_id=uid,
            sender_username="chanuser",
        )
        await server.leaderboards.create("console-lb")
        await server.leaderboards.record_write(
            "console-lb", uid, "chanuser", 42
        )

        status, listing = await console.call(
            "GET", f"/v2/console/channel/{channel_id}"
        )
        assert status == 200
        assert [m["message_id"] for m in listing["messages"]] == [
            msg["message_id"]
        ]

        # Another (valid) channel must 404: membership is validated.
        other_id = stream_to_channel_id(
            Stream(StreamMode.CHANNEL, label="other-room")
        )
        status, _ = await console.call(
            "DELETE",
            f"/v2/console/channel/{other_id}/message/"
            f"{msg['message_id']}",
        )
        assert status == 404
        status, _ = await console.call(
            "DELETE",
            f"/v2/console/channel/{channel_id}/message/"
            f"{msg['message_id']}",
        )
        assert status == 200
        status, listing = await console.call(
            "GET", f"/v2/console/channel/{channel_id}"
        )
        assert listing["messages"] == []

        status, recs = await console.call(
            "GET", "/v2/console/leaderboard/console-lb"
        )
        assert status == 200 and len(recs["records"]) == 1
        status, _ = await console.call(
            "DELETE",
            "/v2/console/leaderboard/console-lb/owner/not-a-user"
        )
        assert status == 404  # rowcount-0 delete must not report success
        status, _ = await console.call(
            "DELETE", f"/v2/console/leaderboard/console-lb/owner/{uid}"
        )
        assert status == 200
        status, recs = await console.call(
            "GET", "/v2/console/leaderboard/console-lb"
        )
        assert recs["records"] == []
    finally:
        await console.close()
        await server.stop(0)


async def test_console_round4_explorer_and_data_admin():
    """VERDICT r3 #2: generic endpoint explorer, DeleteAllData, bulk
    account delete, friends/ledger/subscription browse, collections,
    group export + member admin, per-provider unlink, logout."""
    server = await make_server()
    console = Console(server)
    try:
        await console.login()

        # Seed: two users with friendship, wallet, storage, group, chat.
        nk_http = aiohttp.ClientSession()
        import base64

        basic = "Basic " + base64.b64encode(b"defaultkey:").decode()
        uids = []
        for i in range(2):
            async with nk_http.post(
                f"http://127.0.0.1:{server.port}"
                "/v2/account/authenticate/device",
                json={"account": {"id": f"device-c4-{i:06d}"},
                      "username": f"c4u{i}"},
                headers={"Authorization": basic},
            ) as resp:
                assert resp.status == 200
        await nk_http.close()
        rows = await server.db.fetch_all(
            "SELECT id FROM users ORDER BY username"
        )
        uids = [r["id"] for r in rows]
        await server.friends.add(uids[0], "c4u0", uids[1])
        await server.friends.add(uids[1], "c4u1", uids[0])
        await server.wallets.update_wallets(
            [{"user_id": uids[0], "changeset": {"gold": 3},
              "metadata": {}}], True,
        )
        from nakama_tpu.core.storage import StorageOpWrite, storage_write_objects

        await storage_write_objects(
            server.db, None,
            [StorageOpWrite(collection="c4col", key="k", user_id=uids[0],
                            value='{"a": 1}')],
        )

        # --- ListApiEndpoints + CallApiEndpoint (act as user 0).
        status, body = await console.call(
            "GET", "/v2/console/api/endpoints"
        )
        assert status == 200
        paths = {e["path"] for e in body["endpoints"]}
        assert "/v2/account" in paths and "/v2/friend" in paths
        status, body = await console.call(
            "POST", "/v2/console/api/endpoints/call",
            body={"method": "GET", "path": "/v2/account",
                  "user_id": uids[0]},
        )
        assert status == 200 and body["status"] == 200
        assert "c4u0" in body["body"]
        # Console paths are not reachable through the explorer.
        status, body = await console.call(
            "POST", "/v2/console/api/endpoints/call",
            body={"method": "GET", "path": "/v2/console/config"},
        )
        assert status == 400

        # --- Friends browse + delete.
        status, body = await console.call(
            "GET", f"/v2/console/account/{uids[0]}/friend"
        )
        assert status == 200 and len(body["friends"]) == 1
        status, _ = await console.call(
            "DELETE",
            f"/v2/console/account/{uids[0]}/friend/{uids[1]}",
        )
        assert status == 200
        status, body = await console.call(
            "GET", f"/v2/console/account/{uids[0]}/friend"
        )
        assert body["friends"] == []

        # --- Groups: create via core, then console admin flows.
        g = await server.groups.create(uids[0], "c4-group", open=True)
        await server.groups.join(g["id"], uids[1], "c4u1")
        status, body = await console.call(
            "GET", f"/v2/console/account/{uids[0]}/group"
        )
        assert status == 200 and len(body["user_groups"]) == 1
        status, _ = await console.call(
            "POST",
            f"/v2/console/group/{g['id']}/member/{uids[1]}/promote",
        )
        assert status == 200
        status, body = await console.call(
            "GET", f"/v2/console/group/{g['id']}/export"
        )
        assert status == 200 and len(body["members"]) == 2
        status, _ = await console.call(
            "POST", f"/v2/console/group/{g['id']}",
            body={"description": "edited by ops"},
        )
        assert status == 200
        status, body = await console.call(
            "GET", f"/v2/console/group/{g['id']}"
        )
        assert body["description"] == "edited by ops"
        status, _ = await console.call(
            "DELETE", f"/v2/console/group/{g['id']}/member/{uids[1]}"
        )
        assert status == 200

        # --- Wallet ledger browse + delete.
        status, body = await console.call(
            "GET", f"/v2/console/account/{uids[0]}/walletledger"
        )
        assert status == 200 and len(body["items"]) == 1
        lid = body["items"][0]["id"]
        status, _ = await console.call(
            "DELETE",
            f"/v2/console/account/{uids[0]}/walletledger/{lid}",
        )
        assert status == 200
        status, body = await console.call(
            "GET", f"/v2/console/account/{uids[0]}/walletledger"
        )
        assert body["items"] == []

        # --- Storage collections + unlink + subscriptions browse.
        status, body = await console.call(
            "GET", "/v2/console/storage/collections"
        )
        assert status == 200 and body["collections"] == ["c4col"]
        status, _ = await console.call(
            "POST", f"/v2/console/account/{uids[0]}/unlink/device",
            body={"device_id": "device-c4-000000"},
        )
        # Sole auth method: the guard must refuse, proving the real core
        # ran (not a stub).
        assert status == 400
        status, body = await console.call(
            "GET", "/v2/console/subscription"
        )
        assert status == 200 and body["subscriptions"] == []

        # --- Leaderboard definition.
        await server.leaderboards.create("c4-lb", sort_order="desc")
        status, body = await console.call(
            "GET", "/v2/console/leaderboard/c4-lb/detail"
        )
        assert status == 200 and body["id"] == "c4-lb"

        # --- DeleteAllData wipes domain tables but not console users.
        status, _ = await console.call("DELETE", "/v2/console/all")
        assert status == 200
        for table in ("users", "storage", "groups", "message",
                      "wallet_ledger", "leaderboard"):
            n = (await server.db.fetch_one(
                f"SELECT COUNT(*) AS n FROM {table}"
            ))["n"]
            assert n == 0, (table, n)
        # Console auth still works after the wipe.
        status, _ = await console.call("GET", "/v2/console/status")
        assert status == 200

        # --- Logout revokes the token.
        status, _ = await console.call(
            "POST", "/v2/console/authenticate/logout"
        )
        assert status == 200
        status, _ = await console.call("GET", "/v2/console/status")
        assert status == 401
    finally:
        await console.close()
        await server.stop()


async def test_console_delete_accounts_bulk():
    server = await make_server()
    console = Console(server)
    try:
        await console.login()
        from nakama_tpu.core.authenticate import authenticate_device

        for i in range(3):
            await authenticate_device(
                server.db, f"device-bulk-{i:06d}", None, True
            )
        status, body = await console.call(
            "DELETE", "/v2/console/account"
        )
        assert status == 200 and body["deleted"] == 3
        n = (await server.db.fetch_one(
            "SELECT COUNT(*) AS n FROM users"
        ))["n"]
        assert n == 0
    finally:
        await console.close()
        await server.stop()


async def test_ui_covers_every_console_route():
    """The embedded operator UI must reach every console rpc: the R
    route table in console/ui.py is parsed out of the page source and
    diffed method-for-method against the server's live route table
    (reference parity bar: the Angular app in console/ui.go covers the
    whole console surface)."""
    import re

    from nakama_tpu.console.ui import PAGE

    server = await make_server()
    try:
        ui_routes = {
            (m.group(1), m.group(2))
            for m in re.finditer(
                r"\['(GET|POST|PUT|DELETE)',\s*'(/v2/console[^']*)'\]",
                PAGE,
            )
        }
        server_routes = set()
        for route in server.console.app.router.routes():
            info = route.resource.canonical if route.resource else ""
            if not info.startswith("/v2/console"):
                continue  # "/" (the UI page itself)
            if route.method in ("HEAD", "OPTIONS", "*"):
                continue
            server_routes.add((route.method, info))
        missing = server_routes - ui_routes
        assert not missing, f"console rpcs unreachable from the UI: {missing}"
        phantom = ui_routes - server_routes
        assert not phantom, f"UI routes the server doesn't serve: {phantom}"
    finally:
        await server.stop()


async def test_ui_views_drive_their_endpoints():
    """Each UI view's primary data endpoints answer 200 for an operator
    session — the page's tabs are backed by living endpoints, not dead
    links."""
    server = await make_server()
    console = Console(server)
    try:
        await console.login()
        for method, path in [
            ("GET", "/v2/console/status"),
            ("GET", "/v2/console/runtime"),
            ("GET", "/v2/console/account?limit=50"),
            ("GET", "/v2/console/storage?limit=50"),
            ("GET", "/v2/console/storage/collections"),
            ("GET", "/v2/console/group?limit=50"),
            ("GET", "/v2/console/match"),
            ("GET", "/v2/console/matchmaker"),
            ("GET", "/v2/console/leaderboard"),
            ("GET", "/v2/console/purchase"),
            ("GET", "/v2/console/subscription"),
            ("GET", "/v2/console/user"),
            ("GET", "/v2/console/config"),
            ("GET", "/v2/console/api/endpoints"),
        ]:
            status, _ = await console.call(method, path)
            assert status == 200, f"{method} {path} -> {status}"
        # The page itself serves with every tab name present.
        async with console.http.get(console.base + "/") as r:
            page = await r.text()
            assert r.status == 200
            for tab in ("status", "accounts", "storage", "groups",
                        "matches", "matchmaker", "leaderboards", "chat",
                        "purchases", "users", "config", "explorer"):
                assert tab in page
    finally:
        await console.close()
        await server.stop()
