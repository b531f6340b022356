"""Admin console: a second HTTP listener with its own auth.

Parity: reference server/console.go:167 StartConsoleServer — separate
port, own JWT signing key, authentication against the configured root
admin (config console.username/password) or `console_user` rows with
role-based access and login-attempt lockout (console_authenticate.go:73),
and the operator surface of the console_*.go handlers: account browse/
edit (profile + metadata + wallet replacement)/ban/export/delete, wallet
ledger view, storage browse/write/delete + bulk CSV/JSON import
(console_storage_import.go), group browse + member lists, match listing
+ live state view (match_registry GetState), leaderboard browse,
purchase browse, console-user management with role enforcement
(console_user.go), redacted config view + warnings, runtime info (loaded
modules + rpc ids), an RPC explorer, and a status snapshot fed by the
metrics registry (status_handler.go:64). The reference embeds an Angular
build (console/ui.go:24); here `/` serves a dependency-free operator
page over the same JSON API (console/ui.py).
"""

from __future__ import annotations

import json
import time

from aiohttp import web

from ..api import session_token
from ..core import authenticate as core_auth

ROLE_ADMIN = 1
ROLE_DEVELOPER = 2
ROLE_MAINTAINER = 3
ROLE_READONLY = 4

_REDACTED_KEYS = (
    "password", "key", "secret", "private", "token",
)


class ConsoleServer:
    def __init__(self, server):
        self.server = server
        self.config = server.config
        self.logger = server.logger.with_fields(subsystem="console")
        self.app = web.Application(
            client_max_size=self.config.console.max_message_size_bytes
        )
        self._runner = None
        self._site = None
        self.port: int | None = None
        self._started_at = time.time()
        # Console tokens revoked by AuthenticateLogout before expiry.
        self._revoked: set[str] = set()

        r = self.app.router
        self._metrics_runner = None
        self.metrics_port: int | None = None
        r.add_post("/v2/console/authenticate", self._h_authenticate)
        r.add_get("/v2/console/status", self._h_status)
        r.add_get("/v2/console/overload", self._h_overload)
        r.add_get("/v2/console/traces", self._h_traces)
        r.add_get("/v2/console/traces/{trace_id}", self._h_trace_get)
        r.add_get("/v2/console/config", self._h_config)
        r.add_get("/v2/console/runtime", self._h_runtime)
        r.add_get("/", self._h_ui)
        r.add_get("/v2/console/account", self._h_account_list)
        r.add_get("/v2/console/account/{id}", self._h_account_get)
        r.add_post("/v2/console/account/{id}", self._h_account_update)
        r.add_get(
            "/v2/console/account/{id}/wallet", self._h_account_wallet
        )
        r.add_post("/v2/console/account/{id}/ban", self._h_account_ban)
        r.add_post("/v2/console/account/{id}/unban", self._h_account_unban)
        r.add_delete("/v2/console/account/{id}", self._h_account_delete)
        r.add_get(
            "/v2/console/account/{id}/export", self._h_account_export
        )
        r.add_get("/v2/console/storage", self._h_storage_list)
        r.add_post("/v2/console/storage", self._h_storage_write)
        r.add_post(
            "/v2/console/storage/import", self._h_storage_import
        )
        r.add_get(
            "/v2/console/storage/{collection}/{key}/{user_id}",
            self._h_storage_get,
        )
        r.add_delete(
            "/v2/console/storage/{collection}/{key}/{user_id}",
            self._h_storage_delete,
        )
        r.add_get("/v2/console/match", self._h_match_list)
        r.add_get("/v2/console/matchmaker", self._h_matchmaker)
        r.add_get("/v2/console/cluster", self._h_cluster)
        r.add_get("/v2/console/fleet", self._h_fleet)
        r.add_post("/v2/console/fleet/reshard", self._h_fleet_reshard)
        r.add_get("/v2/console/fleet/traces", self._h_fleet_traces)
        r.add_get(
            "/v2/console/fleet/traces/{trace_id}",
            self._h_fleet_trace_get,
        )
        r.add_get("/v2/console/soak", self._h_soak)
        r.add_get("/v2/console/device", self._h_device)
        r.add_post("/v2/console/device/capture", self._h_device_capture)
        self._capture_busy = False
        r.add_get("/v2/console/match/{id}/state", self._h_match_state)
        r.add_get("/v2/console/leaderboard", self._h_leaderboard_list)
        r.add_get(
            "/v2/console/leaderboard/device", self._h_leaderboard_device
        )
        r.add_get(
            "/v2/console/leaderboard/{id}", self._h_leaderboard_records
        )
        r.add_get(
            "/v2/console/channel/{channel_id}", self._h_channel_messages
        )
        r.add_delete(
            "/v2/console/channel/{channel_id}/message/{message_id}",
            self._h_channel_message_delete,
        )
        r.add_delete(
            "/v2/console/leaderboard/{id}/owner/{owner_id}",
            self._h_leaderboard_record_delete,
        )
        r.add_get("/v2/console/group", self._h_group_list)
        r.add_get("/v2/console/group/{id}/member", self._h_group_members)
        r.add_get("/v2/console/purchase", self._h_purchase_list)
        r.add_get("/v2/console/user", self._h_console_user_list)
        r.add_post("/v2/console/user", self._h_console_user_create)
        r.add_delete(
            "/v2/console/user/{username}", self._h_console_user_delete
        )
        r.add_post("/v2/console/api/endpoints/rpc/{id}", self._h_call_rpc)
        # Round-4 parity routes (reference console.proto:57-139).
        r.add_post(
            "/v2/console/authenticate/logout", self._h_authenticate_logout
        )
        r.add_get("/v2/console/api/endpoints", self._h_list_endpoints)
        r.add_post("/v2/console/api/endpoints/call", self._h_call_endpoint)
        r.add_delete("/v2/console/all", self._h_delete_all_data)
        r.add_delete("/v2/console/account", self._h_delete_accounts)
        r.add_get(
            "/v2/console/account/{id}/friend", self._h_account_friends
        )
        r.add_delete(
            "/v2/console/account/{id}/friend/{friend_id}",
            self._h_account_friend_delete,
        )
        r.add_get(
            "/v2/console/account/{id}/group", self._h_account_groups
        )
        r.add_get(
            "/v2/console/account/{id}/walletledger",
            self._h_wallet_ledger,
        )
        r.add_delete(
            "/v2/console/account/{id}/walletledger/{ledger_id}",
            self._h_wallet_ledger_delete,
        )
        r.add_post(
            "/v2/console/account/{id}/unlink/{provider}",
            self._h_account_unlink,
        )
        r.add_get("/v2/console/storage/collections", self._h_collections)
        r.add_delete("/v2/console/storage", self._h_storage_delete_all)
        r.add_delete("/v2/console/message", self._h_messages_delete)
        r.add_get("/v2/console/subscription", self._h_subscription_list)
        r.add_get("/v2/console/group/{id}", self._h_group_get)
        r.add_post("/v2/console/group/{id}", self._h_group_update)
        r.add_delete("/v2/console/group/{id}", self._h_group_delete)
        r.add_get("/v2/console/group/{id}/export", self._h_group_export)
        r.add_post(
            "/v2/console/group/{id}/member", self._h_group_member_add
        )
        r.add_delete(
            "/v2/console/group/{id}/member/{user_id}",
            self._h_group_member_kick,
        )
        r.add_post(
            "/v2/console/group/{id}/member/{user_id}/promote",
            self._h_group_member_promote,
        )
        r.add_post(
            "/v2/console/group/{id}/member/{user_id}/demote",
            self._h_group_member_demote,
        )
        r.add_get(
            "/v2/console/leaderboard/{id}/detail", self._h_leaderboard_get
        )

    # ----------------------------------------------------------- lifecycle

    async def start(self, host: str, port: int) -> int:
        self._runner = web.AppRunner(self.app, access_log=None)
        await self._runner.setup()
        self._site = web.TCPSite(self._runner, host, port)
        await self._site.start()
        self.port = self._site._server.sockets[0].getsockname()[1]
        if self.config.metrics.prometheus_port:
            # Prometheus exposition on its own internal listener (the
            # reference serves scrape on a dedicated port and treats 0 as
            # disabled, server/metrics.go; unauthenticated by
            # scrape-tooling convention — isolate it by port/firewall).
            # prometheus_port=-1 binds an ephemeral port (tests).
            metrics_app = web.Application()
            metrics_app.router.add_get("/metrics", self._h_metrics)
            self._metrics_runner = web.AppRunner(
                metrics_app, access_log=None
            )
            await self._metrics_runner.setup()
            want = self.config.metrics.prometheus_port
            metrics_site = web.TCPSite(
                self._metrics_runner, host, 0 if want < 0 else want
            )
            await metrics_site.start()
            self.metrics_port = (
                metrics_site._server.sockets[0].getsockname()[1]
            )
        return self.port

    async def stop(self):
        if self._metrics_runner is not None:
            await self._metrics_runner.cleanup()
            self._metrics_runner = None
        if self._runner is not None:
            await self._runner.cleanup()
            self._runner = None

    # ---------------------------------------------------------------- auth

    async def _h_authenticate(self, request: web.Request):
        """Root admin from config, else console_user rows; failures feed
        the login-attempt lockout (reference console_authenticate.go:73)."""
        try:
            body = await request.json()
        except Exception:
            return _err(400, "invalid JSON body")
        username = body.get("username", "")
        password = body.get("password", "")
        attempts = self.server.login_attempt_cache
        client_ip = request.remote or ""
        if not attempts.allow(f"console:{username}", client_ip):
            return _err(429, "too many attempts, locked out")
        role = None
        if (
            username == self.config.console.username
            and password == self.config.console.password
        ):
            role = ROLE_ADMIN
        else:
            row = await self.server.db.fetch_one(
                "SELECT id, password, role, disable_time FROM console_user"
                " WHERE username = ?",
                (username,),
            )
            if (
                row is not None
                and not row["disable_time"]
                and core_auth.check_password(row["password"], password)
            ):
                role = row["role"]
        if role is None:
            attempts.add_failure(f"console:{username}", client_ip)
            return _err(401, "invalid credentials")
        attempts.reset(f"console:{username}")
        token, _ = session_token.generate(
            self.config.console.signing_key,
            username,
            username,
            self.config.console.token_expiry_sec,
            vars={"role": str(role)},
        )
        return web.json_response({"token": token, "role": role})

    def _auth(self, request: web.Request, write: bool = False) -> int:
        header = request.headers.get("Authorization", "")
        token = header[7:] if header.startswith("Bearer ") else ""
        if token in self._revoked:
            raise web.HTTPUnauthorized(
                text=json.dumps({"error": "token revoked"}),
                content_type="application/json",
            )
        try:
            claims = session_token.parse(
                self.config.console.signing_key, token
            )
        except session_token.TokenError:
            raise web.HTTPUnauthorized(
                text=json.dumps({"error": "console auth required"}),
                content_type="application/json",
            )
        role = int(claims.vars.get("role", ROLE_READONLY))
        if write and role > ROLE_MAINTAINER:
            raise web.HTTPForbidden(
                text=json.dumps({"error": "read-only console user"}),
                content_type="application/json",
            )
        return role

    # -------------------------------------------------------------- status

    async def _h_ui(self, request: web.Request):
        """Embedded operator UI (reference embeds an Angular build,
        console/ui.go:24; here one static page over the JSON API)."""
        from .ui import PAGE

        return web.Response(text=PAGE, content_type="text/html")

    async def _h_metrics(self, request: web.Request):
        return web.Response(
            body=self.server.metrics.scrape(),
            content_type="text/plain",
            charset="utf-8",
        )

    async def _h_status(self, request: web.Request):
        self._auth(request)
        s = self.server
        return web.json_response(
            {
                "name": self.config.name,
                "uptime_sec": time.time() - self._started_at,
                "sessions": len(s.session_registry.all()),
                "presences": s.tracker.count(),
                "matches": len(s.match_registry),
                "matchmaker_tickets": len(s.matchmaker),
                "overload_state": (
                    s.overload.stats()["state"]
                    if getattr(s, "overload", None) is not None
                    else "disabled"
                ),
                "slo_burn_rates": (
                    s.slo.sample()
                    if getattr(s, "slo", None) is not None
                    else {}
                ),
                "config_warnings": self.config.check(),
            }
        )

    async def _h_overload(self, request: web.Request):
        """Overload-plane dashboard: ladder state + per-signal levels,
        admission stats (inflight, queues, shed totals by class and
        reason), and the recent transition ledger — the operator's
        "why are we returning 429s" page."""
        self._auth(request)
        s = self.server
        ov = getattr(s, "overload", None)
        if ov is None:
            return web.json_response({"enabled": False})
        return web.json_response(
            {
                "enabled": True,
                **ov.stats(),
                "recent_transitions": (
                    s.matchmaker.tracing.recent_overload_events()
                ),
            }
        )

    async def _h_traces(self, request: web.Request):
        """Kept-trace browser: newest-first summaries from the
        tail-sampled in-process store, plus the sampling posture and
        SLO burn snapshot — the operator's "why was this add→matched
        3s" entry point; a single trace id drills in below."""
        self._auth(request)
        from ..tracing import TRACES

        raw = request.query.get("n", 32)
        try:
            n = min(256, max(1, int(raw)))
        except (TypeError, ValueError):
            # Same contract as the API's _limit clamp: a non-numeric
            # param is the client's 400, never our 500.
            return _err(400, f"n must be an integer, got {raw!r}")
        slo = getattr(self.server, "slo", None)
        return web.json_response(
            {
                "traces": TRACES.list(n),
                **TRACES.stats(),
                "slo": slo.snapshot() if slo is not None else {},
            }
        )

    async def _h_trace_get(self, request: web.Request):
        """One kept trace in the OTLP-ish shape (resourceSpans →
        scopeSpans → spans, attributes flattened)."""
        self._auth(request)
        from ..tracing import TRACES

        trace = TRACES.get(request.match_info["trace_id"])
        if trace is None:
            return _err(404, "trace not found (dropped or evicted)")
        return web.json_response(trace)

    async def _h_config(self, request: web.Request):
        """Config tree with secret redaction (reference
        console_config.go)."""
        self._auth(request)
        import dataclasses

        def scrub(obj):
            if dataclasses.is_dataclass(obj):
                out = {}
                for f in dataclasses.fields(obj):
                    value = getattr(obj, f.name)
                    if any(k in f.name.lower() for k in _REDACTED_KEYS) and (
                        isinstance(value, str) and value
                    ):
                        out[f.name] = "<redacted>"
                    else:
                        out[f.name] = scrub(value)
                return out
            if isinstance(obj, dict):
                return {k: scrub(v) for k, v in obj.items()}
            if isinstance(obj, (list, tuple)):
                return [scrub(v) for v in obj]
            return obj

        return web.json_response(scrub(self.config))

    async def _h_runtime(self, request: web.Request):
        self._auth(request)
        runtime = self.server.runtime
        return web.json_response(
            {
                "loaded": runtime is not None,
                "modules": list(runtime.modules) if runtime else [],
                "rpcs": runtime.rpc_ids() if runtime else [],
                "matches": runtime.match_names() if runtime else [],
            }
        )

    # ------------------------------------------------------------ accounts

    async def _h_account_list(self, request: web.Request):
        self._auth(request)
        q = request.query
        limit = max(1, min(int(q.get("limit", 50)), 100))
        filter_ = q.get("filter", "")
        params: list = []
        where = "WHERE 1=1"
        if filter_:
            where += " AND (id = ? OR username LIKE ?)"
            params.extend([filter_, f"{filter_}%"])
        rows = await self.server.db.fetch_all(
            f"SELECT id, username, display_name, create_time, disable_time"
            f" FROM users {where} ORDER BY create_time DESC LIMIT ?",
            (*params, limit),
        )
        return web.json_response(
            {
                "users": [dict(r) for r in rows],
                "total_count": (
                    await self.server.db.fetch_one(
                        "SELECT COUNT(*) AS n FROM users"
                    )
                )["n"],
            }
        )

    async def _h_account_get(self, request: web.Request):
        self._auth(request)
        from ..core import account as core_account

        try:
            account = await core_account.get_account(
                self.server.db, request.match_info["id"]
            )
        except core_auth.AuthError:
            return _err(404, "account not found")
        wallet = await self.server.wallets.get(request.match_info["id"])
        account["wallet"] = wallet
        return web.json_response(account)

    async def _h_account_update(self, request: web.Request):
        """Operator account edit (reference console_account.go
        UpdateAccount): profile fields, metadata, wallet replacement —
        each optional, absent leaves untouched."""
        self._auth(request, write=True)
        from ..core import account as core_account

        user_id = request.match_info["id"]
        try:
            body = await request.json()
        except Exception:
            return _err(400, "invalid JSON body")
        # Existence check up front: a wallet-only body would otherwise
        # slip past update_account's no-op early return and the 0-row
        # UPDATE, 200-ing an edit that never landed.
        exists = await self.server.db.fetch_one(
            "SELECT 1 FROM users WHERE id = ?", (user_id,)
        )
        if exists is None:
            return _err(404, "account not found")
        # Validate EVERYTHING before the first write — a rejected wallet
        # must not leave a half-applied profile edit.
        wallet = body.get("wallet")
        if "wallet" in body and not isinstance(wallet, dict):
            return _err(400, "wallet must be a JSON object")
        try:
            await core_account.update_account(
                self.server.db,
                user_id,
                username=body.get("username"),
                display_name=body.get("display_name"),
                timezone=body.get("timezone"),
                location=body.get("location"),
                lang_tag=body.get("lang_tag"),
                avatar_url=body.get("avatar_url"),
                metadata=body.get("metadata"),
            )
            if "wallet" in body:
                await self.server.db.execute(
                    "UPDATE users SET wallet = ? WHERE id = ?",
                    (json.dumps(wallet), user_id),
                )
        except Exception as e:
            # Existence was pre-checked: anything raised here is bad
            # input (e.g. invalid username), not not-found.
            return _err(400, str(e))
        return web.json_response({})

    async def _h_account_wallet(self, request: web.Request):
        """Wallet + ledger page (reference console_account.go
        GetWalletLedger)."""
        self._auth(request)
        user_id = request.match_info["id"]
        wallet = await self.server.wallets.get(user_id)
        items, cursor = await self.server.wallets.list_ledger(
            user_id,
            limit=int(request.query.get("limit", 100)),
            cursor=request.query.get("cursor", ""),
        )
        return web.json_response(
            {"wallet": wallet, "ledger": items, "cursor": cursor}
        )

    async def _h_account_ban(self, request: web.Request):
        self._auth(request, write=True)
        user_id = request.match_info["id"]
        await self.server.db.execute(
            "UPDATE users SET disable_time = ? WHERE id = ?",
            (time.time(), user_id),
        )
        self.server.session_cache.ban([user_id])
        return web.json_response({})

    async def _h_account_unban(self, request: web.Request):
        self._auth(request, write=True)
        user_id = request.match_info["id"]
        await self.server.db.execute(
            "UPDATE users SET disable_time = 0 WHERE id = ?", (user_id,)
        )
        self.server.session_cache.unban([user_id])
        return web.json_response({})

    async def _h_account_export(self, request: web.Request):
        """GDPR-style account export (reference ExportAccount via
        console_account.go)."""
        self._auth(request)
        from ..core import account as core_account

        try:
            export = await core_account.export_account(
                self.server.db, request.match_info["id"]
            )
        except core_auth.AuthError:
            return _err(404, "account not found")
        return web.json_response(export)

    async def _h_account_delete(self, request: web.Request):
        self._auth(request, write=True)
        from ..core import account as core_account

        await core_account.delete_account(
            self.server.db, request.match_info["id"], recorded=True
        )
        return web.json_response({})

    # ------------------------------------------------------------- storage

    async def _h_storage_list(self, request: web.Request):
        self._auth(request)
        q = request.query
        limit = max(1, min(int(q.get("limit", 50)), 100))
        params: list = []
        where = "WHERE 1=1"
        if q.get("collection"):
            where += " AND collection = ?"
            params.append(q["collection"])
        if q.get("user_id"):
            where += " AND user_id = ?"
            params.append(q["user_id"])
        rows = await self.server.db.fetch_all(
            f"SELECT collection, key, user_id, version, update_time"
            f" FROM storage {where} ORDER BY collection, key LIMIT ?",
            (*params, limit),
        )
        return web.json_response({"objects": [dict(r) for r in rows]})

    async def _h_storage_get(self, request: web.Request):
        self._auth(request)
        row = await self.server.db.fetch_one(
            "SELECT * FROM storage WHERE collection = ? AND key = ?"
            " AND user_id = ?",
            (
                request.match_info["collection"],
                request.match_info["key"],
                request.match_info["user_id"],
            ),
        )
        if row is None:
            return _err(404, "object not found")
        return web.json_response(dict(row))

    async def _h_storage_write(self, request: web.Request):
        """Operator storage write (reference console_storage.go
        WriteStorageObject): system-caller semantics, any owner."""
        self._auth(request, write=True)
        from ..core.storage import StorageOpWrite, storage_write_objects

        try:
            body = await request.json()
        except Exception:
            return _err(400, "invalid JSON body")
        value = body.get("value", "")
        if not isinstance(value, str):
            value = json.dumps(value)
        try:
            acks = await storage_write_objects(
                self.server.db,
                None,  # system caller: permission/ownership bypass
                [
                    StorageOpWrite(
                        collection=body.get("collection", ""),
                        key=body.get("key", ""),
                        user_id=body.get("user_id", ""),
                        value=value,
                        version=body.get("version", ""),
                        permission_read=int(
                            body.get("permission_read", 1)
                        ),
                        permission_write=int(
                            body.get("permission_write", 1)
                        ),
                    )
                ],
            )
        except Exception as e:
            return _err(400, str(e))
        import dataclasses

        return web.json_response(dataclasses.asdict(acks[0]))

    async def _h_storage_delete(self, request: web.Request):
        self._auth(request, write=True)
        from ..core.storage import (
            StorageOpDelete,
            storage_delete_objects,
        )

        try:
            await storage_delete_objects(
                self.server.db,
                None,
                [
                    StorageOpDelete(
                        collection=request.match_info["collection"],
                        key=request.match_info["key"],
                        user_id=request.match_info["user_id"],
                    )
                ],
            )
        except Exception as e:
            return _err(400, str(e))
        return web.json_response({})

    async def _h_storage_import(self, request: web.Request):
        """Bulk storage import, JSON array or CSV (reference
        console_storage_import.go: importStorage accepts both upload
        formats). JSON: a list of objects with collection/key/user_id/
        value[/permission_read/permission_write]. CSV: a header row
        naming those columns. Rows import in ONE transaction — an import
        either lands whole or not at all (reference behaviour)."""
        self._auth(request, write=True)
        from ..core.storage import StorageOpWrite, storage_write_objects

        raw = await request.text()
        ctype = request.content_type or ""
        rows: list[dict] = []
        try:
            if "csv" in ctype or (
                not raw.lstrip().startswith(("[", "{"))
            ):
                import csv as _csv
                import io as _io

                reader = _csv.DictReader(_io.StringIO(raw))
                for rec in reader:
                    rows.append(dict(rec))
            else:
                data = json.loads(raw)
                if not isinstance(data, list):
                    return _err(400, "JSON import must be an array")
                rows = data
        except Exception as e:
            return _err(400, f"unparseable import: {e}")
        ops = []
        try:
            for rec in rows:
                if not isinstance(rec, dict):
                    return _err(400, "import rows must be objects")
                value = rec.get("value", "")
                if not isinstance(value, str):
                    value = json.dumps(value)

                def perm(key: str) -> int:
                    # "" (CSV empty cell) and absent mean default 1;
                    # an explicit 0 must survive (private objects).
                    raw = rec.get(key)
                    if raw is None or raw == "":
                        return 1
                    return int(raw)

                ops.append(
                    StorageOpWrite(
                        collection=rec.get("collection", ""),
                        key=rec.get("key", ""),
                        user_id=rec.get("user_id", "") or "",
                        value=value,
                        permission_read=perm("permission_read"),
                        permission_write=perm("permission_write"),
                    )
                )
        except (TypeError, ValueError) as e:
            return _err(400, f"bad import row: {e}")
        if not ops:
            return _err(400, "no rows to import")
        try:
            acks = await storage_write_objects(self.server.db, None, ops)
        except Exception as e:
            return _err(400, str(e))
        return web.json_response({"imported": len(acks)})

    # ------------------------------------------------------------- matches

    async def _h_match_list(self, request: web.Request):
        self._auth(request)
        matches = self.server.match_registry.list_matches(
            int(request.query.get("limit", 100))
        )
        return web.json_response({"matches": matches})

    async def _h_matchmaker(self, request: web.Request):
        """Matchmaker observability: pool gauges, the per-interval device
        timing breadcrumbs (SURVEY §5), and the per-cohort delivery
        ledger with its per-stage attribution (dispatched→fetched→
        ready→collected→accepted→published) — a delivery-gap regression
        names its stage from this one endpoint."""
        self._auth(request)
        mm = self.server.matchmaker
        tracing = mm.tracing
        n = int(request.query.get("n", 32))
        return web.json_response(
            {
                "tickets": len(mm),
                "active": len(mm.active),
                "backend": type(mm.backend).__name__,
                "intervals": tracing.recent(n),
                "deliveries": tracing.recent_deliveries(n),
                "delivery_stages": tracing.delivery_stage_stats(),
                "ledger_totals": tracing.ledger_totals(),
            }
        )

    async def _h_cluster(self, request: web.Request):
        """Cluster posture: role, peer liveness, per-peer bus queue /
        breaker state, and (owner) pooled foreign tickets — "is the
        mesh of processes healthy" off one endpoint."""
        self._auth(request)
        cluster = getattr(self.server, "cluster", None)
        if cluster is None:
            return web.json_response({"enabled": False})
        mm = self.server.matchmaker
        tracker = self.server.tracker
        return web.json_response(
            {
                "enabled": True,
                "node": cluster.node,
                **cluster.stats(),
                "presences_local": (
                    tracker.count() - tracker.remote_count()
                    if hasattr(tracker, "remote_count")
                    else tracker.count()
                ),
                "presences_remote": (
                    tracker.remote_count()
                    if hasattr(tracker, "remote_count")
                    else 0
                ),
                "matchmaker_tickets": len(mm),
            }
        )

    async def _h_fleet(self, request: web.Request):
        """The fleet pane of glass (cluster/obs.py): every node's
        federated snapshot with staleness marked, the merged scenario
        SLO table, the shard/lease map, clock-offset estimates, and
        the health-rule engine's active alerts + OK/WARN/CRITICAL
        roll-up. Non-collector nodes answer with a pointer at the
        collector instead of a partial view."""
        self._auth(request)
        obs = getattr(self.server, "fleet_obs", None)
        if obs is None:
            return web.json_response({"enabled": False})
        return web.json_response(obs.console_fleet())

    async def _h_fleet_reshard(self, request: web.Request):
        """Operator-submitted reshard plan (split/merge/move): queued
        on the collector's planner, executed one migration at a time
        with the same journal/rollback posture as auto-planned work.
        Only the collector accepts plans — there is exactly one
        decision loop per fleet."""
        self._auth(request, write=True)
        obs = getattr(self.server, "fleet_obs", None)
        planner = getattr(obs, "planner", None) if obs is not None else None
        if planner is None:
            return _err(
                400,
                "reshard planner not running here (needs"
                " cluster.reshard.enabled and the collector role)",
            )
        try:
            body = await request.json()
        except Exception:
            return _err(400, "invalid JSON body")
        try:
            queued = planner.submit(dict(body))
        except (TypeError, ValueError) as e:
            return _err(400, f"plan refused: {e}")
        return web.json_response(queued)

    async def _h_fleet_traces(self, request: web.Request):
        """Stitched fleet traces: newest-first summaries from the
        collector's fragment store (origin nodes, stitched flag, span
        counts) plus the per-node fragment-feed ages the staleness
        marks derive from."""
        self._auth(request)
        obs = getattr(self.server, "fleet_obs", None)
        if obs is None:
            return web.json_response({"enabled": False})
        raw = request.query.get("n", 32)
        try:
            n = min(256, max(1, int(raw)))
        except (TypeError, ValueError):
            return _err(400, f"n must be an integer, got {raw!r}")
        return web.json_response(obs.console_traces(n))

    async def _h_fleet_trace_get(self, request: web.Request):
        """One stitched fleet trace: every span annotated with its
        origin node + clock-offset estimate, and the cross-node hops
        with per-hop bus latency."""
        self._auth(request)
        obs = getattr(self.server, "fleet_obs", None)
        if obs is None:
            return web.json_response({"enabled": False})
        tree = obs.console_trace_get(request.match_info["trace_id"])
        if tree is None:
            return _err(
                404,
                "fleet trace not found (evicted, never stitched, or"
                " this node is not the collector)",
            )
        return web.json_response(tree)

    async def _h_soak(self, request: web.Request):
        """Live soak posture (loadgen/): the open-loop session
        population counters and the per-scenario SLO table the judge
        gates on — the node's slice of the fleet verdict `bench.py
        --soak` merges."""
        self._auth(request)
        engine = getattr(self.server, "soak_engine", None)
        if engine is None:
            return web.json_response({"enabled": False})
        engine.judge.sample()
        return web.json_response(
            {
                "enabled": True,
                "sessions": engine.stats(),
                "slo_table": engine.judge.table(),
            }
        )

    async def _h_device(self, request: web.Request):
        """Device telemetry dashboard (devobs.py): per-kernel clocks +
        compile-watch counters, memory by owner with the backend
        cross-check, transfer counters per call site, the mesh
        occupancy view, and the recent kernel-event timeline — "where
        did this interval's device time go" off one endpoint."""
        self._auth(request)
        from ..devobs import DEVOBS
        from ..parallel.mesh import describe_mesh

        backend = self.server.matchmaker.backend
        try:
            n = min(256, max(1, int(request.query.get("n", 64))))
        except (TypeError, ValueError):
            return _err(400, "n must be an integer")
        if backend.mesh is None:
            mesh = describe_mesh()
        else:
            mesh = describe_mesh(
                backend.mesh,
                pool_capacity=backend.pool.capacity,
                pool=backend.pool.device,
                gather_bytes=backend.mesh_gather_bytes,
            )
        return web.json_response(
            {
                **DEVOBS.stats(),
                # Where the matchmaker's kernels really run: platform,
                # and whether Pallas is interpreting (None = host oracle).
                "backend": backend.describe(),
                "mesh": mesh,
                "timeline": DEVOBS.recent_timeline(n),
            }
        )

    async def _h_device_capture(self, request: web.Request):
        """On-demand bounded jax.profiler capture — the console wiring
        Tracing.device_trace's docstring promised. One capture at a
        time; duration clamped to config.devobs.capture_max_ms; output
        lands under data_dir/device_captures (view with
        `tensorboard --logdir <path>` / xprof)."""
        self._auth(request, write=True)
        import asyncio
        import os

        try:
            body = await request.json()
        except Exception:
            body = {}
        try:
            duration_ms = int(body.get("duration_ms", 1000))
        except (TypeError, ValueError):
            return _err(400, "duration_ms must be an integer")
        cap = self.config.devobs.capture_max_ms
        duration_ms = min(max(50, duration_ms), cap)
        if self._capture_busy:
            return _err(409, "a device capture is already running")
        tracing = self.server.matchmaker.tracing
        out_dir = os.path.join(
            self.config.data_dir,
            "device_captures",
            time.strftime("%Y%m%d-%H%M%S"),
        )
        os.makedirs(out_dir, exist_ok=True)
        self._capture_busy = True
        try:
            with tracing.device_trace(out_dir):
                # The profiler records process-wide: whatever device
                # work the workloads run inside this bounded window is
                # the capture.
                await asyncio.sleep(duration_ms / 1000.0)
        except Exception as e:
            return _err(503, f"device capture failed: {e}")
        finally:
            self._capture_busy = False
        self.logger.info(
            "device capture written",
            path=out_dir,
            duration_ms=duration_ms,
        )
        return web.json_response(
            {"path": out_dir, "duration_ms": duration_ms}
        )

    async def _h_match_state(self, request: web.Request):
        """Live authoritative match state (reference console match view via
        MatchRegistry GetState, match_registry.go:123)."""
        self._auth(request)
        state = self.server.match_registry.get_state(
            request.match_info["id"]
        )
        if state is None:
            return _err(404, "match not found")
        state_json, tick, presence_count = state
        return web.json_response(
            {
                "state": state_json,
                "tick": tick,
                "presences": presence_count,
            }
        )

    # -------------------------------------------- leaderboards / purchases

    async def _h_leaderboard_list(self, request: web.Request):
        self._auth(request)
        return web.json_response(
            {
                "leaderboards": [
                    lb.as_dict()
                    for lb in self.server.leaderboards.list(
                        with_tournaments=True
                    )
                ]
            }
        )

    async def _h_leaderboard_device(self, request: web.Request):
        """Device rank-engine dashboard: breaker state, adopted boards
        with their staging/flush posture, read/fallback ledger."""
        self._auth(request)
        engine = self.server.leaderboards.device
        if engine is None:
            return web.json_response({"enabled": False, "boards": []})
        return web.json_response(engine.stats())

    async def _h_leaderboard_records(self, request: web.Request):
        self._auth(request)
        try:
            result = await self.server.leaderboards.records_list(
                request.match_info["id"],
                limit=int(request.query.get("limit", 100)),
            )
        except Exception as e:
            return _err(404, str(e))
        return web.json_response(result)

    async def _h_purchase_list(self, request: web.Request):
        self._auth(request)
        return web.json_response(
            await self.server.purchases.list(
                user_id=request.query.get("user_id") or None,
                limit=int(request.query.get("limit", 100)),
            )
        )

    # --------------------------------------------------------------- rpc

    async def _h_channel_messages(self, request: web.Request):
        """Message browse for any channel (reference console.proto
        ListChannelMessages)."""
        self._auth(request)
        from ..api.http import _parse_bool
        from ..core.channel import ChannelError

        try:
            result = await self.server.channels.messages_list(
                request.match_info["channel_id"],
                limit=int(request.query.get("limit", 100)),
                forward=_parse_bool(request.query.get("forward", True)),
                cursor=request.query.get("cursor", ""),
            )
        except ChannelError as e:
            return _err(400, str(e))
        return web.json_response(result)

    async def _h_channel_message_delete(self, request: web.Request):
        """Operator message removal (reference console.proto
        DeleteChannelMessages): through the channel core so the message
        must belong to the named channel and live subscribers get the
        MSG_CHAT_REMOVE broadcast — only the sender gate is bypassed."""
        self._auth(request, write=True)
        from ..core.channel import ChannelError

        try:
            await self.server.channels.message_remove(
                request.match_info["channel_id"],
                request.match_info["message_id"],
                authoritative=True,
            )
        except ChannelError as e:
            status = 404 if e.code == "not_found" else 400
            return _err(status, str(e))
        return web.json_response({})

    async def _h_leaderboard_record_delete(self, request: web.Request):
        """Operator record removal (reference console.proto
        DeleteLeaderboardRecord) — authoritative caller."""
        self._auth(request, write=True)
        from ..leaderboard import LeaderboardError

        try:
            deleted = await self.server.leaderboards.record_delete(
                request.match_info["id"],
                request.match_info["owner_id"],
                caller_authoritative=True,
            )
        except LeaderboardError as e:
            return _err(404, str(e))
        if not deleted:
            return _err(404, "record not found")
        return web.json_response({})

    async def _h_group_list(self, request: web.Request):
        """Group browse (reference console_group.go ListGroups)."""
        self._auth(request)
        q = request.query
        result = await self.server.groups.list(
            name=q.get("name") or None,
            limit=int(q.get("limit", 100)),
            cursor=q.get("cursor", ""),
        )
        return web.json_response(result)

    async def _h_group_members(self, request: web.Request):
        self._auth(request)
        from ..core.group import GroupError

        try:
            result = await self.server.groups.users_list(
                request.match_info["id"],
                limit=int(request.query.get("limit", 100)),
                cursor=request.query.get("cursor", ""),
            )
        except GroupError as e:
            return _err(404, str(e))
        return web.json_response(result)

    # -------------------------------------------------------- console users

    async def _h_console_user_list(self, request: web.Request):
        self._auth(request)
        rows = await self.server.db.fetch_all(
            "SELECT username, email, role, create_time, disable_time"
            " FROM console_user ORDER BY username"
        )
        return web.json_response({"users": [dict(r) for r in rows]})

    async def _h_console_user_create(self, request: web.Request):
        """Operator account provisioning (reference console_user.go
        AddUser): admin-only."""
        role = self._auth(request, write=True)
        if role != ROLE_ADMIN:
            return _err(403, "admin role required")
        try:
            body = await request.json()
        except Exception:
            return _err(400, "invalid JSON body")
        username = body.get("username", "")
        password = body.get("password", "")
        if not username or len(password) < 8:
            return _err(
                400, "username and password (>= 8 chars) required"
            )
        try:
            new_role = int(body.get("role", ROLE_READONLY))
        except (TypeError, ValueError):
            return _err(400, "invalid role")
        if new_role not in (
            ROLE_ADMIN, ROLE_DEVELOPER, ROLE_MAINTAINER, ROLE_READONLY
        ):
            return _err(400, "invalid role")
        import uuid as _uuid

        from ..storage.db import UniqueViolationError

        try:
            await self.server.db.execute(
                "INSERT INTO console_user (id, username, email, password,"
                " role, create_time, update_time, disable_time)"
                " VALUES (?, ?, ?, ?, ?, ?, ?, 0)",
                (
                    str(_uuid.uuid4()),
                    username,
                    # email is NOT NULL UNIQUE; synthesize one if absent
                    # so two email-less operators don't collide on "".
                    body.get("email") or f"{username}@console.local",
                    core_auth.hash_password(password),
                    new_role,
                    time.time(),
                    time.time(),
                ),
            )
        except UniqueViolationError:
            return _err(409, "username already exists")
        return web.json_response({"username": username, "role": new_role})

    async def _h_console_user_delete(self, request: web.Request):
        role = self._auth(request, write=True)
        if role != ROLE_ADMIN:
            return _err(403, "admin role required")
        n = await self.server.db.execute(
            "DELETE FROM console_user WHERE username = ?",
            (request.match_info["username"],),
        )
        if not n:
            return _err(404, "console user not found")
        return web.json_response({})

    async def _h_call_rpc(self, request: web.Request):
        """API explorer: invoke any registered RPC as the console
        (reference console_api_explorer.go)."""
        self._auth(request, write=True)
        runtime = self.server.runtime
        if runtime is None:
            return _err(501, "runtime not loaded")
        fn = runtime.rpc(request.match_info["id"].lower())
        if fn is None:
            return _err(404, "rpc not found")
        payload = await request.text()
        import asyncio

        try:
            result = fn(runtime.context(mode="console"), payload)
            if asyncio.iscoroutine(result):
                result = await result
        except Exception as e:
            return _err(500, str(e))
        return web.json_response({"payload": result or ""})


    # ------------------------------------------- round-4 parity handlers

    async def _h_authenticate_logout(self, request: web.Request):
        """Invalidate the presented console token (reference
        AuthenticateLogout, console.proto): stateless JWTs get a
        revocation set checked by _auth."""
        self._auth(request)
        header = request.headers.get("Authorization", "")
        token = header[7:] if header.startswith("Bearer ") else ""
        self._revoked.add(token)
        if len(self._revoked) > 4096:
            # Prune EXPIRED revocations only — clearing the set would
            # un-revoke live tokens and silently undo earlier logouts.
            live = set()
            for t in self._revoked:
                try:
                    session_token.parse(self.config.console.signing_key, t)
                except session_token.TokenError:
                    continue  # expired/invalid: safe to forget
                live.add(t)
            self._revoked = live
        return web.json_response({})

    async def _h_list_endpoints(self, request: web.Request):
        """Every REST endpoint of the main API listener (reference
        ListApiEndpoints feeding the console explorer,
        console_api_explorer.go)."""
        self._auth(request)
        endpoints = []
        for route in self.server.api.app.router.routes():
            info = route.resource.get_info() if route.resource else {}
            path = info.get("path") or info.get("formatter") or ""
            if route.method in ("HEAD", "OPTIONS") or not path:
                continue
            endpoints.append({"method": route.method, "path": path})
        runtime = self.server.runtime
        return web.json_response(
            {
                "endpoints": sorted(
                    endpoints, key=lambda e: (e["path"], e["method"])
                ),
                "rpc_endpoints": runtime.rpc_ids() if runtime else [],
            }
        )

    async def _h_call_endpoint(self, request: web.Request):
        """Invoke ANY api endpoint through the real API listener
        (reference CallApiEndpoint, console_api_explorer.go): the console
        operator supplies method/path/body, optionally a user_id the call
        should act as — a short-lived session token is minted for it."""
        self._auth(request, write=True)
        try:
            body = await request.json()
        except Exception:
            return _err(400, "invalid JSON body")
        method = str(body.get("method", "GET")).upper()
        path = str(body.get("path", ""))
        if not path.startswith("/v2/") or path.startswith("/v2/console"):
            return _err(400, "path must be a /v2/ api endpoint")
        headers = {}
        user_id = body.get("user_id", "")
        if user_id:
            row = await self.server.db.fetch_one(
                "SELECT username FROM users WHERE id = ?", (user_id,)
            )
            if row is None:
                return _err(404, "user not found")
            token, claims = session_token.generate(
                self.config.session.encryption_key,
                user_id,
                row["username"],
                60,
            )
            # Register with the session cache or the API's validity
            # check rejects the minted token.
            self.server.session_cache.add(
                user_id, claims.expires_at, claims.token_id
            )
            headers["Authorization"] = f"Bearer {token}"
        elif body.get("server_key_auth", True):
            import base64 as _b64

            key = self.config.socket.server_key
            headers["Authorization"] = "Basic " + _b64.b64encode(
                f"{key}:".encode()
            ).decode()
        import aiohttp

        url = f"http://127.0.0.1:{self.server.port}{path}"
        async with aiohttp.ClientSession() as http:
            async with http.request(
                method,
                url,
                params=body.get("query") or None,
                json=body.get("body") if body.get("body") is not None
                else None,
                headers=headers,
            ) as resp:
                text = await resp.text()
        return web.json_response({"status": resp.status, "body": text})

    async def _h_delete_all_data(self, request: web.Request):
        """Wipe every domain table (reference DeleteAllData,
        console.proto:135) — console users and migration history remain;
        in-RAM state (leaderboard caches, matchmaker pool, sessions) is
        reset to match."""
        self._auth(request, write=True)
        tables = (
            "user_edge", "user_device", "notification", "storage",
            "message", "leaderboard_record", "leaderboard",
            "wallet_ledger", "user_tombstone", "group_edge", "groups",
            "purchase", "purchase_receipt", "subscription", "users",
        )
        for t in tables:
            await self.server.db.execute(f"DELETE FROM {t}")
        self.server.leaderboards.clear_rank_state()
        await self.server.leaderboards.load()
        self.server.matchmaker.remove_all(self.server.matchmaker.node)
        # Deleted users' bearer tokens must die with their rows.
        self.server.session_cache.clear()
        for s in self.server.session_registry.all():
            await s.close("data deleted")
        return web.json_response({})

    async def _h_delete_accounts(self, request: web.Request):
        """Delete ALL user accounts (reference DeleteAccounts,
        console.proto:180)."""
        self._auth(request, write=True)
        from ..core import account as core_account

        rows = await self.server.db.fetch_all("SELECT id FROM users")
        for r in rows:
            await core_account.delete_account(
                self.server.db, r["id"], recorded=False
            )
        return web.json_response({"deleted": len(rows)})

    async def _h_account_friends(self, request: web.Request):
        """A user's friend list (reference GetFriends,
        console.proto:230)."""
        self._auth(request)
        result = await self.server.friends.list(
            request.match_info["id"], limit=100
        )
        return web.json_response(result)

    async def _h_account_friend_delete(self, request: web.Request):
        self._auth(request, write=True)
        await self.server.friends.delete(
            request.match_info["id"], request.match_info["friend_id"]
        )
        return web.json_response({})

    async def _h_account_groups(self, request: web.Request):
        """A user's group memberships (reference GetGroups,
        console.proto:245)."""
        self._auth(request)
        result = await self.server.groups.user_groups_list(
            request.match_info["id"], limit=100
        )
        return web.json_response(result)

    async def _h_wallet_ledger(self, request: web.Request):
        """Dedicated ledger window (reference GetWalletLedger,
        console.proto:275)."""
        self._auth(request)
        items, cursor = await self.server.wallets.list_ledger(
            request.match_info["id"],
            limit=int(request.query.get("limit", 100)),
            cursor=request.query.get("cursor", ""),
        )
        return web.json_response({"items": items, "cursor": cursor})

    async def _h_wallet_ledger_delete(self, request: web.Request):
        """Remove one ledger entry (reference DeleteWalletLedger,
        console.proto:200) — the wallet itself is untouched."""
        self._auth(request, write=True)
        n = await self.server.db.execute(
            "DELETE FROM wallet_ledger WHERE id = ? AND user_id = ?",
            (
                request.match_info["ledger_id"],
                request.match_info["id"],
            ),
        )
        if not n:
            return _err(404, "ledger item not found")
        return web.json_response({})

    async def _h_account_unlink(self, request: web.Request):
        """Per-provider unlink on behalf of a user (reference console
        UnlinkApple..UnlinkSteam, console.proto:119-139)."""
        self._auth(request, write=True)
        from ..core import link as core_link

        user_id = request.match_info["id"]
        provider = request.match_info["provider"]
        fns = {
            "device": None,  # needs the device id from the body
            "email": core_link.unlink_email,
            "custom": core_link.unlink_custom,
            "apple": core_link.unlink_apple,
            "facebook": core_link.unlink_facebook,
            "facebookinstantgame": core_link.unlink_facebook_instant,
            "gamecenter": core_link.unlink_gamecenter,
            "google": core_link.unlink_google,
            "steam": core_link.unlink_steam,
        }
        if provider not in fns:
            return _err(400, "unknown provider")
        try:
            if provider == "device":
                try:
                    body = await request.json()
                except Exception:
                    body = {}
                device_id = body.get("device_id", "")
                if not device_id:
                    return _err(400, "device_id required")
                await core_link.unlink_device(
                    self.server.db, user_id, device_id
                )
            else:
                await fns[provider](self.server.db, user_id)
        except Exception as e:
            return _err(400, str(e))
        return web.json_response({})

    async def _h_collections(self, request: web.Request):
        """Distinct storage collections (reference ListStorageCollections,
        console.proto:300)."""
        self._auth(request)
        rows = await self.server.db.fetch_all(
            "SELECT DISTINCT collection FROM storage ORDER BY collection"
        )
        return web.json_response(
            {"collections": [r["collection"] for r in rows]}
        )

    async def _h_storage_delete_all(self, request: web.Request):
        """Wipe the whole object store (reference DeleteStorage,
        console.proto:165)."""
        self._auth(request, write=True)
        await self.server.db.execute("DELETE FROM storage")
        return web.json_response({})

    async def _h_messages_delete(self, request: web.Request):
        """Bulk chat-message deletion by id, or everything before a
        timestamp (reference DeleteChannelMessages, console.proto:145)."""
        self._auth(request, write=True)
        try:
            body = await request.json()
        except Exception:
            body = {}
        ids = body.get("ids") or []
        before = body.get("before")
        if before is not None:
            try:
                before = float(before)
            except (TypeError, ValueError):
                return _err(400, "before must be epoch seconds")
        total = 0
        if ids:
            # Chunked IN-clause: one write transaction per chunk, not one
            # per id — bulk deletes must not serialize thousands of
            # commits onto the single-writer engine.
            ids = [str(m) for m in ids]
            for i in range(0, len(ids), 256):
                chunk = ids[i : i + 256]
                marks = ",".join("?" * len(chunk))
                total += await self.server.db.execute(
                    f"DELETE FROM message WHERE id IN ({marks})",
                    tuple(chunk),
                )
        if before is not None:
            total += await self.server.db.execute(
                "DELETE FROM message WHERE create_time < ?",
                (before,),
            )
        return web.json_response({"total": total})

    async def _h_subscription_list(self, request: web.Request):
        """Validated subscriptions, store-wide or per user (reference
        ListSubscriptions, console.proto:330)."""
        self._auth(request)
        q = request.query
        result = await self.server.purchases.list_subscriptions(
            q.get("user_id", ""),
            limit=int(q.get("limit", 100)),
            cursor=q.get("cursor", ""),
        )
        return web.json_response(result)

    async def _h_group_get(self, request: web.Request):
        self._auth(request)
        try:
            group = await self.server.groups.get(request.match_info["id"])
        except Exception:
            return _err(404, "group not found")
        return web.json_response(group)

    async def _h_group_update(self, request: web.Request):
        """Operator group edit (reference console UpdateGroup)."""
        self._auth(request, write=True)
        try:
            body = await request.json()
        except Exception:
            return _err(400, "invalid JSON body")
        try:
            await self.server.groups.update(
                request.match_info["id"],
                caller_id="",  # console is authoritative
                name=body.get("name"),
                description=body.get("description"),
                avatar_url=body.get("avatar_url"),
                lang_tag=body.get("lang_tag"),
                metadata=body.get("metadata"),
                open=body.get("open"),
                max_count=body.get("max_count"),
            )
        except Exception as e:
            return _err(400, str(e))
        return web.json_response({})

    async def _h_group_delete(self, request: web.Request):
        self._auth(request, write=True)
        try:
            await self.server.groups.delete(
                request.match_info["id"], caller_id=""
            )
        except Exception as e:
            return _err(404, str(e))
        return web.json_response({})

    async def _h_group_export(self, request: web.Request):
        """Group + full member list in one document (reference
        ExportGroup, console.proto:215)."""
        self._auth(request)
        gid = request.match_info["id"]
        try:
            group = await self.server.groups.get(gid)
        except Exception:
            return _err(404, "group not found")
        # Full member list: walk every page (an export must not truncate).
        members: list = []
        cursor = ""
        while True:
            page = await self.server.groups.users_list(
                gid, limit=1000, cursor=cursor
            )
            members.extend(page.get("group_users", []))
            cursor = page.get("cursor", "")
            if not cursor:
                break
        return web.json_response({"group": group, "members": members})

    async def _h_group_member_add(self, request: web.Request):
        """Console AddGroupUsers: direct member admission."""
        self._auth(request, write=True)
        try:
            body = await request.json()
        except Exception:
            return _err(400, "invalid JSON body")
        ids = body.get("user_ids") or []
        if not ids:
            return _err(400, "user_ids required")
        try:
            await self.server.groups.users_add(
                request.match_info["id"], ids, caller_id=""
            )
        except Exception as e:
            return _err(400, str(e))
        return web.json_response({})

    async def _h_group_member_kick(self, request: web.Request):
        """Console DeleteGroupUser."""
        self._auth(request, write=True)
        try:
            await self.server.groups.users_kick(
                request.match_info["id"],
                [request.match_info["user_id"]],
                caller_id="",
            )
        except Exception as e:
            return _err(400, str(e))
        return web.json_response({})

    async def _h_group_member_promote(self, request: web.Request):
        self._auth(request, write=True)
        try:
            await self.server.groups.users_promote(
                request.match_info["id"],
                [request.match_info["user_id"]],
                caller_id="",
            )
        except Exception as e:
            return _err(400, str(e))
        return web.json_response({})

    async def _h_group_member_demote(self, request: web.Request):
        self._auth(request, write=True)
        try:
            await self.server.groups.users_demote(
                request.match_info["id"],
                [request.match_info["user_id"]],
                caller_id="",
            )
        except Exception as e:
            return _err(400, str(e))
        return web.json_response({})

    async def _h_leaderboard_get(self, request: web.Request):
        """One board definition (reference GetLeaderboard,
        console.proto:250)."""
        self._auth(request)
        lb = self.server.leaderboards.get(request.match_info["id"])
        if lb is None:
            return _err(404, "leaderboard not found")
        return web.json_response(lb.as_dict())


def _err(status: int, message: str):
    return web.json_response({"error": message}, status=status)
