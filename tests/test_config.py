import pytest

from nakama_tpu.config import Config, config_to_dict, load_config, parse_args


def test_defaults_match_reference_envelope():
    cfg = Config()
    # Reference defaults: server/config.go:971-989.
    assert cfg.matchmaker.max_tickets == 3
    assert cfg.matchmaker.interval_sec == 15
    assert cfg.matchmaker.max_intervals == 2
    assert cfg.matchmaker.rev_precision is False
    assert cfg.match.input_queue_size == 128
    assert cfg.match.signal_queue_size == 10


def test_yaml_then_flags_precedence(tmp_path):
    p = tmp_path / "c.yml"
    p.write_text(
        "name: testnode\nmatchmaker:\n  interval_sec: 5\nsocket:\n  port: 8350\n"
    )
    cfg = load_config([str(p)], ["--matchmaker.interval_sec", "7", "--socket.server_key=k1"])
    assert cfg.name == "testnode"
    assert cfg.matchmaker.interval_sec == 7  # flag wins over file
    assert cfg.socket.port == 8350
    assert cfg.socket.server_key == "k1"


def test_unknown_yaml_key_rejected(tmp_path):
    p = tmp_path / "c.yml"
    p.write_text("nonsense: 1\n")
    with pytest.raises(ValueError):
        load_config([str(p)])


def test_empty_yaml_section_keeps_defaults(tmp_path):
    p = tmp_path / "c.yml"
    p.write_text("logger:\nname: x\n")
    cfg = load_config([str(p)])
    assert cfg.logger.level == "info"
    p.write_text("logger: 5\n")
    with pytest.raises(ValueError):
        load_config([str(p)])


def test_unknown_flag_is_value_error():
    with pytest.raises(ValueError, match="unknown config flag"):
        load_config(None, ["--sokcet.port", "1"])
    with pytest.raises(ValueError, match="missing value"):
        from nakama_tpu.config import parse_args as pa

        pa(["--config"])


def test_bool_and_list_flags():
    cfg = load_config(None, [
        "--matchmaker.rev_precision", "true",
        "--database.address", "a.db,b.db",
    ])
    assert cfg.matchmaker.rev_precision is True
    assert cfg.database.address == ["a.db", "b.db"]


def test_check_warnings_and_errors():
    cfg = Config()
    warnings = cfg.check()
    assert any("server_key" in w for w in warnings)
    cfg.console.port = cfg.socket.port
    with pytest.raises(ValueError):
        cfg.check()


def test_node_name_id_separator_rejected():
    """The node name is embedded in presence/ticket/match IDs with '.'
    as the separator — a hostile name like "evil.node" corrupts ID
    parsing at the clustering seam. check() must reject it loudly."""
    for bad in ("evil.node", "node name", "a/b", "naka:ma", "", "né"):
        cfg = Config()
        cfg.name = bad
        with pytest.raises(ValueError, match="name"):
            cfg.check()
    for good in ("n1", "node-2", "Node_3", "nakama-tpu"):
        cfg = Config()
        cfg.name = good
        cfg.check()  # no raise


def test_parse_args_hostname_fallback_sanitized():
    cfg = parse_args(["--name", ""])
    # Whatever the hostname was, the fallback must be ID-safe.
    import re

    assert re.fullmatch(r"[A-Za-z0-9_-]+", cfg.name)
    cfg.check()


def test_cluster_config_check():
    cfg = Config()
    cfg.cluster.enabled = True
    cfg.cluster.role = "frontend"
    cfg.cluster.peers = ["owner=127.0.0.1:7353"]
    with pytest.raises(ValueError, match="device_owner"):
        cfg.check()  # frontend must name the owner among its peers
    cfg.cluster.device_owner = "owner"
    cfg.check()
    cfg.cluster.peers = ["owner=127.0.0.1:7353", "owner=127.0.0.1:7354"]
    with pytest.raises(ValueError, match="unique"):
        cfg.check()
    cfg.cluster.peers = ["bad.name=127.0.0.1:7353"]
    with pytest.raises(ValueError, match="A-Za-z0-9"):
        cfg.check()
    cfg.cluster.peers = ["owner=127.0.0.1:7353"]
    cfg.cluster.down_after_ms = cfg.cluster.heartbeat_ms
    with pytest.raises(ValueError, match="down_after_ms"):
        cfg.check()


def test_cluster_shard_config_check():
    """Owner scale-out knobs: duplicate shard ids, a standby naming
    itself, and a lease grace below the heartbeat cadence must all be
    rejected loudly (the satellite's exact list)."""

    def base():
        cfg = Config()
        cfg.name = "f1"
        cfg.cluster.enabled = True
        cfg.cluster.role = "frontend"
        cfg.cluster.peers = [
            "o1=127.0.0.1:7353",
            "o2=127.0.0.1:7354",
            "sb=127.0.0.1:7355",
        ]
        cfg.cluster.shards = ["o1", "o2"]
        return cfg

    base().check()  # the sharded-frontend shape needs no device_owner
    cfg = base()
    cfg.cluster.shards = ["o1", "o1"]
    with pytest.raises(ValueError, match="duplicate shard"):
        cfg.check()
    cfg = base()
    cfg.cluster.shards = ["o1", "ghost"]
    with pytest.raises(ValueError, match="peer"):
        cfg.check()  # shard ids are the owner-fleet node names
    cfg = base()
    cfg.cluster.shards = ["o1", "bad.name"]
    with pytest.raises(ValueError, match="A-Za-z0-9"):
        cfg.check()
    # A standby must shadow a shard — never itself.
    cfg = base()
    cfg.name = "sb"
    cfg.cluster.role = "standby"
    cfg.cluster.peers = ["o1=127.0.0.1:7353", "o2=127.0.0.1:7354"]
    with pytest.raises(ValueError, match="standby_of"):
        cfg.check()  # standby role requires standby_of
    cfg.cluster.standby_of = "sb"
    with pytest.raises(ValueError, match="itself"):
        cfg.check()
    cfg.cluster.standby_of = "o3"
    with pytest.raises(ValueError, match="shard"):
        cfg.check()  # must name a shard id
    cfg.cluster.standby_of = "o1"
    cfg.check()
    # Lease knobs below the heartbeat cadence flap ownership.
    cfg = base()
    cfg.cluster.lease_grace_ms = cfg.cluster.heartbeat_ms - 1
    with pytest.raises(ValueError, match="lease_grace_ms"):
        cfg.check()
    cfg = base()
    cfg.cluster.lease_ms = cfg.cluster.heartbeat_ms - 1
    with pytest.raises(ValueError, match="lease_ms"):
        cfg.check()
    # Owner role must be part of the fleet it claims to own.
    cfg = base()
    cfg.name = "o3"
    cfg.cluster.role = "device_owner"
    cfg.cluster.peers = ["o1=127.0.0.1:7353", "o2=127.0.0.1:7354"]
    with pytest.raises(ValueError, match="shards"):
        cfg.check()


def test_cluster_reshard_config_check():
    """Elastic-topology knobs: the reshard bounds, the reserve-owner
    allowance, nested flag loading, and the planner trigger keys in
    cluster.obs_rules."""

    def base():
        cfg = Config()
        cfg.name = "o1"
        cfg.cluster.enabled = True
        cfg.cluster.role = "device_owner"
        cfg.cluster.peers = ["o2=127.0.0.1:7354", "o3=127.0.0.1:7355"]
        cfg.cluster.shards = ["o1", "o2"]
        return cfg

    # Defaults: disabled, serial migrations, sane budgets.
    cfg = Config()
    assert cfg.cluster.reshard.enabled is False
    assert cfg.cluster.reshard.drain_threshold_lsn == 16
    assert cfg.cluster.reshard.max_concurrent_migrations == 1
    assert cfg.cluster.reshard.handover_timeout_ms == 8000
    base().check()
    cfg = base()
    cfg.cluster.reshard.enabled = True
    cfg.check()
    # Enabled resharding needs a shard map to edit.
    cfg = base()
    cfg.cluster.shards = []
    cfg.cluster.reshard.enabled = True
    with pytest.raises(ValueError, match="requires cluster.shards"):
        cfg.check()
    # Bounds: drain >= 1, serial-only migrations, a handover budget
    # the heartbeat fold can actually meet.
    cfg = base()
    cfg.cluster.reshard.drain_threshold_lsn = 0
    with pytest.raises(ValueError, match="drain_threshold_lsn"):
        cfg.check()
    cfg = base()
    cfg.cluster.reshard.max_concurrent_migrations = 2
    with pytest.raises(ValueError, match="max_concurrent_migrations"):
        cfg.check()
    cfg = base()
    cfg.cluster.reshard.handover_timeout_ms = (
        cfg.cluster.heartbeat_ms - 1
    )
    with pytest.raises(ValueError, match="handover_timeout_ms"):
        cfg.check()
    # A reserve owner (outside the boot map) is only legal when the
    # elastic topology can hand it a shard.
    cfg = base()
    cfg.name = "o3"
    cfg.cluster.peers = ["o1=127.0.0.1:7353", "o2=127.0.0.1:7354"]
    with pytest.raises(ValueError, match="reserve"):
        cfg.check()
    cfg.cluster.reshard.enabled = True
    cfg.check()
    # The planner trigger thresholds ride cluster.obs_rules.
    cfg = base()
    cfg.cluster.obs_rules = [
        "reshard_skew_max=1.5",
        "reshard_hbm_max_bytes=2e9",
        "reshard_burn_1h_max=6",
    ]
    cfg.check()
    cfg.cluster.obs_rules = ["reshard_skew_max=hot"]
    with pytest.raises(ValueError, match="numeric"):
        cfg.check()
    cfg.cluster.obs_rules = ["reshard_bogus=1"]
    with pytest.raises(ValueError, match="reshard_skew_max"):
        cfg.check()
    # The section loads through the nested flag path.
    cfg = load_config([], [
        "--cluster.reshard.enabled", "true",
        "--cluster.reshard.drain_threshold_lsn", "32",
        "--cluster.reshard.handover_timeout_ms=4000",
    ])
    assert cfg.cluster.reshard.enabled is True
    assert cfg.cluster.reshard.drain_threshold_lsn == 32
    assert cfg.cluster.reshard.handover_timeout_ms == 4000


@pytest.mark.parametrize(
    "mesh_devices, mutate, error",
    [
        # 0 = single device, -1 = every visible device, n = a count the
        # host exposes that splits the capacity (conftest provisions 8
        # CPU devices).
        (0, None, None),
        (-1, None, None),
        (4, None, None),
        (8, None, None),
        (-2, None, "mesh_devices must be"),
        # More devices than the host exposes is a boot-time error, not
        # a first-dispatch surprise.
        (8192, None, "devices visible"),
        # The capacity must split into equal column shards.
        (3, None, "equal column shards"),
        (-1, lambda mm: setattr(mm, "pool_capacity", 1001),
         "equal column shards"),
        # The mesh path rides the pipelined gap: refuse sync intervals.
        (4, lambda mm: setattr(mm, "interval_pipelining", False),
         "interval_pipelining"),
        # Off, nothing of the mesh is checked.
        (0, lambda mm: setattr(mm, "interval_pipelining", False), None),
    ],
)
def test_mesh_devices_bounds(mesh_devices, mutate, error):
    cfg = Config()
    cfg.matchmaker.mesh_devices = mesh_devices
    if mutate is not None:
        mutate(cfg.matchmaker)
    if error is None:
        cfg.check()
    else:
        with pytest.raises(ValueError, match=error):
            cfg.check()


def test_deleted_matchmaker_options_are_refused():
    """The served path's options that nothing set are gone for good: a
    config file or flag that still names one fails at load, it is not
    silently ignored."""
    for key in (
        "delivery_event_driven", "device_pairing", "mesh_gather_k",
        "mesh_axis", "emb_score_scale",
    ):
        assert not hasattr(Config().matchmaker, key)
        with pytest.raises(ValueError, match="unknown config flag"):
            parse_args([f"--matchmaker.{key}=1"])
    assert not hasattr(Config(), "parallel")
    with pytest.raises(ValueError, match="unknown config flag"):
        parse_args(["--parallel.enabled=true"])


def test_parse_args_config_flag(tmp_path):
    p = tmp_path / "c.yml"
    p.write_text("name: n1\n")
    cfg = parse_args(["--config", str(p), "--console.port", "9999"])
    assert cfg.name == "n1"
    assert cfg.console.port == 9999


def test_redacted_dump():
    d = config_to_dict(Config(), redact=True)
    assert d["session"]["encryption_key"] == "***"
    assert d["socket"]["port"] == 7350
