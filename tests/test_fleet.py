"""Multi-host fleet plane (ISSUE 17): the --fleet grammar and static
topology rules (jax-free), the TAG_SNAPSHOT policy publication path
(bit-exactness, version skew, truncation robustness — the
tests/test_shm_transport.py contract style), and the control plane
over real sockets (heartbeat health folding, host loss vs the
--min_live_hosts floor, synchronous parameter composition)."""

import socket
import threading
import time

import numpy as np
import pytest

from torchbeast_tpu.fleet import (
    FleetCoordinator,
    FleetSpec,
    apply_snapshot,
    build_snapshot,
    compose_fleet_mesh_devices,
    parse_fleet_spec,
)
from torchbeast_tpu.fleet.topology import CONTROL_PORT_OFFSET
from torchbeast_tpu.resilience.supervisor import (
    DEGRADED,
    HALTED,
    HEALTHY,
    PipelineHealth,
)
from torchbeast_tpu.runtime import wire
from torchbeast_tpu.runtime.placement import fleet_host_for_slot
from torchbeast_tpu.serving.snapshot import PolicySnapshotStore
from torchbeast_tpu.telemetry import MetricsRegistry


# ---------------------------------------------------------------------------
# --fleet grammar


def test_parse_fleet_spec_roundtrip():
    spec = parse_fleet_spec("host=1/4,coord=10.0.0.1:8476")
    assert spec == FleetSpec(1, 4, "10.0.0.1:8476")
    assert not spec.is_lead
    assert parse_fleet_spec("host=0/1,coord=h:2").is_lead
    # Whitespace and ordering are forgiven; meaning is not.
    assert parse_fleet_spec(" coord=h:9 , host=2/3 ") == FleetSpec(
        2, 3, "h:9"
    )


def test_parse_fleet_spec_unset_means_single_host():
    assert parse_fleet_spec(None) is None
    assert parse_fleet_spec("") is None
    assert parse_fleet_spec("   ") is None


@pytest.mark.parametrize("bad", [
    "host=1/4",                      # no coord
    "coord=h:1",                     # no host
    "host=1/4,coord=h:1,host=2/4",   # repeated key
    "host=14,coord=h:1",             # rank not <rank>/<n>
    "host=a/b,coord=h:1",            # non-integer rank
    "host=4/4,coord=h:1",            # rank out of range
    "host=-1/4,coord=h:1",           # negative rank
    "host=0/0,coord=h:1",            # zero hosts
    "host=0/2,coord=nope",           # coord not host:port
    "host=0/2,coord=:123",           # empty host
    "host=0/2,coord=h:port",         # non-integer port
    "host=0/2,coord=h:65535",        # port+1 would not exist
    "host=0/2,coord=h:0",            # port 0
    "host=0/2,clock=h:1",            # unknown key
    "host 0/2",                      # not key=value
])
def test_parse_fleet_spec_rejects(bad):
    with pytest.raises(ValueError):
        parse_fleet_spec(bad)


def test_control_address_is_coord_port_plus_one():
    spec = parse_fleet_spec("host=0/2,coord=10.0.0.1:8476")
    assert spec.control_address == f"10.0.0.1:{8476 + CONTROL_PORT_OFFSET}"
    d = spec.describe()
    assert d["coord"] == "10.0.0.1:8476"
    assert d["control"] == "10.0.0.1:8477"


# ---------------------------------------------------------------------------
# Static actor -> host assignment


def test_fleet_host_for_slot_static_and_in_range():
    for n in (1, 2, 3, 5):
        for slot in range(64):
            h = fleet_host_for_slot(slot, n)
            assert 0 <= h < n
            assert h == fleet_host_for_slot(slot, n)  # process-stable


def test_slots_partition_exactly_across_hosts():
    n_hosts, n_slots = 3, 256
    specs = [FleetSpec(r, n_hosts, "h:1") for r in range(n_hosts)]
    owned = [spec.slots_for_host(n_slots) for spec in specs]
    seen = [s for slots in owned for s in slots]
    assert sorted(seen) == list(range(n_slots))  # disjoint AND covering
    # Salted splitmix64, not round-robin: every host gets a working
    # share (the split can be uneven, but never starves a host).
    assert all(len(slots) > n_slots // (n_hosts * 4) for slots in owned)


def test_fleet_host_hash_decorrelated_from_modulo():
    n_hosts = 2
    assignment = [fleet_host_for_slot(s, n_hosts) for s in range(256)]
    # A salted hash must not reduce to slot % n (which would pile every
    # host's slots onto the same env-server stripe).
    assert assignment != [s % n_hosts for s in range(256)]


# ---------------------------------------------------------------------------
# Mesh composition


class FakeDevice:
    def __init__(self, host, idx):
        self.process_index = host
        self.id = host * 100 + idx

    def __repr__(self):
        return f"dev(h{self.process_index}/{self.id})"


def _fake_fleet_devices(n_hosts, per_host):
    return [
        FakeDevice(h, i) for h in range(n_hosts) for i in range(per_host)
    ]


def test_compose_fleet_mesh_is_host_major():
    fleet = FleetSpec(1, 2, "h:1")
    devices = _fake_fleet_devices(2, 2)
    split, learners = compose_fleet_mesh_devices(
        fleet, "inf=1,learn=rest", devices
    )
    # Each host's split reserves its device 0 for inference; the global
    # learner group is host-major: host 0's learner devices then host 1's.
    assert [d.id for d in learners] == [1, 101]
    assert [d.id for d in split.learner_devices] == [101]
    assert [d.id for d in split.inference_devices] == [100]


def test_compose_fleet_mesh_no_split_whole_hosts_learn():
    fleet = FleetSpec(0, 2, "h:1")
    devices = _fake_fleet_devices(2, 2)
    split, learners = compose_fleet_mesh_devices(fleet, "", devices)
    assert split is None
    assert [d.id for d in learners] == [0, 1, 100, 101]


def test_compose_fleet_mesh_rejects_ragged_and_empty_hosts():
    fleet = FleetSpec(0, 2, "h:1")
    ragged = _fake_fleet_devices(2, 2) + [FakeDevice(1, 9)]
    with pytest.raises(ValueError, match="uniform"):
        compose_fleet_mesh_devices(fleet, "", ragged)
    only_host0 = [FakeDevice(0, 0), FakeDevice(0, 1)]
    with pytest.raises(ValueError, match="no devices"):
        compose_fleet_mesh_devices(fleet, "", only_host0)
    with pytest.raises(ValueError, match="outside"):
        compose_fleet_mesh_devices(fleet, "", [FakeDevice(5, 0)])


# ---------------------------------------------------------------------------
# TAG_SNAPSHOT: the wire-published policy path


def _params():
    rng = np.random.default_rng(11)
    import jax.numpy as jnp

    return {
        "core": {
            "w": jnp.asarray(
                rng.standard_normal((4, 3)).astype(np.float32)
            ),
            "b": jnp.asarray(rng.standard_normal(3).astype(np.float32)),
        },
        "steps": jnp.asarray(np.int32(7)),
    }


def _leaf_bytes(tree):
    import jax

    return [
        np.asarray(a).tobytes()
        for a in jax.tree_util.tree_leaves(tree)
    ]


def test_snapshot_wire_roundtrip_bit_exact_vs_local_publish():
    """A remote slice serving a wire-delivered snapshot must hand out
    bit-identical bytes to a local replica at the same version: wire
    encode -> decode -> apply_snapshot -> latest_on equals a plain
    local publish."""
    import jax

    params = _params()
    local = PolicySnapshotStore(1, registry=MetricsRegistry())
    assert local.publish(3, params)

    remote = PolicySnapshotStore(1, registry=MetricsRegistry())
    snap = wire.decode(wire.encode(build_snapshot(3, params))[4:])
    assert isinstance(snap, wire.PolicySnapshot)
    assert apply_snapshot(remote, snap, template=params)

    device = jax.local_devices()[0]
    v_local, tree_local = local.latest_on(device)
    v_remote, tree_remote = remote.latest_on(device)
    assert v_local == v_remote == 3
    assert (
        jax.tree_util.tree_structure(tree_local)
        == jax.tree_util.tree_structure(tree_remote)
    )
    for lo, re_ in zip(_leaf_bytes(tree_local), _leaf_bytes(tree_remote)):
        assert lo == re_  # bit-exact, not allclose
    # Dtypes restored to the ORIGINAL param dtypes on both sides.
    assert [
        np.asarray(a).dtype
        for a in jax.tree_util.tree_leaves(tree_remote)
    ] == [
        np.asarray(a).dtype for a in jax.tree_util.tree_leaves(params)
    ]


def test_snapshot_encoders_agree():
    snap = build_snapshot(9, _params())
    assert bytes(wire.encode_legacy(snap)) == wire.encode(snap)


def test_snapshot_version_skew_stale_rejected():
    params = _params()
    store = PolicySnapshotStore(1, registry=MetricsRegistry())
    reg = MetricsRegistry()
    stale = reg.counter("fleet.snapshots_stale_dropped")
    assert apply_snapshot(store, build_snapshot(5, params), params,
                          stale_counter=stale)
    assert store.version == 5
    # Same version re-delivered and an older one: both dropped, counted,
    # store untouched.
    assert not apply_snapshot(store, build_snapshot(5, params), params,
                              stale_counter=stale)
    assert not apply_snapshot(store, build_snapshot(3, params), params,
                              stale_counter=stale)
    assert store.version == 5
    assert stale.value() == 2
    # Fresh version still lands.
    assert apply_snapshot(store, build_snapshot(6, params), params,
                          stale_counter=stale)
    assert store.version == 6


def test_snapshot_template_mismatch_is_wire_error():
    params = _params()
    store = PolicySnapshotStore(1, registry=MetricsRegistry())
    snap = build_snapshot(1, params)
    with pytest.raises(wire.WireError, match="leaf"):
        apply_snapshot(store, snap, template={"just_one": params["steps"]})
    with pytest.raises(wire.WireError, match="PolicySnapshot"):
        apply_snapshot(store, {"not": "a snapshot"}, template=params)


def test_snapshot_truncation_fuzz_raises_wire_error():
    """Every truncation point of an encoded TAG_SNAPSHOT payload must
    surface as WireError (the one exception connection teardown
    catches), never struct.error/ValueError."""
    payload = bytes(wire.encode_legacy(build_snapshot(2, _params())))[4:]
    cuts = set(range(0, min(len(payload), 64)))
    cuts.update(np.random.default_rng(3).integers(
        0, len(payload), size=80
    ).tolist())
    for cut in sorted(cuts):
        with pytest.raises(wire.WireError):
            wire.decode(payload[:cut])


def test_snapshot_negative_version_rejected_at_build():
    with pytest.raises(wire.WireError):
        wire.PolicySnapshot(-1, [], [])


# ---------------------------------------------------------------------------
# Control plane over real sockets


def _free_port_pair():
    for _ in range(50):
        s1, s2 = socket.socket(), socket.socket()
        try:
            s1.bind(("127.0.0.1", 0))
            port = s1.getsockname()[1]
            try:
                s2.bind(("127.0.0.1", port + 1))
            except OSError:
                continue
            return port
        finally:
            s1.close()
            s2.close()
    raise RuntimeError("no adjacent free ports")


def _pair(min_live_hosts=1, heartbeat_s=0.05, sync_timeout_s=5.0):
    """(lead, remote) coordinators connected over a loopback pair, each
    with its own health plane and registry."""
    port = _free_port_pair()
    coord = f"127.0.0.1:{port}"
    lead = FleetCoordinator(
        FleetSpec(0, 2, coord), PipelineHealth(registry=MetricsRegistry()),
        "wire", min_live_hosts=min_live_hosts, heartbeat_s=heartbeat_s,
        connect_timeout_s=10.0, sync_timeout_s=sync_timeout_s,
        registry=MetricsRegistry(),
    )
    remote = FleetCoordinator(
        FleetSpec(1, 2, coord), PipelineHealth(registry=MetricsRegistry()),
        "wire", min_live_hosts=min_live_hosts, heartbeat_s=heartbeat_s,
        connect_timeout_s=10.0, sync_timeout_s=sync_timeout_s,
        registry=MetricsRegistry(),
    )
    # start() on the lead blocks until the remote dials in.
    lead_started = threading.Thread(target=lead.start, daemon=True)
    lead_started.start()
    remote.start()
    lead_started.join(timeout=10.0)
    assert not lead_started.is_alive(), "lead never saw the remote hello"
    return lead, remote


def _wait(predicate, timeout_s=5.0, what="condition"):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if predicate():
            return
        time.sleep(0.01)
    raise AssertionError(f"timed out waiting for {what}")


def test_coordinator_heartbeat_folds_sticky_degradation():
    lead, remote = _pair()
    try:
        assert lead.live_hosts() == 2
        # A healthy heartbeat folds nothing.
        _wait(lambda: 1 in lead.remote_stats(), what="first heartbeat")
        assert lead._health.state == HEALTHY
        # A recovered incident (restarts > 0, state back to HEALTHY)
        # still leaves a permanent fleet.host1 mark on the lead.
        remote.set_stats_source(
            lambda: {"updates": 42, "restarts": 2, "reconnects": 3}
        )
        remote.set_gauges_source(
            lambda: {"inference.slice.0.depth": 1.5}
        )
        _wait(lambda: lead._health.state == DEGRADED,
              what="fold on the lead")
        assert any(
            r.startswith("fleet.host1") for _, r in lead._health.reasons()
        )
        _wait(
            lambda: lead.remote_gauges().get(1, {}).get(
                "inference.slice.0.depth"
            ) == 1.5,
            what="remote gauges in heartbeats",
        )
        assert lead.remote_stats()[1]["updates"] == 42
        # Sticky: the remote going quiet-and-healthy cannot clear it.
        remote.set_stats_source(
            lambda: {"updates": 50, "restarts": 0, "reconnects": 0}
        )
        time.sleep(0.2)
        assert lead._health.state == DEGRADED
    finally:
        remote.shutdown()
        lead.shutdown()


def test_coordinator_snapshot_delivery_and_skew():
    lead, remote = _pair()
    try:
        params = _params()
        store = PolicySnapshotStore(1, registry=MetricsRegistry())
        remote.attach_snapshot_store(store, params)
        assert lead.publish_snapshot(4, params) == 1
        _wait(lambda: store.version == 4, what="snapshot v4 applied")
        # Re-publishing the same version is dropped as stale remotely.
        assert lead.publish_snapshot(4, params) == 1
        _wait(
            lambda: remote._c_snap_stale.value() == 1,
            what="stale drop counted",
        )
        assert store.version == 4
        assert lead.publish_snapshot(7, params) == 1
        _wait(lambda: store.version == 7, what="snapshot v7 applied")
    finally:
        remote.shutdown()
        lead.shutdown()


def test_coordinator_param_sync_means_across_hosts():
    lead, remote = _pair()
    try:
        tree_lead = {"w": np.full((3,), 1.0, np.float32)}
        tree_remote = {"w": np.full((3,), 3.0, np.float32)}
        out = {}

        def remote_side():
            out["remote"] = remote.sync_params(tree_remote)

        t = threading.Thread(target=remote_side, daemon=True)
        t.start()
        out["lead"] = lead.sync_params(tree_lead)
        t.join(timeout=10.0)
        assert not t.is_alive()
        for side in ("lead", "remote"):
            got = out[side]
            assert got is not None, f"{side} sync degraded"
            np.testing.assert_array_equal(
                np.asarray(got["w"]), np.full((3,), 2.0, np.float32)
            )
            assert np.asarray(got["w"]).dtype == np.float32
    finally:
        remote.shutdown()
        lead.shutdown()


def test_coordinator_lead_sync_degrades_after_remote_done():
    lead, remote = _pair(sync_timeout_s=1.0)
    try:
        remote.learner_done()
        _wait(lambda: 1 in lead._done, what="done registered")
        # The lead no longer waits on host 1: a solo round returns its
        # own params (mean of one) instead of timing out.
        tree = {"w": np.full((2,), 5.0, np.float32)}
        t0 = time.monotonic()
        got = lead.sync_params(tree)
        assert time.monotonic() - t0 < 0.9
        np.testing.assert_array_equal(np.asarray(got["w"]), tree["w"])
    finally:
        remote.shutdown()
        lead.shutdown()


def test_coordinator_host_loss_above_floor_is_sticky_degraded():
    lead, remote = _pair(min_live_hosts=1)
    try:
        # Abrupt death: close the remote's socket without a bye.
        with remote._lock:
            conn = remote._conns.pop(0)
            remote._send_locks.pop(0, None)
        conn.close()
        # The lead counts the host lost under its lock and degrades
        # after letting go of it: wait for the verdict itself.
        _wait(lambda: lead._health.state == DEGRADED, what="loss degrades")
        assert lead.live_hosts() == 1
        assert any(
            r.startswith("fleet.host1_lost")
            for _, r in lead._health.reasons()
        )
    finally:
        remote._closing.set()
        lead.shutdown()


def test_coordinator_host_loss_below_floor_halts_fleet():
    lead, remote = _pair(min_live_hosts=2)
    try:
        with remote._lock:
            conn = remote._conns.pop(0)
            remote._send_locks.pop(0, None)
        conn.close()
        _wait(lambda: lead._health.state == HALTED,
              what="floor-crossing halt")
        assert any(
            "min_live_hosts" in r for _, r in lead._health.reasons()
        )
    finally:
        remote._closing.set()
        lead.shutdown()


def test_coordinator_remote_halts_when_lead_lost_uncleanly():
    lead, remote = _pair()
    try:
        with lead._lock:
            conn = lead._conns.pop(1)
            lead._send_locks.pop(1, None)
        conn.close()
        _wait(lambda: remote._health.state == HALTED,
              what="remote halt on lead loss")
        assert remote.live_hosts() == 0
    finally:
        lead._closing.set()
        remote.shutdown()


def test_coordinator_clean_shutdown_is_not_a_loss():
    lead, remote = _pair()
    try:
        remote.shutdown()  # sends bye
        _wait(lambda: 1 in lead._done, what="clean departure recorded")
        time.sleep(0.1)
        assert lead._health.state == HEALTHY  # no loss, no fold
        assert lead.live_hosts() == 2  # departed cleanly, never "lost"
    finally:
        lead.shutdown()


def test_coordinator_remote_sync_bails_after_clean_lead_exit():
    lead, remote = _pair(sync_timeout_s=5.0)
    try:
        lead.shutdown()  # clean bye to the remote
        _wait(
            lambda: remote._lead_gone, what="lead departure seen",
        )
        t0 = time.monotonic()
        got = remote.sync_params({"w": np.zeros(2, np.float32)})
        assert got is None  # degraded round: caller keeps its params
        assert time.monotonic() - t0 < 1.0  # without burning the timeout
        assert remote._health.state == HEALTHY  # clean exit != fault
    finally:
        remote.shutdown()


def test_coordinator_rejects_bad_floor():
    with pytest.raises(ValueError):
        FleetCoordinator(
            FleetSpec(0, 2, "h:1"),
            PipelineHealth(registry=MetricsRegistry()),
            "wire", min_live_hosts=3, registry=MetricsRegistry(),
        )
    with pytest.raises(ValueError):
        FleetCoordinator(
            FleetSpec(0, 2, "h:1"),
            PipelineHealth(registry=MetricsRegistry()),
            "wire", min_live_hosts=0, registry=MetricsRegistry(),
        )
