"""Fleet dispatch (DESIGN.md §14): sharded gang waves over a device mesh.

The load-bearing property is the same EQUIVALENCE test_gang pins, one level
up: sharding a gang wave over a data-axis mesh must change nothing
observable — flush records and egress frames come back byte-identical to
the unsharded gang (itself byte-identical to solo sessions) — and a device
lost mid-wave must cost ZERO acknowledged frames: the wave replays on the
shrunk mesh from its members' last committed FlushRecords.

In-process tests run on however many devices the host exposes (usually 1:
the mesh-of-1 fleet is the degenerate case that must cost nothing). The
multi-device shard/chaos drills run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=4 because the device count
is fixed at jax init; CI's fleet job additionally runs the multi-shard
property tests under 8 simulated devices.
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro import cstream
from repro.core.strategies import (
    EngineConfig,
    FleetPlan,
    plan_execution,
    plan_fleet,
    plan_gang,
)
from repro.data import make_dataset
from repro.data.stream import rate_for_dataset, zipf_timestamps
from repro.runtime.elastic import ElasticSession, logical_mapping, plan_mesh
from repro.runtime.fault import DeviceLoss, DeviceLossInjector, HeartbeatMonitor
from repro.runtime.server import StreamServer

#: stateful codecs (rle runs, tdic32 dictionary) next to stateless — the
#: shard scatter must keep every member straight, like the gang scatter
MIX = [("tcomp32", "micro"), ("rle", "sensor"), ("tdic32", "rovio")]


def _cfg(codec, **kw):
    base = dict(codec=codec, micro_batch_bytes=2048, lanes=4)
    base.update(kw)
    return EngineConfig(**base)


# ------------------------------------------------------------ mesh planning --
@pytest.mark.parametrize("n", [1, 2, 3, 5, 8])
def test_plan_mesh_cstream_any_device_count(n):
    """The serving fleet meshes ANY healthy count — including the primes a
    device loss leaves behind — as a pure data axis."""
    assert plan_mesh(n, profile="cstream") == ((n,), ("data",))


def test_plan_mesh_validation():
    with pytest.raises(ValueError, match=">= 1"):
        plan_mesh(0, profile="cstream")
    with pytest.raises(ValueError, match="unknown mesh profile"):
        plan_mesh(4, profile="tpu")
    # the LM factoring is untouched: model axis pinned to the largest
    # power-of-two divisor <= 16, remainder to data
    assert plan_mesh(16) == ((1, 16), ("data", "model"))
    assert plan_mesh(3) == ((3, 1), ("data", "model"))


def test_logical_mapping_data_only_mesh():
    assert logical_mapping(("data",)) == {"data": "data"}
    assert logical_mapping(("data", "model")) == {"data": "data", "model": "model"}


def test_elastic_session_cstream_profile():
    es = ElasticSession(n_devices=1, profile="cstream")
    assert tuple(es.mesh.axis_names) == ("data",)
    assert es.mapping == {"data": "data"}
    # resize with an explicit (pinned) survivor list round-trips
    es.resize(1, devices=[jax.devices()[0]])
    assert es.n_devices == 1
    assert list(np.asarray(es.mesh.devices).ravel()) == [jax.devices()[0]]


def test_plan_fleet_scales_gang_plan():
    gp = plan_gang(plan_execution(_cfg("tcomp32")))
    fp = plan_fleet(gp, 4)
    assert isinstance(fp, FleetPlan)
    assert fp.devices == 4
    assert fp.max_wave == 4 * gp.max_gang
    assert fp.budget == 4 * gp.budget
    assert fp.quantum_s == gp.quantum_s
    with pytest.raises(ValueError, match=">= 1 device"):
        plan_fleet(gp, 0)


# ------------------------------------------------------------- chaos pieces --
def test_device_loss_injector_fires_once():
    inj = DeviceLossInjector(fail_at_waves={2: 1})
    inj.maybe_fail(0)  # unscheduled waves pass
    with pytest.raises(DeviceLoss) as exc:
        inj.maybe_fail(2)
    assert exc.value.device_index == 1
    assert exc.value.wave == 2
    inj.maybe_fail(2)  # the retried wave must succeed


def test_device_loss_without_fleet_raises():
    """A non-fleet gang server has no mesh to shrink: loss propagates."""
    server = StreamServer(gang=True, fault_injector=DeviceLossInjector({0: 0}))
    s = server.admit("t", _cfg("tcomp32"))
    cap = s.capacity
    with pytest.raises(DeviceLoss):
        server.run({"t": (np.arange(cap, dtype=np.uint32), np.zeros(cap))})


def test_device_loss_with_no_survivors_raises():
    """Killing the last device cannot re-admit the orphans anywhere."""
    server = StreamServer(
        gang=True, mesh=1, fault_injector=DeviceLossInjector({0: 0})
    )
    s = server.admit("t", _cfg("tcomp32"))
    cap = s.capacity
    with pytest.raises(DeviceLoss):
        server.run({"t": (np.arange(cap, dtype=np.uint32), np.zeros(cap))})


# ------------------------------------------------------- server validation --
def test_server_mesh_requires_gang():
    with pytest.raises(ValueError, match="gang=True"):
        StreamServer(mesh=1)


def test_server_mesh_bounds():
    with pytest.raises(ValueError, match=">= 1"):
        StreamServer(gang=True, mesh=0)
    n = jax.device_count()
    with pytest.raises(ValueError, match=f"exceeds the {n} visible .* <= {n}"):
        StreamServer(gang=True, mesh=n + 1)


def test_server_rejects_lm_mesh():
    """A model-axis mesh has no session axis to shard waves over."""
    lm = ElasticSession(n_devices=1, profile="lm")
    with pytest.raises(ValueError, match="pure \\('data',\\)"):
        StreamServer(gang=True, mesh=lm)


# ------------------------------------------------------- negotiation surface --
def test_jobspec_devices_field():
    with pytest.raises(cstream.NegotiationError, match="devices"):
        cstream.JobSpec(devices=-1)
    spec = cstream.JobSpec(codec="tcomp32", gang=True, devices=1)
    assert cstream.JobSpec.from_dict(spec.to_dict()) == spec
    assert spec.to_dict()["devices"] == 1


def test_negotiate_devices_requires_gang():
    with pytest.raises(cstream.NegotiationError, match="gang=False"):
        cstream.negotiate(cstream.JobSpec(devices=2, gang=False))


def test_negotiate_devices_bounded_by_visible():
    too_many = jax.device_count() + 1
    with pytest.raises(cstream.NegotiationError, match="visible .* devices <= "):
        cstream.negotiate(cstream.JobSpec(devices=too_many, gang=True))


def test_negotiate_attaches_fleet_plan():
    plan = cstream.negotiate(cstream.JobSpec(codec="tcomp32", gang=True, devices=1))
    assert isinstance(plan.fleet, FleetPlan)
    assert plan.fleet.devices == 1
    assert plan.fleet.max_wave == plan.gang.max_gang
    # devices=0: dispatcher-local, no fleet sizing
    assert cstream.negotiate(cstream.JobSpec(codec="tcomp32")).fleet is None


def test_dispatcher_mesh_negotiation_errors():
    with pytest.raises(cstream.NegotiationError, match="gang=True"):
        cstream.Dispatcher(mesh=1)
    with pytest.raises(cstream.NegotiationError, match="visible"):
        cstream.Dispatcher(gang=True, mesh=jax.device_count() + 1)
    # a spec demanding a wider mesh than this dispatcher runs is refused
    # (on a 1-device host the visible-device check fires first — either
    # way the spec cannot open here)
    d = cstream.Dispatcher(gang=True, mesh=1)
    assert d.devices == 1
    with pytest.raises(cstream.NegotiationError):
        d.open(cstream.JobSpec(codec="tcomp32", gang=True, devices=2))


def test_open_many_validation_and_naming():
    d = cstream.Dispatcher(gang=True)
    spec = cstream.JobSpec(codec="tcomp32", gang=True)
    with pytest.raises(cstream.NegotiationError, match="exactly one"):
        d.open_many(spec)
    with pytest.raises(cstream.NegotiationError, match="exactly one"):
        d.open_many(spec, count=2, topics=["a", "b"])
    with pytest.raises(cstream.NegotiationError, match=">= 1"):
        d.open_many(spec, count=0)
    hs = d.open_many(spec, topics=["a", "b"])
    assert [h.topic for h in hs] == ["a", "b"]
    more = d.open_many(spec, count=2)  # auto names skip existing sessions
    assert all(h.topic not in ("a", "b") for h in more)
    assert len(d.sessions) == 4


def test_open_many_shares_owner_pipeline():
    """Fleet-scale admission: 8 same-spec sessions negotiate once and share
    ONE compiled pipeline (codec state stays per-session), and the report
    counts that pipeline's dispatches once — not once per session."""
    d = cstream.Dispatcher(gang=True, max_sessions=16)
    hs = d.open_many(
        cstream.JobSpec(codec="tcomp32", gang=True, flush_tuples=128), count=8
    )
    pipes = {id(h._session.pipeline) for h in hs}
    assert len(pipes) == 1
    for i, h in enumerate(hs):
        h.push(
            np.arange(128, dtype=np.uint32),
            timestamps=np.full(128, 0.001 * i, np.float64),
        )
    d.run()
    rep = d.close()
    owner = hs[0]._session.pipeline
    assert rep.n_dispatches == owner.dispatches
    assert rep.total_tuples == 8 * 128


# ------------------------------------------------------ fleet equivalence --
def _run_mixed(mesh=None, heartbeat=None, n_sessions=6, n=2400):
    rate = rate_for_dataset(1)
    server = StreamServer(
        max_sessions=16, egress=True, gang=True, mesh=mesh, heartbeat=heartbeat
    )
    feeds = {}
    for i in range(n_sessions):
        codec, ds = MIX[i % len(MIX)]
        vals = make_dataset(ds, n_tuples=n).stream()[:n]
        topic = f"{codec}-{i}"
        server.admit(topic, _cfg(codec), sample=vals)
        feeds[topic] = (vals, zipf_timestamps(n, rate, zipf_factor=0.7, seed=i))
    return server, server.run(feeds)


def test_fleet_mesh1_bit_identical_to_gang():
    """The degenerate 1-device fleet IS the gang dispatcher: records, frames
    and fidelity byte-identical, and the report's fleet surface filled in."""
    hb = HeartbeatMonitor(timeout_s=1e9)  # not started: beat() only
    beat0 = hb._last_beat
    gang_srv, gang_rep = _run_mixed(mesh=None)
    fleet_srv, fleet_rep = _run_mixed(mesh=1, heartbeat=hb)

    assert gang_rep.total_tuples == fleet_rep.total_tuples
    for topic in gang_srv.sessions:
        a, b = gang_srv.sessions[topic], fleet_srv.sessions[topic]
        assert [f.key() for f in a.flushes] == [f.key() for f in b.flushes], topic
        assert a.egress_frame().to_bytes() == b.egress_frame().to_bytes(), topic
    # fleet accounting: mesh width, per-signature stats, modeled makespan
    assert fleet_rep.devices == 1
    assert fleet_rep.fault_events == []
    assert fleet_rep.device_makespan_s > 0
    assert fleet_rep.fleet_mbps > 0
    assert set(fleet_rep.dispatch_stats) == {
        f"{codec}-{i}".split("-")[0]
        + f"/4x{fleet_srv.sessions[f'{codec}-{i}'].capacity // 4}"
        for i, (codec, _) in enumerate(MIX)
    }
    for st in fleet_rep.dispatch_stats.values():
        assert st.n_sessions == 2  # 6 sessions over 3 signatures
        assert st.sessions_dispatched > 0
        assert st.padded_slots == 0  # mesh of 1 never pads
        assert st.occupancy == 1.0
        assert 0 < st.mean_wave <= st.max_wave <= 2
    # every completed wave beat the liveness monitor
    assert hb._last_beat > beat0


def test_fleet_report_breakdown_solo_waves():
    """Waves of one take the inline solo path but still count in the
    signature breakdown."""
    server = StreamServer(gang=True, mesh=1)
    s = server.admit("only", _cfg("tcomp32"))
    cap = s.capacity
    server.run({"only": (np.arange(cap, dtype=np.uint32), np.zeros(cap))})
    rep = server.report()
    (st,) = rep.dispatch_stats.values()
    assert st.label.startswith("tcomp32/")
    assert st.n_solo >= 1 and st.n_waves == 0
    assert st.sessions_dispatched == st.n_solo
    assert rep.device_makespan_s > 0


# ---------------------------------------------------- multi-device drills --
_SUBPROCESS_DRILL = textwrap.dedent(
    """
    import numpy as np
    import jax
    assert jax.device_count() == 4, jax.device_count()

    from repro.core.strategies import EngineConfig
    from repro.data import make_dataset
    from repro.data.stream import rate_for_dataset, zipf_timestamps
    from repro.runtime.fault import DeviceLossInjector
    from repro.runtime.server import StreamServer

    MIX = [("tcomp32", "micro"), ("rle", "sensor"), ("tdic32", "rovio")]

    def run(mesh=None, fault=None, n_sessions=9, n=2000):
        rate = rate_for_dataset(1)
        server = StreamServer(max_sessions=16, egress=True, gang=True,
                              mesh=mesh, fault_injector=fault)
        feeds = {}
        for i in range(n_sessions):
            codec, ds = MIX[i % len(MIX)]
            vals = make_dataset(ds, n_tuples=n).stream()[:n]
            cfg = EngineConfig(codec=codec, micro_batch_bytes=2048, lanes=4)
            server.admit(f"{codec}-{i}", cfg, sample=vals)
            feeds[f"{codec}-{i}"] = (
                vals, zipf_timestamps(n, rate, zipf_factor=0.7, seed=i))
        rep = server.run(feeds)
        out = {t: (tuple(f.key() for f in s.flushes),
                   s.egress_frame().to_bytes())
               for t, s in server.sessions.items()}
        return out, rep

    base, _ = run()
    shard, rep4 = run(mesh=4)
    assert shard == base, "4-way sharded waves are not byte-identical"
    assert rep4.devices == 4
    assert any(s.padded_slots > 0 or s.n_waves > 0
               for s in rep4.dispatch_stats.values())

    # chaos: kill mesh slot 2 during wave 1, slot 0 during wave 3 ->
    # 4 -> 3 -> 2 devices (the 3-mesh exercises a prime survivor count)
    inj = DeviceLossInjector({1: 2, 3: 0})
    chaos, repc = run(mesh=4, fault=inj)
    assert chaos == base, "device loss leaked into acknowledged frames"
    assert len(repc.fault_events) == 2, repc.fault_events
    assert [e["n_devices"] for e in repc.fault_events] == [3, 2]
    assert repc.devices == 2
    print("FLEET-DRILL-OK")
    """
)


@pytest.mark.slow
def test_sharded_and_chaos_waves_bit_identical_subprocess():
    """4 simulated devices (needs XLA_FLAGS before jax init, hence the
    subprocess): sharded waves AND waves replayed through two injected
    device losses produce byte-identical records/frames to the unsharded
    gang — zero acknowledged frames lost."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
    ).strip()
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in [
            os.path.join(os.path.dirname(__file__), "..", "src"),
            env.get("PYTHONPATH", ""),
        ] if p
    )
    proc = subprocess.run(
        [sys.executable, "-c", _SUBPROCESS_DRILL],
        env=env, capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "FLEET-DRILL-OK" in proc.stdout
