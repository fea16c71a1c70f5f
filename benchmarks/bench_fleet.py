"""Beyond-paper: multi-device fleet dispatch (DESIGN.md §14).

What this bench earns (recorded in BENCH_fleet.json so the perf claims have
an artifact):

  * SCALE — one Dispatcher(mesh=N) drives 10k+ concurrent sessions as
    shard_map-sharded gang waves; modeled per-device makespan drops near
    1/N, so aggregate fleet throughput scales near-linearly: >= 3x at 4
    simulated devices vs 1, near-linear (warn) to 8. The paper's across-
    stream parallelism (Fig 9) taken past one device.
  * IDENTITY — sharding is invisible on the wire: every session's flush
    records and egress frames byte-identical to the unsharded gang.
  * CHAOS — a device killed mid-wave (twice: 4 -> 3 -> 2, crossing a prime
    mesh width) re-meshes onto the survivors and replays from the members'
    last committed FlushRecords: byte-identical output, every acknowledged
    flush decodes bit-exact, ZERO acknowledged frames lost.

Every point runs in this one process, on a mesh over the first N of the
devices it sees: a chip belongs to one process, so no point may run in a
child. The identity and chaos drills need four devices; on a CPU host set
XLA_FLAGS=--xla_force_host_platform_device_count=8 before launch. Points
wider than the visible device count are skipped and reported. Correctness
claims raise (failing the smoke gate); scaling claims are recorded as
claims.
"""
from __future__ import annotations

import json
import os
import sys

OUT_JSON = os.path.join(os.path.dirname(__file__), "BENCH_fleet.json")

#: lossless stateful mix for identity/chaos: rle carries open runs, tdic32
#: runs the shared-dictionary LWW merge inside the sharded dispatch
MIX = ("tcomp32", "rle", "tdic32")


# ---------------------------------------------------------------- workers --
def _worker_scale(devices: int, sessions: int) -> dict:
    import time

    import numpy as np

    from repro import cstream

    n = 128  # one flush-sized burst per session
    t0 = time.perf_counter()
    d = cstream.Dispatcher(gang=True, mesh=devices, max_sessions=sessions + 16)
    handles = d.open_many(
        cstream.JobSpec(codec="tcomp32", gang=True, flush_tuples=n, devices=devices),
        count=sessions,
    )
    admit_s = time.perf_counter() - t0
    rng = np.random.default_rng(7)
    burst = np.clip(
        np.cumsum(rng.integers(-8, 9, size=n)) + 4096, 0, 65535
    ).astype(np.uint32)
    # per-session contiguous bursts: sessions spread over simulated time so
    # quantum edges and the backpressure budget both shape waves
    for i, h in enumerate(handles):
        h.push(burst, timestamps=np.full(n, i * 5e-5))
    t0 = time.perf_counter()
    rep = d.close()
    wall_s = time.perf_counter() - t0
    (st,) = rep.dispatch_stats.values()
    return {
        "devices": rep.devices,
        "sessions": rep.n_sessions,
        "tuples": rep.total_tuples,
        "input_mb": rep.total_input_bytes / 1e6,
        "admit_s": admit_s,
        "wall_s": wall_s,
        "device_makespan_s": rep.device_makespan_s,
        "fleet_mbps": rep.fleet_mbps,
        "dispatches": rep.n_dispatches,
        "waves": st.n_waves,
        "solo_waves": st.n_solo,
        "mean_wave": st.mean_wave,
        "occupancy": st.occupancy,
        "all_flushed": all(
            s.n_flushes >= 1 and s.n_tuples == n for s in rep.sessions.values()
        ),
    }


def _mixed_server_run(mesh=None, fault=None, n_sessions: int = 12, n: int = 2000):
    from repro.core.strategies import EngineConfig, StateStrategy
    from repro.data import make_dataset
    from repro.data.stream import rate_for_dataset, zipf_timestamps
    from repro.runtime.server import ServerCore

    datasets = {"tcomp32": "micro", "rle": "sensor", "tdic32": "rovio"}
    rate = rate_for_dataset(1)
    server = ServerCore(
        max_sessions=n_sessions + 4, egress=True, gang=True,
        mesh=mesh, fault_injector=fault,
    )
    feeds = {}
    for i in range(n_sessions):
        codec = MIX[i % len(MIX)]
        vals = make_dataset(datasets[codec], n_tuples=n).stream()[:n]
        cfg = EngineConfig(
            codec=codec, micro_batch_bytes=2048, lanes=4,
            state=(
                StateStrategy.SHARED if codec == "tdic32" else StateStrategy.PRIVATE
            ),
        )
        topic = f"{codec}-{i}"
        server.admit(topic, cfg, sample=vals)
        feeds[topic] = (vals, zipf_timestamps(n, rate, zipf_factor=0.7, seed=i))
    rep = server.run(feeds)
    out = {
        t: (tuple(f.key() for f in s.flushes), s.egress_frame().to_bytes())
        for t, s in sorted(server.sessions.items())
    }
    bit_exact = all(
        s.egress_fidelity()[0].bit_exact for s in server.sessions.values()
    )
    return out, bit_exact, rep


def _worker_identity(devices: int) -> dict:
    base, _, _ = _mixed_server_run()
    shard, bit_exact, rep = _mixed_server_run(mesh=devices)
    return {
        "devices": rep.devices,
        "sessions": rep.n_sessions,
        "frames_identical": shard == base,
        "decode_bit_exact": bit_exact,
        "waves": sum(s.n_waves for s in rep.dispatch_stats.values()),
        "padded_slots": sum(s.padded_slots for s in rep.dispatch_stats.values()),
    }


def _worker_chaos(devices: int) -> dict:
    from repro.runtime.fault import DeviceLossInjector

    base, _, _ = _mixed_server_run()
    # kill mesh slot devices-1 during wave 1 and slot 0 during wave 3:
    # 4 -> 3 -> 2 exercises a prime survivor count mid-run
    inj = DeviceLossInjector({1: devices - 1, 3: 0})
    chaos, bit_exact, rep = _mixed_server_run(mesh=devices, fault=inj)
    return {
        "devices_start": devices,
        "devices_final": rep.devices,
        "fault_events": rep.fault_events,
        "frames_identical": chaos == base,
        "decode_bit_exact": bit_exact,
        "acknowledged_flushes": int(
            sum(s.n_flushes for s in rep.sessions.values())
        ),
    }


# ------------------------------------------------------------------- driver --
def run(quick: bool = True) -> dict:
    from benchmarks.common import fmt_table

    import jax

    sessions = 10240  # the 10k-concurrent-sessions operating point
    dev_points = [1, 4, 8] if quick else [1, 2, 4, 8]
    visible = jax.device_count()
    if visible < 4:
        raise RuntimeError(
            f"bench_fleet needs >= 4 visible devices, {visible} "
            f"{jax.devices()[0].platform} device(s) visible; on a CPU host "
            "launch with XLA_FLAGS=--xla_force_host_platform_device_count=8"
        )
    skipped = [d for d in dev_points if d > visible]
    if skipped:
        print(f"   skipped device points {skipped}: {visible} devices visible")
    scale = [_worker_scale(d, sessions) for d in dev_points if d <= visible]
    print(fmt_table(
        scale,
        ["devices", "sessions", "input_mb", "admit_s", "wall_s",
         "device_makespan_s", "fleet_mbps", "waves", "mean_wave", "occupancy"],
        f"fleet scale-out: {sessions} sessions, sharded gang waves",
    ))

    base_mbps = scale[0]["fleet_mbps"]
    speedups = {r["devices"]: r["fleet_mbps"] / base_mbps for r in scale}
    print("   modeled fleet speedup vs 1 device:",
          {d: round(s, 2) for d, s in speedups.items()})

    identity = _worker_identity(4)
    chaos = _worker_chaos(4)
    print(fmt_table([identity], list(identity), "identity: 4-way sharded vs gang"))
    print(fmt_table(
        [{k: v for k, v in chaos.items() if k != "fault_events"}],
        [k for k in chaos if k != "fault_events"],
        "chaos: kill-a-device x2 (4 -> 3 -> 2)",
    ))
    print("   fault events:", chaos["fault_events"])

    correctness = {
        # sharding must be invisible on the wire
        "fleet_sharded_frames_bit_identical": (
            identity["frames_identical"] and identity["decode_bit_exact"]
        ),
        # zero acknowledged frames lost across two device losses, and every
        # acknowledged flush decodes bit-exact
        "fleet_chaos_zero_frame_loss": (
            chaos["frames_identical"]
            and chaos["decode_bit_exact"]
            and len(chaos["fault_events"]) == 2
            and chaos["devices_final"] == 2
        ),
        # the sharded path actually carried the fleet (no silent solo fall-back)
        "fleet_waves_sharded": identity["waves"] > 0,
        "fleet_10k_sessions_all_flushed": all(
            r["sessions"] >= sessions and r["all_flushed"] for r in scale
        ),
    }
    claims = dict(correctness)
    claims["fleet_3x_at_4_devices"] = speedups.get(4, 0.0) >= 3.0
    # near-linear tail is a warn-level target: host-simulated devices model
    # per-device makespan, and padding waste grows with mesh width
    claims["fleet_near_linear_8_devices"] = speedups.get(8, 0.0) >= 6.0
    print("   claims:", claims)

    out = {
        "rows": scale + [identity,
                         {k: v for k, v in chaos.items() if k != "fault_events"}],
        "speedups": {str(d): s for d, s in speedups.items()},
        "fault_events": chaos["fault_events"],
        "claims": claims,
    }
    with open(OUT_JSON, "w") as f:
        json.dump(out, f, indent=1, default=str)
    print(f"   wrote {OUT_JSON}")

    # correctness gates the smoke run: a miss is a recovery/wire bug, not a
    # perf regression — fail the module, not just the claim line
    failed = [k for k, ok in correctness.items() if not ok]
    if failed:
        raise RuntimeError(f"fleet correctness claims failed: {failed}")
    return out


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true", help="fast CI subset")
    ap.add_argument("--full", action="store_true", help="all device points")
    args = ap.parse_args(argv)
    run(quick=args.smoke or not args.full)
    return 0


if __name__ == "__main__":
    sys.exit(main())
