#!/usr/bin/env python3
"""Smoke run of the served compression path on a TPU.

    python chip_smoke.py                 # one chip: the D1 edge-fleet path
    python chip_smoke.py --chips 4       # four chips: the fleet phase only

One chip drives the ROADMAP D1 deployment (paper §5, Table 4) through the
public surface, JobSpec -> negotiate -> Dispatcher/StreamHandle -> egress
frames -> to_bytes -> DecompressionPipeline.ingest: 1024 concurrent gang
sessions of 16384 four-byte tuples each (64 MiB in), flushed in 8 KiB
micro-batches (flush_tuples=2048, the Fig 11 optimum), with bursty arrivals
from `zipf_timestamps` at the paper's 16 MB/s per-stream rate. Data comes
from `--seed` over the six Table 4 datasets. The codec mix is stateless
(tcomp32), stateful (rle, tdic32) and lossy bounded (pla); a share of the
sessions negotiates rANS entropy and CRC32C integrity, and one tdic32 topic
uses a dictionary trained at run time from seed data. Checks: lossless
sessions decode bit-exact, lossy ones stay within `error_bound()`, and a
16-session subset rerun on the host CPU yields byte-identical wire bytes.

`--chips 4` runs only the fleet phase and what it is compared with: the
same mixed fleet on `Dispatcher(mesh=4)` against the unsharded gang on one
chip of the same process (byte-identical frames), then the
`DeviceLossInjector({1: 2, 3: 0})` drill, 4 -> 3 -> 2 devices, with zero
acknowledged frames lost.

Every timing printed is smoke wall-clock, not a benchmark. The last line
of standard output is the JSON contract line; any failed check or a device
that is not a TPU exits non-zero before it.
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
WALL = "smoke wall-clock, not a benchmark"

#: Table 4 datasets (data/datasets.py DATASETS)
DATASETS = ("ecg", "rovio", "sensor", "stock", "stock_key", "micro")

#: session groups: (label, codec, extra JobSpec fields). Session i joins
#: group i % len(GROUPS) and draws dataset (i // len(GROUPS)) % 6.
GROUPS = (
    ("tcomp32", "tcomp32", {}),
    ("tcomp32+rans+crc", "tcomp32", {"entropy": "rans", "integrity": "crc32c"}),
    ("rle", "rle", {}),
    ("rle+crc", "rle", {"integrity": "crc32c"}),
    ("tdic32", "tdic32", {}),
    ("tdic32+dict", "tdic32", {"dictionary": "fleet:v1"}),
    ("pla", "pla", {}),
    ("pla+rans", "pla", {"entropy": "rans"}),
)
#: lossless stateless + stateful mix for the four-chip fleet phase
FLEET_GROUPS = tuple(g for g in GROUPS if g[0] in ("tcomp32", "rle", "tdic32"))

FLUSH_TUPLES = 2048  # 8 KiB micro-batches of 4-byte tuples (Fig 11)
ZIPF = 0.7  # burstiness of arrivals (data/stream.py)


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def check(ok: bool, name: str, detail: str) -> None:
    print(f"check {name}: {'ok' if ok else 'FAILED'} ({detail})")
    if not ok:
        fail(f"{name}: {detail}")


#: jax.monitoring event timing each backend compile, persistent-cache
#: hits included (the span wraps the cache lookup)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileMeter:
    """Counts backend compiles (cache hits included) and their seconds
    through jax.monitoring; `mark(phase)` closes the running phase."""

    def __init__(self):
        import jax

        self.n = 0
        self.secs = 0.0
        self.hits = 0
        self.names = collections.Counter()  # compiles per program, this phase
        self.phases = []
        self._last = (0, 0.0, 0)
        def on_duration(name, secs, fun_name="?", **_):
            if name == COMPILE_EVENT:
                self.n += 1
                self.secs += secs
                self.names[fun_name] += 1

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def mark(self, phase: str) -> None:
        n0, s0, h0 = self._last
        self.phases.append((phase, self.n - n0, self.secs - s0, self.hits - h0))
        self._last = (self.n, self.secs, self.hits)
        n, s, h = self.phases[-1][1:]
        top = ", ".join(f"{k} x{v}" for k, v in self.names.most_common(6))
        self.names.clear()
        print(f"compiles {phase}: {n} ({h} from the persistent cache), "
              f"{s:.3f} s compiling ({WALL}); most: {top or '-'}")


# ------------------------------------------------------------- workload --
def make_feeds(n_sessions: int, tuples: int, seed: int, groups):
    """Per-session (group index, values, timestamps), built in bulk from
    `seed`: one generator call per dataset, sliced into sessions."""
    import numpy as np

    from repro.data import make_dataset
    from repro.data.datasets import DATASETS as META
    from repro.data.stream import rate_for_dataset, zipf_timestamps

    ng = len(groups)
    ds_of = [(i // ng) % len(DATASETS) for i in range(n_sessions)]
    streams = {}
    for d, name in enumerate(DATASETS):
        count = ds_of.count(d)
        if count:
            wpt = META[name][2]
            n_rows = -(-count * tuples // wpt)
            streams[d] = make_dataset(name, n_tuples=n_rows, seed=seed + d).stream()
    used = dict.fromkeys(streams, 0)
    rate = rate_for_dataset(1)  # 4-byte tuples at the paper's 16 MB/s
    feeds = []
    for i in range(n_sessions):
        d = ds_of[i]
        k = used[d]
        used[d] += 1
        vals = np.ascontiguousarray(streams[d][k * tuples:(k + 1) * tuples])
        ts = zipf_timestamps(tuples, rate, zipf_factor=ZIPF, seed=seed * 100003 + i)
        feeds.append((i % ng, vals, ts))
    return feeds


def train_fleet_dictionary(seed: int):
    """Publish the run-time trained dictionary `fleet:v1` into a fresh
    in-memory default registry, trained from seeded rovio-like keys."""
    from repro.core import dictstore
    from repro.data import make_dataset

    registry = dictstore.DictRegistry()
    dictstore.set_default_registry(registry)
    sample = make_dataset("rovio", n_tuples=16384, seed=seed + 1000).stream()
    registry.publish(dictstore.train_dict(sample, idx_bits=12, topic="fleet"))
    return registry


def group_specs(groups, feeds):
    """One JobSpec per group, calibrated on the group's first session's
    data; a trained-dictionary spec keeps the dictionary's own idx_bits."""
    from repro import cstream

    first = {}
    for g, vals, _ in feeds:
        first.setdefault(g, vals)
    specs = []
    for g, (_, codec, extra) in enumerate(groups):
        spec = cstream.JobSpec(
            codec=codec,
            egress=True,
            gang=True,
            lanes=4,
            micro_batch_bytes=4 * FLUSH_TUPLES,
            flush_tuples=FLUSH_TUPLES,
            **extra,
        )
        calibrate = g in first and spec.dictionary is None
        specs.append(spec.calibrated(first[g]) if calibrate else spec)
    return specs


def serve(specs, feeds, mesh=None, fault=None, topics=None):
    """Admit every session on one gang Dispatcher, replay the feeds, drain.
    Returns (dispatcher, handles, report)."""
    from repro import cstream

    d = cstream.Dispatcher(
        gang=True, mesh=mesh, fault_injector=fault, max_sessions=len(feeds) + 8
    )
    members = {}
    for i, (g, _, _) in enumerate(feeds):
        members.setdefault(g, []).append(i)
    handles = [None] * len(feeds)
    for g, idx in members.items():
        names = [f"s{i:05d}" if topics is None else topics[i] for i in idx]
        for i, h in zip(idx, d.open_many(specs[g], topics=names)):
            handles[i] = h
    for h, (_, vals, ts) in zip(handles, feeds):
        h.push(vals, timestamps=ts)
    rep = d.close()
    return d, handles, rep


def wire_of(handles):
    """Serialized wire bytes per handle: the session's closing frames."""
    return [[f.to_bytes() for f in h.frames()] for h in handles]


def collect(handles, feeds, wires):
    """Collector side: decode every session's serialized frames through
    `DecompressionPipeline.ingest` and hold them to the codec's contract.
    Returns (lossless exact, lossy in bound, lossless, lossy, worst error)."""
    import numpy as np

    from repro.core.pipeline import DecompressionPipeline

    decoders = {}
    exact = in_bound = n_lossless = n_lossy = 0
    worst = 0.0
    for h, (_, vals, _), frames in zip(handles, feeds, wires):
        plan = h.plan
        dec = decoders.get(id(plan))
        if dec is None:
            dec = DecompressionPipeline(plan.spec, codec=plan.codec, plan=plan.execution)
            decoders[id(plan)] = dec
        parts = [dec.ingest(buf).values for buf in frames]
        out = np.concatenate(parts) if parts else np.zeros(0, np.uint32)
        if out.shape != vals.shape:
            fail(f"{h.topic}: decoded {out.shape} tuples, fed {vals.shape}")
        if plan.cap.lossy:
            n_lossy += 1
            err = float(np.max(np.abs(out.astype(np.float64) - vals.astype(np.float64))))
            worst = max(worst, err)
            in_bound += err <= plan.codec.error_bound()
        else:
            n_lossless += 1
            exact += bool(np.array_equal(out, vals))
    return exact, in_bound, n_lossless, n_lossy, worst


# ---------------------------------------------------------------- phases --
def one_chip(args, meter) -> None:
    import jax

    t0 = time.perf_counter()
    train_fleet_dictionary(args.seed)
    feeds = make_feeds(args.sessions, args.tuples, args.seed, GROUPS)
    specs = group_specs(GROUPS, feeds)
    in_bytes = sum(v.nbytes for _, v, _ in feeds)
    print(f"sessions: {len(feeds)} in {len(GROUPS)} codec groups "
          f"({', '.join(g[0] for g in GROUPS)})")
    print(f"input bytes: {in_bytes} ({in_bytes / 2**20:.1f} MiB), "
          f"{args.tuples} tuples/session, flush_tuples={FLUSH_TUPLES}")
    print(f"set-up: {time.perf_counter() - t0:.3f} s ({WALL})")
    meter.mark("set-up")

    # warm-up: two sessions per group, one micro-batch each plus a tail. Each
    # Dispatcher builds its own pipelines and jit caches, so the warm-up
    # fills the persistent compile cache, which the run then reads back
    t0 = time.perf_counter()
    warm = make_feeds(2 * len(GROUPS), FLUSH_TUPLES + 512, args.seed + 7, GROUPS)
    _, wh, _ = serve(specs, warm, topics=[f"w{i}" for i in range(len(warm))])
    collect(wh, warm, wire_of(wh))
    print(f"warm-up: {time.perf_counter() - t0:.3f} s ({WALL})")
    meter.mark("warm-up")

    t0 = time.perf_counter()
    _, handles, rep = serve(specs, feeds)
    t_serve = time.perf_counter() - t0
    waves = sum(s.n_waves for s in rep.dispatch_stats.values())
    solo = sum(s.n_solo for s in rep.dispatch_stats.values())
    flushes = sum(r.n_flushes for r in rep.sessions.values())
    print(f"serve: {t_serve:.3f} s ({WALL}); {flushes} flushes in {waves} gang "
          f"waves + {solo} solo dispatches; {rep.n_dispatches} dispatches")
    print(f"modeled (RK3399 profile, not measured): makespan "
          f"{rep.makespan_s:.6f} s, energy {rep.energy_j:.6f} J")
    meter.mark("serve")

    t0 = time.perf_counter()
    wires = wire_of(handles)
    wire_bytes = sum(len(b) for w in wires for b in w)
    exact, in_bound, n_ll, n_ly, worst = collect(handles, feeds, wires)
    print(f"wire bytes: {wire_bytes} (ratio {in_bytes / max(wire_bytes, 1):.4f})")
    for g, (label, _, _) in enumerate(GROUPS):
        g_in = sum(v.nbytes for k, v, _ in feeds if k == g)
        g_wire = sum(len(b) for (k, _, _), w in zip(feeds, wires) if k == g for b in w)
        print(f"  {label}: {g_in} -> {g_wire} bytes (ratio {g_in / max(g_wire, 1):.4f})")
    print(f"collect (to_bytes + ingest decode): {time.perf_counter() - t0:.3f} s ({WALL})")
    meter.mark("collect")
    check(exact == n_ll, "lossless_bit_exact", f"{exact}/{n_ll} sessions")
    check(in_bound == n_ly, "lossy_within_bound", f"{in_bound}/{n_ly} sessions, "
          f"worst max-abs error {worst}")
    if args.reduced:
        print(f"scale: reduced by --sessions/--tuples ({len(feeds)} sessions, "
              f"{in_bytes} bytes), not the D1 size")
    else:
        check(len(feeds) >= 1024, "scale_sessions", f"{len(feeds)} sessions")
        check(in_bytes >= 64 * 2**20, "scale_bytes", f"{in_bytes} input bytes")

    # the same subset on the host CPU, in this process: the wire format is
    # deterministic, so any difference is a bug
    t0 = time.perf_counter()
    pick = list(range(min(args.cpu_subset, len(feeds))))
    pick = sorted({(i * len(feeds)) // max(len(pick), 1) for i in pick})
    sub = [feeds[i] for i in pick]
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        _, ch, _ = serve(specs, sub, topics=[f"s{i:05d}" for i in pick])
        cpu_wires = wire_of(ch)
    same = sum(cpu_wires[k] == wires[i] for k, i in enumerate(pick))
    print(f"cpu rerun of {len(pick)} sessions: {time.perf_counter() - t0:.3f} s ({WALL})")
    meter.mark("cpu-rerun")
    check(same == len(pick), "chip_vs_cpu_wire_identical",
          f"{same}/{len(pick)} sessions byte-identical on {cpu.platform}")


def record_wave_shards(limit: int = 3):
    """Wrap the gang step so the first sharded waves print the per-device
    shard shapes of their stacked input blocks."""
    from repro.core.pipeline import CompressionPipeline

    orig = CompressionPipeline.gang_step
    seen = []

    def gang_step(self, states, blocks, masks, meta7=False, mesh=None):
        if mesh is not None and len(seen) < limit:
            shards = sorted(
                (s.device.id, tuple(s.data.shape)) for s in blocks.addressable_shards
            )
            seen.append(shards)
            print(f"wave {len(seen)} input blocks {tuple(blocks.shape)} on "
                  f"{len(shards)} devices: " + ", ".join(
                      f"dev{d}:{shape}" for d, shape in shards))
        return orig(self, states, blocks, masks, meta7=meta7, mesh=mesh)

    CompressionPipeline.gang_step = gang_step
    return seen


def four_chips(args, meter) -> None:
    import jax

    from repro.runtime.fault import DeviceLossInjector

    n_dev = len(jax.devices())
    check(n_dev >= 4, "four_devices", f"{n_dev} visible devices")
    t0 = time.perf_counter()
    train_fleet_dictionary(args.seed)
    feeds = make_feeds(args.fleet_sessions, args.fleet_tuples, args.seed, FLEET_GROUPS)
    specs = group_specs(FLEET_GROUPS, feeds)
    in_bytes = sum(v.nbytes for _, v, _ in feeds)
    print(f"fleet sessions: {len(feeds)} ({', '.join(g[0] for g in FLEET_GROUPS)}), "
          f"input bytes: {in_bytes}")
    meter.mark("set-up")

    t0 = time.perf_counter()
    _, base_h, base_rep = serve(specs, feeds)
    base = wire_of(base_h)
    print(f"unsharded gang on one chip: {time.perf_counter() - t0:.3f} s ({WALL}), "
          f"{sum(len(b) for w in base for b in w)} wire bytes")
    meter.mark("gang-1-chip")

    seen = record_wave_shards()
    t0 = time.perf_counter()
    _, sh_h, sh_rep = serve(specs, feeds, mesh=4)
    shard = wire_of(sh_h)
    waves = sum(s.n_waves for s in sh_rep.dispatch_stats.values())
    print(f"sharded mesh=4: {time.perf_counter() - t0:.3f} s ({WALL}), {waves} waves")
    meter.mark("mesh-4")
    check(bool(seen) and all(len(s) == 4 for s in seen), "waves_span_4_devices",
          f"{len(seen)} recorded waves")
    same = sum(a == b for a, b in zip(shard, base))
    check(same == len(base), "sharded_wire_identical",
          f"{same}/{len(base)} sessions byte-identical to the one-chip gang")

    class LoudInjector(DeviceLossInjector):
        """Prints each injected loss as it fires, so a run that dies inside
        the drill shows how far it got."""

        def maybe_fail(self, wave):
            try:
                super().maybe_fail(wave)
            except Exception as loss:
                print(f"drill: injecting '{loss}'")
                raise

    t0 = time.perf_counter()
    inj = LoudInjector({1: 2, 3: 0})
    d, ch_h, ch_rep = serve(specs, feeds, mesh=4, fault=inj)
    chaos = wire_of(ch_h)
    print(f"device-loss drill: {time.perf_counter() - t0:.3f} s ({WALL}), "
          f"fault events {ch_rep.fault_events}")
    meter.mark("drill")
    path = [4] + [e["n_devices"] for e in ch_rep.fault_events]
    check(path == [4, 3, 2] and ch_rep.devices == 2, "drill_4_3_2",
          f"devices {' -> '.join(map(str, path))}")
    acked = sum(len(s.flushes) for s in d.sessions.values())
    base_acked = sum(r.n_flushes for r in base_rep.sessions.values())
    same = sum(a == b for a, b in zip(chaos, base))
    check(same == len(base) and acked == base_acked, "drill_zero_acknowledged_loss",
          f"{same}/{len(base)} sessions byte-identical, {acked}/{base_acked} "
          "acknowledged flushes")
    exact, _, n_ll, _, _ = collect(ch_h, feeds, chaos)
    check(exact == n_ll, "drill_decode_bit_exact", f"{exact}/{n_ll} sessions")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the D1 served path (default); 4: the fleet phase only")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sessions", type=int, default=1024)
    ap.add_argument("--tuples", type=int, default=16384, help="4-byte tuples per session")
    ap.add_argument("--cpu-subset", type=int, default=16)
    ap.add_argument("--fleet-sessions", type=int, default=192)
    ap.add_argument("--fleet-tuples", type=int, default=8192)
    args = ap.parse_args(argv)
    args.reduced = args.sessions < 1024 or args.tuples < 16384
    sys.stdout.reconfigure(line_buffering=True)  # phases show as they end
    if os.environ.get("JAX_PLATFORMS") == "tpu":
        os.environ["JAX_PLATFORMS"] = "tpu,cpu"  # the host rerun needs the CPU

    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: refusing to run: JAX found no TPU "
              f"(devices[0] is {dev.platform}:{dev.device_kind})", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro import compile_cache

    cache = compile_cache.enable()
    print(f"device: platform={dev.platform} kind={dev.device_kind} count={len(devs)}")
    print(f"compile cache: {cache}")
    meter = CompileMeter()
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(args, meter)
    else:
        one_chip(args, meter)
    warm = sum(p[1] for p in meter.phases if p[0] in ("set-up", "warm-up"))
    rest = sum(p[1] for p in meter.phases if p[0] not in ("set-up", "warm-up"))
    print(f"compiles total: {meter.n} ({meter.hits} from the persistent cache), "
          f"{meter.secs:.3f} s; through warm-up {warm}, after warm-up {rest}")
    print(f"wall: {time.perf_counter() - t0:.3f} s ({WALL})")
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": len(devs)},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
