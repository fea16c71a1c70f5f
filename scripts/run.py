"""cstream ops CLI — driven entirely by the unified job API (`repro.cstream`).

    PYTHONPATH=src python scripts/run.py --list-codecs
    PYTHONPATH=src python scripts/run.py --smoke
    PYTHONPATH=src python scripts/run.py --compress rle --dataset micro -n 65536

`--list-codecs` prints the capability registry (registry name, paper Table 1
name, wire id, capabilities) the negotiation layer keys on. `--smoke` is the
CI api-stability gate: it serializes/negotiates/opens a JobSpec for every
Table 1 codec through `repro.cstream` only, and is run under
`-W error::DeprecationWarning` so any legacy-shim leakage into the new
surface fails the job.
"""
from __future__ import annotations

import argparse
import json
import sys


def list_codecs() -> int:
    from repro import cstream

    cols = [
        "name", "table1", "wire", "lossy", "stateful", "kind", "scope",
        "maskable", "aligned", "entropy", "dict", "integrity", "bound", "params",
    ]
    rows = []
    for c in cstream.capabilities():
        rows.append({
            "name": c.name,
            "table1": c.paper_name or "-",
            "wire": str(c.wire_id) if c.wire_id is not None else "-",
            "entropy": ",".join(c.entropy) or "-",
            "dict": "yes" if c.state_kind == "dictionary" else "-",
            "integrity": ",".join(c.integrity) or "-",
            "lossy": "lossy" if c.lossy else "lossless",
            "stateful": "yes" if c.stateful else "no",
            "kind": c.state_kind,
            "scope": c.scope,
            "maskable": "yes" if c.maskable else "no",
            "aligned": "yes" if c.aligned else "no",
            "bound": (
                "-" if c.default_error_bound is None
                else f"{c.default_error_bound:.4g}"
            ),
            "params": ",".join(c.accepted_params) or "-",
        })
    widths = {k: max(len(k), max(len(r[k]) for r in rows)) for k in cols}
    print("  ".join(k.ljust(widths[k]) for k in cols))
    for r in rows:
        print("  ".join(r[k].ljust(widths[k]) for k in cols))
    return 0


def list_dicts() -> int:
    """Dump the default trained-dictionary registry (DESIGN.md §17)."""
    from repro.core import dictstore

    reg = dictstore.default_registry()
    rows = [
        {
            "ref": f"{r['topic']}:v{r['version']}",
            "idx_bits": str(r["idx_bits"]),
            "entries": str(r["entries"]),
            "bytes": str(r["bytes"]),
            "hash": str(r["hash"]),
            "pinned": "yes" if r["pinned"] else "-",
        }
        for r in reg.summary()
    ]
    if not rows:
        root = reg.root or "<in-memory>"
        print(f"no trained dictionaries published (registry root: {root}); "
              f"train with dictstore.train_dict and publish, or set CSTREAM_DICT_ROOT")
        return 0
    cols = ["ref", "idx_bits", "entries", "bytes", "hash", "pinned"]
    widths = {k: max(len(k), max(len(r[k]) for r in rows)) for k in cols}
    print("  ".join(k.ljust(widths[k]) for k in cols))
    for r in rows:
        print("  ".join(r[k].ljust(widths[k]) for k in cols))
    return 0


def smoke() -> int:
    """API-stability smoke: serialize/negotiate/open across all ten codecs."""
    import numpy as np

    from repro import cstream

    # gate on the ten Table 1 codecs; extension codecs (paper_name None)
    # may exist in the registry without breaking API stability
    names = [c.name for c in cstream.capabilities() if c.paper_name is not None]
    assert len(names) == 10, f"expected the ten Table 1 codecs, saw {names}"
    rng = np.random.default_rng(0)
    values = np.repeat(rng.integers(0, 4096, size=512).astype(np.uint32), 5)
    failures = []
    for name in names:
        try:
            spec = cstream.JobSpec(codec=name, micro_batch_bytes=2048, egress=True)
            spec = cstream.JobSpec.from_dict(
                json.loads(json.dumps(spec.to_dict()))  # wire round-trip
            )
            assert spec == cstream.JobSpec.from_dict(spec.to_dict())
            plan = cstream.negotiate(spec)
            assert plan.cap.wire_id is not None
            with cstream.open(spec, sample=values) as h:
                h.push(values)
                seg = h.flush()
                rep = h.report()
            assert seg is not None and rep.n_tuples == values.size
            assert rep.fidelity is not None and rep.fidelity.within_bound
            print(f"  [OK] {name}: ratio {rep.ratio:.2f}, "
                  f"fidelity max_abs {rep.fidelity.max_abs:.3g}")
        except Exception as exc:  # noqa: BLE001 — the smoke reports per codec
            failures.append(name)
            print(f"  [FAIL] {name}: {type(exc).__name__}: {exc}")
    print(f"api smoke: {len(names) - len(failures)}/{len(names)} codecs pass")
    if _fleet_smoke():
        failures.append("fleet")
    if _entropy_smoke():
        failures.append("entropy")
    if _dict_smoke():
        failures.append("dict")
    if _chaos_smoke():
        failures.append("chaos")
    return 1 if failures else 0


def _entropy_smoke() -> int:
    """Entropy-stage gate (DESIGN.md §15): negotiate/open/roundtrip a
    JobSpec with entropy='rans', and check the invalid combination fails
    with a single-line NegotiationError."""
    import numpy as np

    from repro import cstream
    from repro.core import bits

    try:
        try:  # entropy without egress must be refused, on one line
            cstream.negotiate(cstream.JobSpec(codec="rle", entropy="rans"))
        except cstream.NegotiationError as exc:
            assert "\n" not in str(exc), "multi-line NegotiationError"
        else:
            raise AssertionError("entropy without egress negotiated")
        spec = cstream.JobSpec(
            codec="rle", egress=True, entropy="rans", micro_batch_bytes=2048
        )
        plan = cstream.negotiate(spec)
        assert plan.entropy is not None and plan.entropy.kind == "rans"
        rng = np.random.default_rng(0)
        values = np.repeat(rng.integers(0, 64, size=512).astype(np.uint32), 8)
        with cstream.open(spec, sample=values) as h:
            seg = h.push(values).flush()
            rep = h.report()
        assert rep.fidelity.bit_exact
        frame = bits.Frame.from_bytes(seg.frame.to_bytes())  # wire-parseable
        assert frame.n_valid == values.size
        print(f"  [OK] entropy: rans roundtrip, wire {seg.frame.wire_bytes}B")
        return 0
    except Exception as exc:  # noqa: BLE001 — same reporting as the codec loop
        print(f"  [FAIL] entropy: {type(exc).__name__}: {exc}")
        return 1


def _dict_smoke() -> int:
    """Trained-dictionary gate (DESIGN.md §17): train/publish/negotiate a
    seeded tdic32 job, roundtrip bit-exact, and check the two invalid
    combinations fail with single-line NegotiationErrors."""
    import numpy as np

    from repro import cstream
    from repro.core import dictstore

    registry = dictstore.DictRegistry()
    prev = dictstore.set_default_registry(registry)
    try:
        rng = np.random.default_rng(3)
        book = rng.integers(0, 1 << 32, size=256, dtype=np.uint64).astype(np.uint32)
        sample = book[(rng.zipf(1.3, size=4096) - 1) % book.size]
        registry.publish(dictstore.train_dict(sample, idx_bits=12, topic="smoke"))
        for bad in (  # non-dictionary codec / unknown topic: one-line refusals
            cstream.JobSpec(codec="rle", egress=True, dictionary="smoke:v1"),
            cstream.JobSpec(codec="tdic32", egress=True, dictionary="nope:v1"),
        ):
            try:
                cstream.negotiate(bad)
            except cstream.NegotiationError as exc:
                assert "\n" not in str(exc), "multi-line NegotiationError"
            else:
                raise AssertionError(f"negotiated invalid dictionary spec {bad}")
        spec = cstream.JobSpec(codec="tdic32", egress=True, dictionary="smoke:latest")
        plan = cstream.negotiate(spec)
        assert plan.dictionary is not None and plan.dictionary.version == 1
        values = book[(rng.zipf(1.3, size=2048) - 1) % book.size]
        with cstream.open(spec) as h:
            seg = h.push(values).flush()
            rep = h.report()
        assert rep.fidelity.bit_exact and seg.frame.dict_id == ("smoke", 1)
        print(f"  [OK] dict: seeded roundtrip, wire {seg.frame.wire_bytes}B, "
              f"id {seg.frame.dict_id}")
        return 0
    except Exception as exc:  # noqa: BLE001 — same reporting as the codec loop
        print(f"  [FAIL] dict: {type(exc).__name__}: {exc}")
        return 1
    finally:
        dictstore.set_default_registry(prev)


def _chaos_smoke() -> int:
    """Hardened-wire gate (DESIGN.md §18): negotiate a CRC-protected job,
    roundtrip it bit-exact through the collector ingest path, then corrupt
    one byte on the wire — the decoder must refuse with a single-line
    FrameIntegrityError, quarantine, and resume exactly after reset."""
    import numpy as np

    from repro import cstream
    from repro.core import bits
    from repro.core.pipeline import DecompressionPipeline

    try:
        try:  # integrity without egress must be refused, on one line
            cstream.negotiate(cstream.JobSpec(codec="rle", integrity="crc32c"))
        except cstream.NegotiationError as exc:
            assert "\n" not in str(exc), "multi-line NegotiationError"
        else:
            raise AssertionError("integrity without egress negotiated")
        spec = cstream.JobSpec(
            codec="tcomp32", egress=True, integrity="crc32c", micro_batch_bytes=2048
        )
        plan = cstream.negotiate(spec)
        assert plan.integrity is not None and plan.integrity.kind == "crc32c"
        rng = np.random.default_rng(5)
        values = np.repeat(rng.integers(0, 4096, size=512).astype(np.uint32), 4)
        with cstream.open(spec) as h:
            h.push(values).flush()
            frames = h.frames()
        dec = DecompressionPipeline(plan.spec, codec=plan.codec, plan=plan.execution)
        wire = frames[0].to_bytes()
        bad = bytearray(wire)
        bad[len(bad) // 2] ^= 0x40
        try:
            dec.ingest(bytes(bad))
        except bits.FrameIntegrityError as exc:
            assert "\n" not in str(exc), "multi-line FrameIntegrityError"
        else:
            raise AssertionError("corrupt CRC frame decoded")
        assert dec.quarantined is not None
        dec.reset_quarantine()
        got = dec.ingest(wire).values  # retransmit path: exact after reset
        assert np.array_equal(got, values)
        print(f"  [OK] chaos: crc32c roundtrip, corrupt byte refused + "
              f"quarantined, wire {len(wire)}B")
        return 0
    except Exception as exc:  # noqa: BLE001 — same reporting as the codec loop
        print(f"  [FAIL] chaos: {type(exc).__name__}: {exc}")
        return 1


def _fleet_smoke() -> int:
    """Fleet-surface gate (DESIGN.md §14), device-count independent: a
    mesh-of-1 Dispatcher must negotiate `JobSpec.devices`, admit many
    sessions through ONE negotiation (shared compiled pipeline), dispatch
    them as gang waves, and report the per-signature breakdown."""
    import numpy as np

    from repro import cstream

    try:
        spec = cstream.JobSpec(codec="tcomp32", gang=True, devices=1, flush_tuples=128)
        assert cstream.negotiate(spec).fleet is not None
        try:
            cstream.Dispatcher(mesh=1)  # mesh without gang must be refused
        except cstream.NegotiationError:
            pass
        else:
            raise AssertionError("Dispatcher(mesh=1) without gang=True passed")
        with cstream.Dispatcher(gang=True, mesh=1, max_sessions=64) as d:
            handles = d.open_many(spec, count=8)
            assert len({id(h._session.pipeline) for h in handles}) == 1
            for i, h in enumerate(handles):
                h.push(
                    np.arange(128, dtype=np.uint32),
                    timestamps=np.full(128, 0.001 * i),
                )
            d.run()
            rep = d.report()
        assert rep.devices == 1 and rep.total_tuples == 8 * 128
        assert rep.dispatch_stats and all(
            s.sessions_dispatched > 0 for s in rep.dispatch_stats.values()
        )
        print("  [OK] fleet: mesh-of-1 dispatch, shared-pipeline admission, "
              f"{sum(s.n_waves for s in rep.dispatch_stats.values())} waves")
        return 0
    except Exception as exc:  # noqa: BLE001 — same reporting as the codec loop
        print(f"  [FAIL] fleet: {type(exc).__name__}: {exc}")
        return 1


def compress(codec: str, dataset: str, n: int) -> int:
    import numpy as np

    from repro import cstream
    from repro.data.datasets import make_dataset

    values = make_dataset(dataset, n_tuples=n).stream()[:n]
    spec = cstream.JobSpec(codec=codec, egress=True)
    with cstream.open(spec, sample=values) as h:
        h.push(np.asarray(values, np.uint32))
        h.flush()
        rep = h.report()
    fid = rep.fidelity
    print(json.dumps({
        "codec": codec,
        "dataset": dataset,
        "n_tuples": rep.n_tuples,
        "ratio": rep.ratio,
        "wire_bytes": rep.wire_bytes,
        "compute_s": rep.wall_s,
        "makespan_s": rep.makespan_s,
        "energy_j": rep.energy_j,
        "bit_exact": fid.bit_exact,
        "max_abs": fid.max_abs,
        "nrmse": fid.nrmse,
    }, indent=1))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--list-codecs", action="store_true",
        help="print the codec capability registry (paper Table 1)",
    )
    ap.add_argument(
        "--list-dicts", action="store_true",
        help="print the default trained-dictionary registry (topic:vN rows)",
    )
    ap.add_argument(
        "--smoke", action="store_true",
        help="API-stability smoke over all ten codecs (CI gate)",
    )
    ap.add_argument(
        "--chaos", action="store_true",
        help="hardened-wire smoke: CRC roundtrip + corruption quarantine gate",
    )
    ap.add_argument("--compress", metavar="CODEC", help="compress a dataset stream")
    ap.add_argument("--dataset", default="micro", help="dataset name (default: micro)")
    ap.add_argument("-n", type=int, default=1 << 16, help="tuples to stream")
    args = ap.parse_args(argv)
    from repro import compile_cache

    compile_cache.enable()

    if args.list_codecs:
        return list_codecs()
    if args.list_dicts:
        return list_dicts()
    if args.smoke:
        return smoke()
    if args.chaos:
        return _chaos_smoke()
    if args.compress:
        return compress(args.compress, args.dataset, args.n)
    ap.print_help()
    return 2


if __name__ == "__main__":
    sys.exit(main())
