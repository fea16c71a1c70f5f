"""`lossless.closed` at a tiny size on the CPU, read by the benchmark's own
readers: the report checks no frame unless its fidelity is read, so each
delivered frame is marshalled once (by `StreamHandle.frames()`) and decoded
once (by the collector), and every rotation still passes the reference."""
from benchmarks.chip import registry, run
from benchmarks.chip.harness import Cell
from repro.runtime import spans

CPU = {"platform": "cpu", "kind": "cpu", "count": 1, "memory_peak_bytes": 0}


def test_lossless_cell_marshals_and_decodes_each_frame_once(monkeypatch):
    from repro.core import dictstore

    old = dictstore.set_default_registry(None)
    dictstore.set_default_registry(old)
    wl = registry.workload("lossless.closed")
    wl.config = dict(wl.config, sessions=14)  # one session per group
    before = spans.snapshot()
    try:
        cell = Cell(wl, seed=2**34 + 4099, seconds=0.2)
        cell.setup()
        cell.run_window()
        cell.check()
    finally:
        dictstore.set_default_registry(old)
    snap = spans.snapshot
    monkeypatch.setattr(spans, "snapshot", lambda: spans.since(before, snap()))
    line = run.result(cell, wl, True, dict(CPU), registry.load_peaks("TPU v5 lite"))
    assert line["correct"] is True and line["failed"] == 0
    got = {k: line["metrics"][k]["value"] for k in ("marshals_per_frame", "decodes_per_frame")}
    assert got == {"marshals_per_frame": 1.0, "decodes_per_frame": 1.0}
    # the served path never ran the report's check
    assert "report.fidelity" not in spans.snapshot()["spans"]
