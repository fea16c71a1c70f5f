"""The readers of the program's own counters (`benchmarks/chip/program.py`
and the three metrics on it): silent where the program has no recorder or
nothing was counted, and the ratios a tiny cell's steps give on the CPU."""
import sys
import types

import numpy as np
import pytest

from benchmarks.chip import registry, run
from benchmarks.chip.harness import Cell
from repro.runtime import spans

READERS = {
    "replay_tuples_per_run": ("replay.tuples", "replay.runs"),
    "marshals_per_frame": ("egress.frames_marshalled", "handle.frames_delivered"),
    "decodes_per_frame": ("decode.frames", "collector.frames_ingested"),
}
CPU = {"platform": "cpu", "kind": "cpu", "count": 1, "memory_peak_bytes": 0}


def _counted(monkeypatch, counters):
    monkeypatch.setattr(spans, "snapshot", lambda: {"spans": {}, "counters": dict(counters)})


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_is_silent_without_the_recorder(monkeypatch, name):
    import repro.runtime

    monkeypatch.delattr(repro.runtime, "spans")
    monkeypatch.setitem(sys.modules, "repro.runtime.spans", None)  # import fails
    assert registry.load_reader(name)(types.SimpleNamespace()) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_is_silent_with_nothing_counted(monkeypatch, name):
    num, _ = READERS[name]
    _counted(monkeypatch, {num: 7})
    assert registry.load_reader(name)(types.SimpleNamespace()) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_divides_its_counters(monkeypatch, name):
    num, den = READERS[name]
    _counted(monkeypatch, {num: 9, den: 4, "other": 1})
    assert registry.load_reader(name)(types.SimpleNamespace()) == 2.25


def test_a_tiny_cell_reads_its_steps_ratios(monkeypatch):
    """Each step replays the same arrivals, and today's served path
    marshals and decodes every delivered frame twice (once more for the
    report's fidelity check); a change that removes the duplicate updates
    the 2.0s."""
    from repro.core import dictstore

    old = dictstore.set_default_registry(None)
    dictstore.set_default_registry(old)
    wl = registry.workload("lossy.closed")
    wl.config = dict(wl.config, sessions=8)
    before = spans.snapshot()
    try:
        cell = Cell(wl, seed=2**33 + 17, seconds=0.2)
        cell.setup()
        cell.run_window()
        cell.check()
    finally:
        dictstore.set_default_registry(old)
    snap = spans.snapshot
    monkeypatch.setattr(spans, "snapshot", lambda: spans.since(before, snap()))
    line = run.result(cell, wl, True, dict(CPU), registry.load_peaks("TPU v5 lite"))
    got = {k: v["value"] for k, v in line["metrics"].items() if k in READERS}
    # the merged order of one step: topics sorted by name, ties in topic order
    ts = np.concatenate(cell.fleet.arrivals)
    idx = np.repeat(np.arange(cell.fleet.S), [len(a) for a in cell.fleet.arrivals])
    runs = np.count_nonzero(np.diff(idx[np.argsort(ts, kind="stable")])) + 1
    assert got == {
        "replay_tuples_per_run": pytest.approx(len(ts) / runs, rel=1e-12),
        "marshals_per_frame": 2.0,
        "decodes_per_frame": 2.0,
    }
    assert line["correct"] is True
