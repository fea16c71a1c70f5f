"""The reader of the event replay's step counter (`replay_tuples_per_step`):
silent where the program has no recorder or counts no steps, and bounded
on a tiny cell's steps by the flushes, waves and topics a step serves."""
import sys
import types

import numpy as np
import pytest

from benchmarks.chip import registry, run
from benchmarks.chip.harness import Cell
from repro.runtime import spans

NAME = "replay_tuples_per_step"
CPU = {"platform": "cpu", "kind": "cpu", "count": 1, "memory_peak_bytes": 0}


def _counted(monkeypatch, counters):
    monkeypatch.setattr(spans, "snapshot", lambda: {"spans": {}, "counters": dict(counters)})


def test_reader_is_silent_without_the_recorder(monkeypatch):
    import repro.runtime

    monkeypatch.delattr(repro.runtime, "spans")
    monkeypatch.setitem(sys.modules, "repro.runtime.spans", None)  # import fails
    assert registry.load_reader(NAME)(types.SimpleNamespace()) is None


@pytest.mark.parametrize(
    "counters,want",
    [
        ({"replay.tuples": 7, "replay.runs": 5}, None),  # a walk with no step counter
        ({"replay.tuples": 9000, "replay.steps": 4, "replay.runs": 1}, 2250.0),
    ],
)
def test_reader_divides_tuples_by_steps(monkeypatch, counters, want):
    _counted(monkeypatch, counters)
    assert registry.load_reader(NAME)(types.SimpleNamespace()) == want


def test_a_tiny_cell_steps_only_where_flushes_and_waves_act(monkeypatch):
    """On a tiny cell, the walk's steps stay within one per flush, wave
    and topic of each served step (plus one), the runs are the merged
    order's runs, and the reader gives tuples over steps."""
    from repro.core import dictstore

    old = dictstore.set_default_registry(None)
    dictstore.set_default_registry(old)
    wl = registry.workload("lossless.closed")
    wl.config = dict(wl.config, sessions=8)
    before = spans.snapshot()
    try:
        cell = Cell(wl, seed=2**34 + 5, seconds=0.2)
        cell.setup()
        cell.run_window()
        cell.check()
    finally:
        dictstore.set_default_registry(old)
    counters = spans.since(before, spans.snapshot())["counters"]
    served = [cell.steps[0]] * (len(cell.steps) + 1)  # the warm-up step is alike
    flushes = sum(sum(st["flushes"]) for st in served)
    # dispatch stats count from the Dispatcher's start: every step's waves
    waves = sum(st.n_waves + st.n_solo for st in cell.report.dispatch_stats.values())
    bound = flushes + waves + len(served) * (cell.fleet.S + 1)
    assert 0 < counters["replay.steps"] <= bound
    ts = np.concatenate(cell.fleet.arrivals)
    idx = np.repeat(np.arange(cell.fleet.S), [len(a) for a in cell.fleet.arrivals])
    runs = np.count_nonzero(np.diff(idx[np.argsort(ts, kind="stable")])) + 1
    assert counters["replay.runs"] == runs * len(served)
    assert counters["replay.tuples"] == len(ts) * len(served)
    snap = spans.snapshot
    monkeypatch.setattr(spans, "snapshot", lambda: {"spans": {}, "counters": counters})
    line = run.result(cell, wl, True, dict(CPU), registry.load_peaks("TPU v5 lite"))
    monkeypatch.setattr(spans, "snapshot", snap)
    assert line["metrics"][NAME]["value"] == pytest.approx(
        counters["replay.tuples"] / counters["replay.steps"], rel=1e-12
    )
    assert line["correct"] is True
