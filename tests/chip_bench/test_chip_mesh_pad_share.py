"""The reader of the gang waves' pad counters (`mesh_pad_share`): silent
where the program has no recorder or counts no wave slots, and pad slots
over all slots, in %, where it does."""
import sys
import types

import pytest

from benchmarks.chip import registry
from repro.runtime import spans

NAME = "mesh_pad_share"


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
        ({}, None),  # a program that counts no waves
        ({"wave.pad_slots": 7}, None),  # pad counted, slots not
        ({"wave.pad_slots": 9, "wave.slots": 4, "other": 1}, 225.0),
        ({"wave.slots": 40}, 0.0),  # one chip: waves with no pad
        ({"wave.slots": 96, "wave.pad_slots": 3}, 3.125),
    ],
)
def test_reader_gives_pad_slots_over_slots_in_percent(monkeypatch, counters, want):
    _counted(monkeypatch, counters)
    assert registry.load_reader(NAME)(types.SimpleNamespace()) == want
