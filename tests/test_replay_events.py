"""The event-driven arrival replay (DESIGN.md §14) against the per-run walk.

`ServerCore._replay` visits only the runs of the merged arrival order
where a flush or a gang quantum edge acts. The oracle below is the walk it
replaced, kept verbatim: one `offer_many` per run of equal topic, a
deadline heap popped at every run end, and a gang edge poll at every run
end. Both must make the same `StreamSession.flush` calls (topic, batch,
stamp) and the same waves, interleaved in the same order, and so the same
flush records, dispatch counts and frame bytes, on every feed below.
"""
from __future__ import annotations

import heapq
import os
import subprocess
import sys
import textwrap
from typing import Dict, List, Optional, Tuple

import numpy as np
import pytest

from repro import cstream
from repro.core import dictstore
from repro.core.controller import ScriptedController, resolve_ladder
from repro.core.strategies import EngineConfig
from repro.data.stream import zipf_timestamps
from repro.runtime import spans
from repro.runtime.fault import DeviceLossInjector
from repro.runtime.server import FlushRecord, ServerCore, StreamSession, batch_end


# ------------------------------------------------------------------ oracle --
def _old_due(self, now: float) -> bool:
    """Size reached, or the oldest buffered tuple timed out."""
    if self._count >= self.capacity:
        return True
    deadline = self.flush_deadline
    return deadline is not None and now >= deadline


def _old_poll(self, now: float) -> Optional[FlushRecord]:
    if not _old_due(self, now):
        return None
    return self.flush(now=min(now, self.flush_deadline))


def _old_offer_many(self, values: np.ndarray, tss: np.ndarray) -> List[FlushRecord]:
    out: List[FlushRecord] = []

    def _flushed(rec: Optional[FlushRecord]) -> None:
        if rec is not None:
            out.append(rec)

    i, n = 0, len(values)
    while i < n:
        if self._count == 0:
            deadline = float(tss[i]) + self.flush_timeout_s
        else:
            deadline = self.flush_deadline
            if float(tss[i]) > deadline:  # timer fired before this tuple
                _flushed(self.flush(now=deadline))
                continue
        space = self.capacity - self._count
        # tuples that arrive before the current batch's deadline join it
        take = int(np.searchsorted(tss[i : i + space], deadline, side="right"))
        take = max(take, 1)  # tss[i] <= deadline by construction
        self._values[self._count : self._count + take] = values[i : i + take]
        self._arrivals[self._count : self._count + take] = tss[i : i + take]
        self._count += take
        i += take
        if self._count >= self.capacity:
            _flushed(self.flush(now=float(tss[i - 1])))
    return out


def _old_replay(
    self, topics: List[str], values: List[np.ndarray], tss: List[np.ndarray]
) -> None:
    """The per-run walk of the merged arrival order and its final drain."""
    # merged arrival order (stable: ties keep topic order)
    all_ts = np.concatenate(tss) if tss else np.zeros(0)
    topic_idx = np.concatenate(
        [np.full(len(ts), i, np.int32) for i, ts in enumerate(tss)]
    ) if tss else np.zeros(0, np.int32)
    within = np.concatenate(
        [np.arange(len(ts), dtype=np.int64) for ts in tss]
    ) if tss else np.zeros(0, np.int64)
    order = np.argsort(all_ts, kind="stable")

    sess = [self.sessions[t] for t in topics]
    next_edges: Dict[tuple, float] = {}

    def _quantum(sig: tuple) -> float:
        if self.gang_quantum_s is not None:
            return self.gang_quantum_s
        return self._gang_plans[sig].quantum_s

    def _poll_gang_edges(now: float) -> None:
        for sig in list(self._queues):
            if not self._queues[sig]:
                next_edges.pop(sig, None)
                continue
            q_s = _quantum(sig)
            edge = next_edges.get(sig)
            if edge is None:
                next_edges[sig] = (np.floor(now / q_s) + 1.0) * q_s
            elif now >= edge:
                self._dispatch_signature(sig)
                next_edges[sig] = (np.floor(now / q_s) + 1.0) * q_s

    pending: List[Tuple[float, int]] = []

    def _note(k: int) -> None:
        d = sess[k].flush_deadline
        if d is not None:
            heapq.heappush(pending, (d, k))

    i, n, runs = 0, len(order), 0
    while i < n:
        runs += 1
        j = i
        tpi = topic_idx[order[i]]
        while j < n and topic_idx[order[j]] == tpi:
            j += 1
        run_idx = within[order[i:j]]
        now = float(all_ts[order[j - 1]])
        _old_offer_many(sess[tpi], values[tpi][run_idx], tss[tpi][run_idx])
        _note(tpi)
        while pending and pending[0][0] <= now:
            d, k = heapq.heappop(pending)
            if sess[k].flush_deadline == d:  # else stale: batch moved on
                _old_poll(sess[k], now)
                _note(k)
        if self.gang:
            _poll_gang_edges(now)
        i = j
    for s in sess:
        if s.buffered:
            s.flush(s.flush_deadline)
    if self.gang:
        self._dispatch_all(final=True)
    spans.count("replay.tuples", n)
    spans.count("replay.runs", runs)


# ------------------------------------------------------------------- cases --
def _cfg(codec="tcomp32"):
    return EngineConfig(codec=codec, micro_batch_bytes=2048, lanes=4)


class _TickClock:
    """A clock that advances on every read: a breaker driven by it opens,
    probes and closes on the exact sequence of its calls."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        self.t += 1.0
        return self.t


def _server(topics_feeds, flush_tuples=64, codecs=("tcomp32", "rle"), egress=False, **kw):
    srv = ServerCore(max_sessions=64, egress=egress, **kw)
    for k, t in enumerate(sorted(topics_feeds)):
        srv.admit(t, _cfg(codecs[k % len(codecs)]), flush_tuples=flush_tuples)
    return srv, lambda: srv.run(topics_feeds)


def _values(n, seed):
    return np.cumsum(np.random.default_rng(seed).integers(0, 9, n)).astype(np.uint32)


def case_chip_arrivals():
    """The chip cells' arrival generator (benchmarks/chip/fleet.py: 4096
    tuples at 16 MB/s of 4-byte tuples, zipf 0.7, the closed traffic's
    arrival seed) at 16 sessions and the cells' 2048-tuple flush, through
    `Dispatcher.open_many` as the cells serve them."""
    from benchmarks.chip.fleet import TIMES, sub_seed

    spec = cstream.JobSpec(
        codec="tcomp32", egress=True, gang=True, lanes=4,
        micro_batch_bytes=8192, flush_tuples=2048,
    )
    d = cstream.Dispatcher(gang=True)
    handles = d.open_many(spec, topics=[f"t{j:04d}" for j in range(16)])
    for j, h in enumerate(handles):
        ts = zipf_timestamps(4096, 4e6, zipf_factor=0.7, seed=sub_seed(20230617, TIMES, j))
        h.push(_values(4096, j), timestamps=ts)
    return d._core, d.run


def _sparse_feeds(n_topics=6, seed=0, scale=0.4):
    rng = np.random.default_rng(seed)
    feeds = {}
    for j in range(n_topics):
        n = int(rng.integers(40, 400))
        gaps = rng.exponential(scale * (j + 1) / 40.0, n)
        feeds[f"s{j}"] = (_values(n, 100 + j), np.cumsum(gaps))
    return feeds


def case_sparse_timeouts():
    """Sparse arrivals: batches time out on their own next tuple and on
    other topics' run ends, mid-feed."""
    return _server(_sparse_feeds(), flush_tuples=64, gang=True, flush_timeout_s=0.05)


def case_small_quantum():
    """A small pinned quantum: gang edges fire mid-replay."""
    return _server(
        _sparse_feeds(seed=1, scale=0.05), flush_tuples=64, gang=True,
        flush_timeout_s=0.2, gang_quantum_s=0.013,
    )


def case_backpressure():
    """A gang budget of 2: waves fire inside `_enqueue_flush`."""
    return _server(
        _sparse_feeds(seed=2, scale=0.02), flush_tuples=64, gang=True,
        flush_timeout_s=0.2, gang_budget=2,
    )


def _tie_feeds(n_topics=5, seed=3):
    rng = np.random.default_rng(seed)
    return {
        f"i{j}": (_values(n, 200 + j), np.sort(rng.integers(0, 60, n)).astype(np.float64))
        for j, n in enumerate(rng.integers(30, 300, n_topics))
    }


def case_integer_ties():
    """Integer timestamps with an integer timeout and quantum: deadlines
    and edges fall exactly on other topics' arrivals and run ends."""
    return _server(
        _tie_feeds(), flush_tuples=64, egress=True, gang=True, flush_timeout_s=4.0,
        gang_quantum_s=2.0,
    )


def case_integer_ties_zero_timeout():
    """A zero timeout: every deadline IS an arrival time."""
    return _server(
        _tie_feeds(seed=4), flush_tuples=64, gang=True, flush_timeout_s=0.0,
        gang_quantum_s=3.0,
    )


def case_solo():
    """Solo mode: each flush compresses inline at its event."""
    return _server(_sparse_feeds(seed=5), flush_tuples=64, gang=False, flush_timeout_s=0.05)


def case_solo_ties():
    """Solo mode on integer timestamps: timer pops tie with arrivals."""
    return _server(
        _tie_feeds(seed=6), flush_tuples=64, egress=True, gang=False, flush_timeout_s=4.0
    )


def case_breaker():
    """An open breaker on a clock that ticks per read: failures park waves,
    probes land on the exact sequence of `allow()` calls."""
    inj = DeviceLossInjector(fail_at_waves={0: (7, 7, 7), 4: (7, 7, 7)})
    return _server(
        _sparse_feeds(seed=7, scale=0.05), flush_tuples=64, codecs=("tcomp32",),
        gang=True, mesh=1, gang_budget=2, flush_timeout_s=0.2, gang_quantum_s=0.02,
        fault_injector=inj, breaker={"cooldown_s": 6.0, "clock": _TickClock()},
    )


def case_adaptive():
    """Adaptive sessions switch tier at a flush boundary (gang waves
    regroup under the new signature), with ties on the timer."""
    ladder = resolve_ladder(cheap="leb128")
    spec = cstream.JobSpec(
        codec="leb128", egress=True, adaptive=True, gang=True,
        flush_tuples=128, flush_timeout_s=2.0,
    )
    d = cstream.Dispatcher(gang=True)
    rng = np.random.default_rng(8)
    for i in range(3):
        ctl = ScriptedController(ladder, ["cheap", "heavy", "heavy", "cheap", "cheap"])
        h = d.open(spec, topic=f"g{i}", controller=ctl)
        n = 128 * 4 + 40 * i
        h.push(_values(n, 300 + i), np.sort(rng.integers(0, 12, n)).astype(np.float64))
    return d._core, d.run


def case_dict_swap():
    """A staged dictionary swap lands at the first flush boundary of the
    replay; waves never mix dictionary versions."""
    reg = dictstore.DictRegistry()
    rng = np.random.default_rng(9)
    sample = rng.zipf(1.3, 4096).astype(np.uint32) % 512
    v1 = reg.publish(dictstore.train_dict(sample, idx_bits=10, topic="sensor"))
    v2 = reg.publish(dictstore.train_dict(sample[::-1] + 3, idx_bits=10, topic="sensor"))
    old = dictstore.set_default_registry(reg)
    try:
        spec = cstream.JobSpec(
            codec="tdic32", params={"idx_bits": 10}, egress=True, gang=True,
            flush_tuples=128, flush_timeout_s=0.05, dictionary="sensor:v1",
        )
        d = cstream.Dispatcher(gang=True)
        handles = [d.open(spec, topic=f"d{i}") for i in range(3)]
    finally:
        dictstore.set_default_registry(old)
    assert v1.version == 1
    for i, h in enumerate(handles):
        n = 128 * 3 + 17 * i
        h.push(sample[:n], np.cumsum(rng.exponential(0.004, n)))
        if i != 1:
            h.swap_dictionary(v2)

    def run():
        prev = dictstore.set_default_registry(reg)
        try:
            return d.run()
        finally:
            dictstore.set_default_registry(prev)

    return d._core, run


def case_carried():
    """Sessions that enter the replay with an open batch (one past its
    deadline before its feed starts, one with no feed at all) and a gang
    queue that holds a flush from before the replay."""
    feeds = _sparse_feeds(seed=11, scale=0.05)
    feeds["s1"] = (feeds["s1"][0], feeds["s1"][1] + 0.3)  # no flush in the first quantum
    feeds["s5"] = (np.zeros(0, np.uint32), np.zeros(0))
    srv, run = _server(feeds, flush_tuples=64, gang=True, flush_timeout_s=0.1)
    srv.sessions["s0"].offer_many(_values(70, 1), np.linspace(-0.05, -0.01, 70))
    srv.sessions["s1"].offer_many(_values(9, 2), np.linspace(-0.5, -0.4, 9))
    srv.sessions["s5"].offer_many(_values(5, 3), np.linspace(0.0, 0.01, 5))
    s2 = srv.sessions["s2"]
    s2.offer_many(_values(3, 4), np.linspace(-0.3, -0.2, 3))
    s2.flush(now=-0.1)
    assert srv._queues[s2.signature]
    return srv, run


def _timeouts(got):
    return any(t for ts in got["timeouts"].values() for t in ts)


def _mid_replay_waves(got):  # waves fired before the final drain
    return any(not force for _, _, force in got["waves"])


def _breaker_shed(got):
    snap = next(iter(got["breakers"].values()))
    return snap["trips"] >= 1 and snap["shed"] >= 1


#: case -> (build, what the case must reach for the comparison to compare it)
CASES = {
    "chip_arrivals": (case_chip_arrivals, lambda got: len(got["waves"]) == 2),
    "sparse_timeouts": (case_sparse_timeouts, _timeouts),
    "small_quantum": (case_small_quantum, _mid_replay_waves),
    "backpressure": (case_backpressure, lambda got: any(len(m) == 2 for _, m, _ in got["waves"])),
    "integer_ties": (case_integer_ties, _timeouts),
    "integer_ties_zero_timeout": (case_integer_ties_zero_timeout, _timeouts),
    "solo": (case_solo, _timeouts),
    "solo_ties": (case_solo_ties, _timeouts),
    "breaker": (case_breaker, _breaker_shed),
    "adaptive": (case_adaptive, lambda got: len({sig for sig, _, _ in got["waves"]}) == 2),
    "carried": (case_carried, _timeouts),
    "dict_swap": (case_dict_swap, lambda got: len({sig for sig, _, _ in got["waves"]}) == 2),
}


# --------------------------------------------------------------- recording --
def _serve(build, replay):
    """Build a case, serve it through `replay`, and record every flush call
    and wave in the order they happen, the records, dispatches, frames and
    replay counters."""
    log: list = []  # ("flush", topic, n, stamp, arrivals, values) / ("wave", sig, topics, force)
    flush, execute = StreamSession.flush, ServerCore._execute_wave

    def _flush(self, now):
        n = self._count
        log.append(("flush", self.topic, n, float(now), self._arrivals[:n].tobytes(),
                    self._values[:n].tobytes()))
        return flush(self, now)

    def _execute(self, sig, wave, force=False):
        log.append(("wave", sig, tuple(s.topic for s, _ in wave), force))
        return execute(self, sig, wave, force=force)

    with pytest.MonkeyPatch.context() as m:
        m.setattr(StreamSession, "flush", _flush)
        m.setattr(ServerCore, "_execute_wave", _execute)
        m.setattr(ServerCore, "_replay", replay)
        core, run = build()
        before = spans.snapshot()
        rep = run()
        counters = spans.since(before, spans.snapshot())["counters"]
    sessions = {t: core.sessions[t] for t in sorted(core.sessions)}
    return dict(
        log=log,
        calls=[e[1:] for e in log if e[0] == "flush"],
        waves=[e[1:] for e in log if e[0] == "wave"],
        records={t: [f.key() for f in s.flushes] for t, s in sessions.items()},
        timeouts={t: [f.timeout for f in s.flushes] for t, s in sessions.items()},
        n_dispatches=rep.n_dispatches,
        runs=counters.get("replay.runs", 0),
        tuples=counters.get("replay.tuples", 0),
        frames={t: [f.to_bytes() for f in s.egress_frames()] for t, s in sessions.items()},
        fault_events=rep.fault_events,
        breakers=rep.breakers,
    ), counters


def _compare(build, reaches):
    old, _ = _serve(build, _old_replay)
    new, counters = _serve(build, ServerCore._replay)
    assert old["calls"] and reaches(old), "the case does not reach its path"
    for key in ("log", "records", "timeouts", "n_dispatches", "runs",
                "tuples", "frames", "fault_events", "breakers"):
        assert new[key] == old[key], key
    # the walk stops only where a flush or an edge poll acts
    assert 0 < counters["replay.steps"] <= len(new["calls"]) + len(new["waves"])


@pytest.mark.parametrize("case", sorted(CASES))
def test_event_replay_matches_the_per_run_walk(case):
    _compare(*CASES[case])


def case_fleet2():
    """Sharded waves on a 2-device mesh, one device lost at wave 2: the
    wave replays on the survivor (needs 2 visible devices)."""
    return _server(
        _sparse_feeds(seed=10, scale=0.05), flush_tuples=64, codecs=("tcomp32", "rle"),
        egress=True, gang=True, mesh=2, flush_timeout_s=0.2, gang_quantum_s=0.02,
        fault_injector=DeviceLossInjector(fail_at_waves={2: 1}),
    )


_FLEET = textwrap.dedent(
    """
    import sys
    import jax
    assert jax.device_count() == 2, jax.device_count()
    sys.path.insert(0, {tests!r})
    import test_replay_events as T
    T._compare(T.case_fleet2, lambda got: [e["n_devices"] for e in got["fault_events"]] == [1])
    print("FLEET-REPLAY-OK")
    """
)


def test_event_replay_matches_the_per_run_walk_on_a_fleet_subprocess():
    """The fleet case on 2 virtual CPU devices (the device count is fixed at
    JAX start-up, hence the subprocess, as in tests/test_fleet.py)."""
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=2"
    ).strip()
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in [os.path.join(here, "..", "src"), env.get("PYTHONPATH", "")] if p
    )
    proc = subprocess.run(
        [sys.executable, "-c", _FLEET.format(tests=here)],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "FLEET-REPLAY-OK" in proc.stdout


# ---------------------------------------------------------- batch boundary --
@pytest.mark.parametrize(
    "tss,count,deadline,cut,want",
    [
        ([1.0, 2.0, 3.0, 4.0], 0, 5.0, None, (4, "size")),
        ([1.0, 2.0, 9.0], 0, 5.0, None, (2, "timeout")),
        ([1.0, 2.0], 0, 5.0, None, (2, None)),
        ([5.0, 5.0, 6.0], 1, 5.0, None, (2, "timeout")),  # ts == deadline joins
        ([6.0], 2, 5.0, None, (0, "timeout")),  # the timer fired before it
        ([1.0, 2.0, 3.0], 0, 5.0, 2, (2, "timeout")),  # a timer pop first
        ([1.0, 2.0, 3.0, 4.0], 0, 5.0, 4, (4, "size")),  # filled before the pop
        ([1.0, 2.0], 0, 5.0, 2, (2, "timeout")),  # popped after its last tuple
    ],
)
def test_batch_end(tss, count, deadline, cut, want):
    assert batch_end(np.array(tss), 0, count, deadline, 4, cut) == want


def test_offer_is_a_one_tuple_offer_many():
    """`offer` takes the same boundary: a tuple past the open batch's
    deadline flushes the batch by timeout before it is buffered."""
    s = StreamSession("t", _cfg(), flush_tuples=64, flush_timeout_s=1.0)
    assert s.offer(1, ts=0.0) is None and s.offer(2, ts=1.0) is None
    rec = s.offer(3, ts=1.5)
    assert rec is not None and rec.timeout and rec.n_tuples == 2
    assert rec.max_wait_s == pytest.approx(1.0)
    assert s.buffered == 1 and s.flush_deadline == pytest.approx(2.5)
