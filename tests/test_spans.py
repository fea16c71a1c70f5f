"""The program's recorder of spans and counters (`repro.runtime.spans`):
self time, spans that raise, per-thread stacks and `since()`; then the
spans and counters of the served path, through a tiny gang Dispatcher on
the CPU and into a profiler trace."""
import os
import threading
import types

import numpy as np
import pytest

from repro import cstream
from repro.core.pipeline import DecompressionPipeline
from repro.runtime import spans


@pytest.fixture
def clock(monkeypatch):
    """A host clock that moves only when the test says so."""
    now = [0.0]
    monkeypatch.setattr(spans, "time", types.SimpleNamespace(perf_counter=lambda: now[0]))
    return now


def test_self_time_leaves_out_nested_spans(clock):
    before = spans.snapshot()
    with spans.span("t.outer"):
        clock[0] += 1.0
        with spans.span("t.inner"):
            clock[0] += 2.0
            with spans.span("t.leaf"):
                clock[0] += 4.0
        with spans.span("t.inner"):
            clock[0] += 8.0
        clock[0] += 16.0
    got = spans.since(before)["spans"]
    assert got["t.outer"] == {"calls": 1, "total_s": 31.0, "self_s": 17.0}
    assert got["t.inner"] == {"calls": 2, "total_s": 14.0, "self_s": 10.0}
    assert got["t.leaf"] == {"calls": 1, "total_s": 4.0, "self_s": 4.0}


def test_a_span_closes_and_counts_when_its_body_raises(clock):
    before = spans.snapshot()
    with pytest.raises(KeyError):
        with spans.span("t.raises"):
            clock[0] += 3.0
            raise KeyError("x")
    # the stack was popped: the next span has no parent to charge
    with spans.span("t.after"):
        clock[0] += 1.0
    got = spans.since(before)["spans"]
    assert got["t.raises"] == {"calls": 1, "total_s": 3.0, "self_s": 3.0}
    assert got["t.after"] == {"calls": 1, "total_s": 1.0, "self_s": 1.0}


def test_spanned_makes_each_call_one_span(clock):
    @spans.spanned("t.fn")
    def fn(x, y=1):
        """doc"""
        clock[0] += x
        return x + y

    before = spans.snapshot()
    assert fn(2, y=3) == 5 and fn(1) == 2
    assert fn.__name__ == "fn" and fn.__doc__ == "doc"
    assert spans.since(before)["spans"]["t.fn"] == {"calls": 2, "total_s": 3.0, "self_s": 3.0}


def test_stacks_are_per_thread():
    before = spans.snapshot()
    inner_open, outer_done = threading.Event(), threading.Event()

    def other():
        with spans.span("t.thread_inner"):
            inner_open.set()
            outer_done.wait(5)

    th = threading.Thread(target=other)
    with spans.span("t.thread_outer"):
        th.start()
        inner_open.wait(5)
    outer_done.set()
    th.join(5)
    got = spans.since(before)["spans"]
    # the other thread's span, open all through, is no child of this one
    assert got["t.thread_outer"]["self_s"] == got["t.thread_outer"]["total_s"]
    assert got["t.thread_inner"]["self_s"] == got["t.thread_inner"]["total_s"]
    assert got["t.thread_inner"]["calls"] == 1


def test_no_update_is_lost_across_threads():
    import sys

    n_threads, n_iter = 4 * (os.cpu_count() or 1), 500
    before = spans.snapshot()

    def work():
        for _ in range(n_iter):
            with spans.span("t.stress"):
                spans.count("t.stress")

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(60)
    finally:
        sys.setswitchinterval(old)
    assert not any(th.is_alive() for th in threads)
    got = spans.since(before)
    assert got["spans"]["t.stress"]["calls"] == n_threads * n_iter
    assert got["counters"]["t.stress"] == n_threads * n_iter


def test_since_is_after_less_before():
    before = {
        "spans": {"a": {"calls": 2, "total_s": 1.5, "self_s": 1.0},
                  "b": {"calls": 1, "total_s": 1.0, "self_s": 1.0}},
        "counters": {"x": 5, "y": 1},
    }
    after = {
        "spans": {"a": {"calls": 5, "total_s": 4.0, "self_s": 2.5},
                  "b": {"calls": 1, "total_s": 1.0, "self_s": 1.0},
                  "c": {"calls": 1, "total_s": 0.25, "self_s": 0.25}},
        "counters": {"x": 12, "y": 1, "z": 3},
    }
    assert spans.since(before, after) == {
        "spans": {"a": {"calls": 3, "total_s": 2.5, "self_s": 1.5},
                  "c": {"calls": 1, "total_s": 0.25, "self_s": 0.25}},
        "counters": {"x": 7, "z": 3},
    }
    assert spans.since(after, after) == {"spans": {}, "counters": {}}


def test_count_and_snapshot_are_plain_data():
    before = spans.snapshot()
    spans.count("t.counted")
    spans.count("t.counted", 4)
    snap = spans.snapshot()
    assert spans.since(before, snap)["counters"] == {"t.counted": 5}
    snap["counters"]["t.counted"] = -1  # a copy: the recorder is untouched
    assert spans.snapshot()["counters"]["t.counted"] == before["counters"].get("t.counted", 0) + 5


#: the served path's spans, by the names the benchmark's readers use
SERVED_SPANS = {
    "dispatcher.run", "server.replay", "server.wave", "wave.step",
    "server.report", "egress.frame", "egress.rans",
    "frame.to_bytes", "collector.ingest", "collector.decode",
}
#: the span a report's egress field runs when it is first read
REPORT_FIDELITY = "report.fidelity"


def _serve(d, n=1500):
    """Open four rANS + CRC32C sessions and two plain ones on `d`, push
    bursty arrivals, run, and take every frame through to_bytes and ingest.
    Returns the feeds as `ServerCore.run` sees them."""
    rng = np.random.default_rng(5)
    specs = {
        "rans": cstream.JobSpec(codec="tcomp32", egress=True, gang=True, flush_tuples=512,
                                entropy="rans", integrity="crc32c"),
        "plain": cstream.JobSpec(codec="tcomp32", egress=True, gang=True, flush_tuples=512),
    }
    kinds = {f"r{i}": "rans" for i in range(4)} | {f"p{i}": "plain" for i in range(2)}
    handles, feeds = {}, {}
    for t, k in kinds.items():
        v = rng.integers(0, 1 << 12, n).astype(np.uint32)
        ts = np.cumsum(rng.exponential(1e-5, n) * (1 + 9 * (rng.random(n) < 0.1)))
        handles[t] = d.open(specs[k], topic=t)
        handles[t].push(v, timestamps=ts)
        feeds[t] = (v, ts)
    d.run()
    for t, k in kinds.items():
        plan = cstream.negotiate(specs[k])
        dec = DecompressionPipeline(plan.spec, codec=plan.codec, plan=plan.execution)
        got = np.concatenate([dec.ingest(f.to_bytes()).values for f in handles[t].frames()])
        np.testing.assert_array_equal(got, feeds[t][0])
    return feeds


def _equal_topic_runs(feeds) -> int:
    """Runs of equal topic in the merged arrival order (stable, topics sorted)."""
    topics = sorted(feeds)
    ts = np.concatenate([feeds[t][1] for t in topics])
    idx = np.concatenate([np.full(len(feeds[t][1]), i) for i, t in enumerate(topics)])
    merged = idx[np.argsort(ts, kind="stable")]
    return int(np.count_nonzero(np.diff(merged))) + 1


def test_the_served_path_records_its_spans_and_counters():
    """The counters pin the served path: `report()` neither marshals nor
    decodes, so each delivered frame is marshalled once and decoded once,
    at the collector. Reading the report's fidelity afterwards decodes each
    session's frame once more and marshals none: it reuses the frame the
    handle delivered."""
    d = cstream.Dispatcher(gang=True)
    before = spans.snapshot()
    feeds = _serve(d)
    got = spans.since(before)
    assert SERVED_SPANS <= set(got["spans"])
    assert REPORT_FIDELITY not in got["spans"]
    c = got["counters"]
    assert c["replay.tuples"] == sum(len(v) for v, _ in feeds.values())
    assert c["replay.runs"] == _equal_topic_runs(feeds)
    assert c["handle.frames_delivered"] == len(feeds)
    assert c["egress.frames_marshalled"] == c["handle.frames_delivered"]
    assert c["collector.frames_ingested"] == len(feeds)
    assert c["decode.frames"] == c["collector.frames_ingested"]
    assert "egress.frame_reuses" not in c
    s = got["spans"]
    # the replay's waves are children
    assert s["dispatcher.run"]["total_s"] >= s["server.replay"]["total_s"] + s["server.report"]["total_s"]
    assert s["server.replay"]["self_s"] < s["server.replay"]["total_s"]
    assert s["server.wave"]["total_s"] >= s["wave.step"]["total_s"] > 0
    # one to_bytes and one ingest per delivered frame; rANS on four of six
    assert s["frame.to_bytes"]["calls"] == s["collector.ingest"]["calls"] == len(feeds)
    assert s["egress.rans"]["calls"] == 4
    assert s["egress.frame"]["calls"] == len(feeds)
    # every flush dispatch, gang or solo, is one timed wave.step
    rep = d.report()
    assert s["wave.step"]["calls"] == sum(
        st.n_waves + st.n_solo for st in rep.dispatch_stats.values())

    before = spans.snapshot()
    assert all(rep.sessions[t].fidelity.bit_exact for t in feeds)
    got = spans.since(before)
    assert got["counters"] == {"egress.frame_reuses": len(feeds), "decode.frames": len(feeds)}
    assert set(got["spans"]) == {REPORT_FIDELITY, "collector.decode"}
    assert got["spans"][REPORT_FIDELITY]["calls"] == len(feeds)


def test_the_spans_land_in_a_profiler_trace(tmp_path):
    import jax
    from jax.profiler import ProfileData

    d = cstream.Dispatcher(gang=True)
    _serve(d, n=600)  # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        traced = cstream.Dispatcher(gang=True)
        _serve(traced, n=600)
        assert all(r.fidelity.bit_exact for r in traced.report().sessions.values())
    finally:
        jax.profiler.stop_trace()
    paths = [os.path.join(r, f) for r, _, fs in os.walk(tmp_path) for f in fs
             if f.endswith(".xplane.pb")]
    pd = ProfileData.from_file(paths[0])
    names = {e.name for p in pd.planes for ln in p.lines for e in ln.events
             if e.name.startswith("cstream.")}
    assert {"cstream." + n for n in SERVED_SPANS | {REPORT_FIDELITY}} <= names


def test_the_core_imports_the_recorder_without_the_server():
    """`repro.runtime` loads its names on first use: a fresh process that
    imports the core first (which records into `repro.runtime.spans`) gets
    no import cycle, and the runtime's names still resolve."""
    import subprocess
    import sys

    code = (
        "import sys\n"
        "import repro.core.bits\n"
        "assert 'repro.runtime.server' not in sys.modules\n"
        "from repro.runtime import ServerCore, StreamSession, batch_specs\n"
        "import repro.runtime\n"
        "try:\n"
        "    repro.runtime.no_such_name\n"
        "except AttributeError:\n"
        "    pass\n"
        "else:\n"
        "    raise SystemExit('no AttributeError')\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    src = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = os.pathsep.join(filter(None, [src, env.get("PYTHONPATH")]))
    p = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode == 0, p.stderr
