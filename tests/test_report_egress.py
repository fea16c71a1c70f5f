"""A session report's egress fields (`SessionReport.fidelity`, `wire_bytes`,
`decode_s`) are checked when first read, against the session as it stood
when the report was taken. The served path then marshals each frame once,
for `StreamHandle.frames()`, and decodes it once, at the collector.

The eager check that `StreamSession.report()` used to run for every session
is kept here as the oracle (`_eager_frame`, `_eager_check`)."""
import hashlib
from typing import List

import numpy as np
import pytest

from repro import cstream
from repro.core import bits, dictstore, metrics
from repro.core.controller import ScriptedController, resolve_ladder
from repro.core.pipeline import DecompressionPipeline
from repro.runtime import spans

IDX_BITS = 10
N = 1024  # tuples per topic per phase: two size flushes of 512


# ---------------------------------------------------------------- oracle --
def _eager_frame(sess) -> bits.Frame:
    """The open segment's frame, marshalled afresh from the session."""
    flush_entry = sess.pipeline.flush_block_entry(sess.state)
    flush_slots = 0 if flush_entry is None else sess.pipeline.flush_slots
    n_full = len(sess._egress_blocks)
    n_valid = sum(b[3] for b in sess._egress_blocks)
    per_lane = sess.capacity // sess.lanes
    if not sess._compact:
        blocks = list(sess._egress_blocks)
        if flush_entry is not None:
            blocks.append(flush_entry)
        return sess.pipeline.marshal_frame(
            blocks, per_lane=per_lane, n_full=n_full, tail_per_lane=0,
            flush_slots=flush_slots, n_valid=n_valid,
        )
    segments = [b[0] for b in sess._egress_blocks]
    block_bits = [b[1] for b in sess._egress_blocks]
    block_valid = [b[3] for b in sess._egress_blocks]
    flush_raw = np.zeros(0, np.int32)
    if flush_entry is not None:
        fw, fb, fbl, _ = flush_entry
        segments.append(np.asarray(fw[: (int(fb) + 31) // 32], np.uint32))
        block_bits.append(int(fb))
        block_valid.append(0)
        flush_raw = np.asarray(fbl, np.int32).ravel()
    payload = np.concatenate(segments) if segments else np.zeros(0, np.uint32)
    bitlen = packed_meta = None
    if sess._meta_packed:
        packed_meta = np.concatenate(
            [b[2] for b in sess._egress_blocks] + [bits._pack_bitlens(flush_raw)]
        ) if sess._egress_blocks or flush_raw.size else np.zeros(0, np.uint32)
    else:
        bitlen = np.concatenate(
            [b[2] for b in sess._egress_blocks] + [flush_raw]
        ) if sess._egress_blocks or flush_raw.size else np.zeros(0, np.int32)
    return sess.pipeline.marshal_compacted(
        per_lane=per_lane, n_full=n_full, tail_per_lane=0,
        flush_slots=flush_slots, n_valid=n_valid,
        block_bits=np.asarray(block_bits, np.int64),
        block_valid=np.asarray(block_valid, np.int64),
        payload=payload, bitlen=bitlen, packed_meta=packed_meta,
    )


def _eager_check(sess):
    """(Fidelity, wire bytes, decoded values, open frame's bytes): every
    sealed segment decoded with its own decompressor, then the open one."""
    decoded: List[np.ndarray] = []
    feds: List[np.ndarray] = []
    wire = 0
    for frame, fed, tier in sess._sealed:
        if tier is not None and tier in sess._tiers:
            tier_cfg, tier_codec, _ = sess._tiers[tier]
            decomp = DecompressionPipeline(tier_cfg, codec=tier_codec)
        else:
            codec = sess._dict_codecs.get(frame.dict_id, sess.pipeline.codec)
            decomp = DecompressionPipeline(sess.config, codec=codec)
        decoded.append(decomp.decompress(frame).values)
        feds.append(fed)
        wire += frame.wire_bytes
    open_bytes = None
    if sess._egress_blocks:
        frame = _eager_frame(sess)
        open_bytes = frame.to_bytes()
        if sess.controller is not None:
            decomp = DecompressionPipeline(sess.config, codec=sess.pipeline.codec)
        else:
            decomp = sess.pipeline.decompressor()
        decoded.append(decomp.decompress(frame).values)
        feds.append(np.concatenate(sess._egress_values))
        wire += frame.wire_bytes
    fed_all = np.concatenate(feds) if feds else np.zeros(0, np.uint32)
    dec_all = np.concatenate(decoded) if decoded else np.zeros(0, np.uint32)
    fid = metrics.fidelity(fed_all, dec_all, bound=sess.pipeline.codec.error_bound())
    return fid, wire, dec_all, open_bytes


# ----------------------------------------------------------------- fleet --
@pytest.fixture
def registry():
    """Fresh in-memory dictionary registry installed as the process default."""
    reg = dictstore.DictRegistry()
    prev = dictstore.set_default_registry(reg)
    yield reg
    dictstore.set_default_registry(prev)


def _publish(reg, seed):
    rng = np.random.default_rng(seed)
    sample = ((rng.zipf(1.3, size=4096) - 1) % 300).astype(np.uint32) * np.uint32(7)
    return reg.publish(dictstore.train_dict(sample, idx_bits=IDX_BITS, topic="sensor"))


def _specs():
    base = dict(egress=True, gang=True, flush_tuples=512)
    tdic = dict(base, codec="tdic32", params={"idx_bits": IDX_BITS})
    return {
        "lossless": cstream.JobSpec(codec="tcomp32", **base),
        "rans_crc": cstream.JobSpec(codec="tcomp32", entropy="rans", integrity="crc32c", **base),
        "stateful": cstream.JobSpec(codec="rle", **base),
        "pla": cstream.JobSpec(codec="pla", **base),
        "pla_rans": cstream.JobSpec(codec="pla", entropy="rans", **base),
        "dict": cstream.JobSpec(dictionary="sensor:v1", **tdic),
        "hotswap": cstream.JobSpec(dictionary="sensor:latest", integrity="crc32c", **tdic),
        "adaptive": cstream.JobSpec(codec="leb128", adaptive=True, **base),
        "plain": cstream.JobSpec(codec="tcomp32", gang=True, flush_tuples=512),
    }


def _values(topic: str, phase: int) -> np.ndarray:
    rng = np.random.default_rng([sum(map(ord, topic)), phase])
    if topic in ("dict", "hotswap"):
        return ((rng.zipf(1.3, size=N) - 1) % 300).astype(np.uint32) * np.uint32(7)
    walk = np.cumsum(rng.integers(-8, 9, size=N)) + 4096
    return np.clip(walk, 0, 65535).astype(np.uint32)


def _push(handles, phase: int) -> None:
    ts = phase * 1.0 + np.arange(N) * 1e-5  # size flushes only
    for t, h in handles.items():
        h.push(_values(t, phase), timestamps=ts)


def _open(d):
    handles = {}
    for t, spec in _specs().items():
        ctl = None
        if spec.adaptive:  # two flushes on the cheap rung, then heavy: one tier seal
            ctl = ScriptedController(resolve_ladder(cheap="leb128"), ["cheap", "cheap", "heavy"])
        handles[t] = d.open(spec, topic=t, controller=ctl)
    return handles


def _two_phases(registry):
    """Phase 0, a report and the oracle's reading of it; then a new
    dictionary version and phase 1, which seals the adaptive session's
    cheap segment and the hot-swapped session's v1 segment."""
    _publish(registry, seed=0)
    d = cstream.Dispatcher(gang=True)
    handles = _open(d)
    _push(handles, 0)
    first = d.run()
    first_oracle = {t: _eager_check(s) for t, s in d.sessions.items() if s.egress}
    _publish(registry, seed=1)  # the "latest" session swaps at its next flush
    _push(handles, 1)
    d.run()
    return d, handles, first, first_oracle


def _assert_matches(rep, oracle):
    fid, wire, dec_all, _ = oracle
    assert rep.fidelity == fid
    assert rep.wire_bytes == wire
    assert rep.decode_s > 0
    assert rep.fidelity.n_tuples == dec_all.size == rep.n_tuples


# ----------------------------------------------------------------- tests --
def test_run_marshals_and_decodes_nothing_until_a_field_is_read(registry):
    _publish(registry, seed=0)
    d = cstream.Dispatcher(gang=True)
    handles = _open(d)
    _push(handles, 0)
    before = spans.snapshot()
    rep = d.run()
    d.report()
    got = spans.since(before)
    for name in ("egress.frames_marshalled", "decode.frames", "egress.frame_reuses"):
        assert name not in got["counters"]
    assert "report.fidelity" not in got["spans"]

    before = spans.snapshot()
    assert rep.sessions["rans_crc"].fidelity.bit_exact
    got = spans.since(before)
    assert got["counters"] == {"egress.frames_marshalled": 1, "decode.frames": 1}
    assert got["spans"]["report.fidelity"]["calls"] == 1
    assert got["spans"]["egress.rans"]["calls"] == 1

    # the delivered frame is the one the check marshalled
    before = spans.snapshot()
    (frame,) = handles["rans_crc"].frames()
    assert spans.since(before)["counters"] == {
        "egress.frame_reuses": 1, "handle.frames_delivered": 1}
    # a report of the same session state shares the check: nothing decodes
    before = spans.snapshot()
    again = d.report().sessions["rans_crc"]
    assert again == rep.sessions["rans_crc"]
    assert spans.since(before)["counters"] == {}


def test_frames_first_then_the_check_marshals_once(registry):
    _publish(registry, seed=0)
    d = cstream.Dispatcher(gang=True)
    handles = _open(d)
    _push(handles, 0)
    rep = d.run()
    before = spans.snapshot()
    frames = {t: h.frames() for t, h in handles.items()}
    got = spans.since(before)["counters"]
    assert got["egress.frames_marshalled"] == len(handles) - 1  # "plain" has no egress
    assert frames["plain"] == [] and rep.sessions["plain"].fidelity is None
    before = spans.snapshot()
    fids = {t: r.fidelity for t, r in rep.sessions.items() if t != "plain"}
    got = spans.since(before)["counters"]
    assert "egress.frames_marshalled" not in got
    assert got["egress.frame_reuses"] == got["decode.frames"] == len(fids)
    assert all(f.within_bound for f in fids.values())


def test_reads_equal_the_eager_check_at_report_time(registry):
    d, handles, first, first_oracle = _two_phases(registry)
    second = d.report()
    second_oracle = {t: _eager_check(s) for t, s in d.sessions.items() if s.egress}
    # read only now: the first report still reads the session before phase 1
    for t, oracle in first_oracle.items():
        _assert_matches(first.sessions[t], oracle)
        _assert_matches(second.sessions[t], second_oracle[t])
        assert first.sessions[t].n_tuples == N
        assert second.sessions[t].n_tuples == 2 * N
        assert first.sessions[t].fidelity.within_bound
        assert second.sessions[t].fidelity.within_bound
    for t in ("lossless", "rans_crc", "stateful", "dict", "hotswap", "adaptive"):
        assert second.sessions[t].fidelity.bit_exact, t
    assert second.sessions["adaptive"].tier_switches == 1
    assert second.sessions["hotswap"].dict_swaps == 1
    for t in ("adaptive", "hotswap"):
        s = d.sessions[t]
        assert s.n_segments == 2
        # the delivered frames: the sealed segment, then the open one, which
        # is byte-identical to the eager marshal
        frames = handles[t].frames()
        assert frames[0] is s._sealed[0][0]
        assert frames[1].to_bytes() == second_oracle[t][3]
    assert [f.dict_id for f in handles["hotswap"].frames()] == [("sensor", 1), ("sensor", 2)]
    for r in (first, second):
        plain = r.sessions["plain"]
        assert (plain.fidelity, plain.wire_bytes, plain.decode_s) == (None, None, None)


def test_frames_are_byte_identical_to_the_eager_marshal(registry):
    d, handles, _, _ = _two_phases(registry)
    for t, h in handles.items():
        s = d.sessions[t]
        if not s.egress:
            continue
        frames = h.frames()
        want = [f.to_bytes() for f, _, _ in s._sealed] + [_eager_frame(s).to_bytes()]
        assert [f.to_bytes() for f in frames] == want, t


#: sha256 over every frame's bytes of `_two_phases`, as the serving path
#: delivered them while its report still checked every session eagerly:
#: checking on read moves no wire byte
FRAMES_SHA256 = "4621af3ef3f56d83b14cfef3c011fcdc125a5e5f8e9f2aa0473f681aa3281ddc"


def _frames_digest(d, handles) -> str:
    h = hashlib.sha256()
    for t in sorted(handles):
        for f in handles[t].frames():
            h.update(t.encode() + f.to_bytes())
    return h.hexdigest()


def test_frames_keep_their_golden_bytes(registry):
    d, handles, _, _ = _two_phases(registry)
    assert _frames_digest(d, handles) == FRAMES_SHA256
