"""Executor layer — blocked executors for BOTH directions (DESIGN.md §2, §10).

`BlockedExecutor` owns what compression and decompression share: the codec,
the resolved execution plan, block shaping, and the chunked-`lax.scan`
machinery (one dispatch per `plan.scan_chunk` blocks, codec state carried
across chunks). On top of it:

  * `CompressionPipeline` — encode + bit-pack. Execution paths:
      - **fused** (default for lazy execution): chunks of blocks run as ONE
        `lax.scan` dispatch — the per-block Python dispatch loop the paper's
        Fig 10b charges as "blocked time" disappears from the hot path.
      - **dispatch** (the `eager` strategy / benchmark baseline): one jitted
        step per block.
    Stream finalization calls `Codec.flush` and packs the trailing state
    symbols (e.g. RLE's open run) as a flush mini-block, and
    `collect_payload=True` keeps each block's packed words + per-symbol
    bitlens so `frame_from` can assemble the wire-format `bits.Frame`.
  * `DecompressionPipeline` — the egress path: splits a frame back into
    blocks and replays codec state through the SAME fused chunked scan,
    unpacking symbols with `bits.unpack_symbols` (exclusive-cumsum offsets +
    vectorized gather/shift) and decoding in the scan body. Stream-scope
    codecs (RLE) unpack through the scan, then decode the whole symbol
    stream in one vectorized expansion — EDPC's decoupled decode dataflow.

Streams whose length is not a multiple of the block size do not raise: the
tail is edge-padded up to one (possibly smaller) aligned block. Pad symbols
are dropped from the bitstream only for `meta.maskable` codecs — codecs
whose decoder replays state from the symbols themselves (ADPCM, Delta,
Tdic32, RLE) must ship their pad symbols or encoder and decoder state fork;
the frame's per-block valid counts trim the pads after decode either way.

The shared-dictionary last-writer-wins merge lives here as `lww_select` /
`merge_shared_dictionary` and is reused by the local engine, the
`sharded_compress_fn` collective path (engine.py), and the decode-side
state replay — one semantics, three call sites.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bits
from repro.core.algorithms import (
    Codec,
    Encoded,
    WIRE_CODEC_IDS,
    WIRE_CODEC_NAMES,
    make_codec,
)
from repro.core.calibration import calibrated_kwargs
from repro.core.strategies import (
    EngineConfig,  # noqa: F401  (re-exported for legacy callers)
    ExecutionPlan,
    ExecutionStrategy,
    SpecLike,
    StateStrategy,
    plan_execution,
)
from repro.runtime import spans


#: scan length used when force-fusing a stream whose plan is per-block
#: dispatch (the eager Fig 10b breakdown replay): long enough to amortize
#: dispatch, short enough to keep trace size bounded
_FORCED_FUSE_CHUNK = 128


def codec_align(codec: Codec) -> int:
    """Per-lane tuple alignment a codec requires (policy input).

    PLA fits superwindows of 2W tuples; every other codec packs any shape.
    Shared by the executors here and the job-API negotiation layer."""
    return 2 * codec.window if codec.name == "pla" else 1


def dispatch_signature(
    codec: Codec,
    lanes: int,
    per_lane: int,
    dtype: str = "uint32",
    entropy: str = "none",
    integrity: str = "none",
) -> Tuple[Any, ...]:
    """Gang dispatch signature: streams/sessions stack into one vmapped
    dispatch only when codec (including resolved/calibrated parameters),
    block geometry, dtype, entropy stage, and integrity mode all match —
    anything else would run a member under the wrong kernel, the wrong
    quantizer, or marshal its frames under the wrong wire feature set.
    Used by the serving runtime's gang queues and the job API's gang
    negotiation."""
    parts: List[Any] = [codec.name, lanes, per_lane, dtype, entropy, integrity]
    for k, v in sorted(vars(codec).items()):
        if isinstance(v, (bool, int, float, str)):
            parts.append((k, v))
        elif isinstance(v, (np.ndarray, jax.Array)):
            # array-valued codec params hash by dtype/shape/bytes
            a = np.asarray(v)
            parts.append((k, (str(a.dtype), a.shape, a.tobytes())))
        else:
            # refuse rather than hash object identity: a repr/pointer key
            # would make identical sessions silently never gang
            raise TypeError(
                f"codec param {k!r} of {codec.name!r} has unhashable type "
                f"{type(v).__name__} for gang signatures"
            )
    return tuple(parts)


# ------------------------------------------------------- shared-state merge --
def lww_select(
    tables: jax.Array, valids: jax.Array, tss: jax.Array
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Last-writer-wins slot selection over group axis 0.

    Given per-group dictionary views `(G, TS)`, returns the merged
    `(table, valid, ts)` row `(TS,)` where each slot takes the entry with the
    newest write timestamp (invalid slots never win). This one function is
    the whole merge semantics: the local engine applies it across lanes, the
    sharded engine applies it again across devices on all-gathered rows —
    associativity of max makes the hierarchical merge equal the flat one."""
    key = jnp.where(valids, tss, -1)
    best = jnp.argmax(key, axis=0)
    slot = jnp.arange(key.shape[-1])
    return tables[best, slot], jnp.any(valids, axis=0), key[best, slot]


def merge_shared_dictionary(state: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """Deterministic cross-lane dictionary merge (shared-state strategy).

    All lanes converge to the same table after every micro-batch with true
    last-writer-wins semantics (per-slot write timestamps) — the batched
    equivalent of the paper's lock-guarded shared table. Decoder-replayable;
    the paper's lock contention becomes this all-lane reduction (and an
    all-gather across devices in the sharded engine)."""
    lanes, ts_size = state["table"].shape
    table, valid, ts = lww_select(state["table"], state["valid"], state["ts"])
    clock = jnp.broadcast_to(jnp.max(state["clock"]), (lanes,))
    return {
        "table": jnp.broadcast_to(table, (lanes, ts_size)),
        "valid": jnp.broadcast_to(valid, (lanes, ts_size)),
        "ts": jnp.broadcast_to(ts, (lanes, ts_size)),
        "clock": clock,
    }


# ------------------------------------------------------------ shaped stream --
@dataclasses.dataclass
class ShapedStream:
    """Block view of a value stream: full blocks + optional masked tail."""

    blocks: np.ndarray  # uint32[n_full, lanes, B]
    tail: Optional[np.ndarray]  # uint32[lanes, B_tail] or None
    tail_mask: Optional[np.ndarray]  # bool[lanes, B_tail], True = real tuple
    n_valid: int  # real (unpadded) tuples across blocks + tail

    @property
    def n_blocks(self) -> int:
        return len(self.blocks) + (1 if self.tail is not None else 0)


@dataclasses.dataclass
class BlockPayload:
    """One block's wire contribution: packed words + per-symbol bitlens."""

    words: np.ndarray  # uint32[<=out_words] (worst-case buffer; prefix used)
    nbits: int
    bitlen: np.ndarray  # int32[lanes * B]
    valid: int  # real tuples in this block (0 for the flush mini-block)


@dataclasses.dataclass
class CompactedPayload:
    """One execution's egress, fetched wire-shaped (DESIGN.md §13).

    The device compacts every block's live word prefix to its exclusive-
    prefix-sum offset and packs the per-symbol bitlens at 7 bits/symbol, so
    what crosses device->host is (within per-block word alignment and the
    raw tail/flush metadata) exactly what `Frame.to_bytes` will emit —
    `Frame.from_compacted` then does header math only."""

    block_bits: np.ndarray  # int64[n_blocks (+tail +flush)]
    block_valid: np.ndarray  # int64[n_blocks], real tuples per block
    sym_counts: np.ndarray  # int64[n_blocks], symbol slots per block
    payload: np.ndarray  # uint32 — exact wire payload, stream order
    bitlen: np.ndarray  # int32[n_symbols] (decode-ready, unpacked)
    packed_meta: Optional[np.ndarray]  # uint32 — wire 7-bit metadata stream
    d2h_bytes: int  # payload+metadata bytes actually transferred

    def block_payloads(self) -> List[BlockPayload]:
        """Per-block view (numpy slices, no copies) for legacy consumers."""
        used = (self.block_bits + 31) // 32
        w_off = np.concatenate([[0], np.cumsum(used)]).astype(np.int64)
        s_off = np.concatenate([[0], np.cumsum(self.sym_counts)]).astype(np.int64)
        return [
            BlockPayload(
                self.payload[w_off[b] : w_off[b + 1]],
                int(self.block_bits[b]),
                self.bitlen[s_off[b] : s_off[b + 1]],
                int(self.block_valid[b]),
            )
            for b in range(self.block_bits.size)
        ]


@dataclasses.dataclass
class ExecutionResult:
    """What one execution pass produced: bits per block + measured wall."""

    per_block_bits: np.ndarray  # float[n_blocks (+1 flush)] (pad masked)
    wall_s: float
    n_tuples: int  # real tuples compressed
    state: Any  # final codec state (for session reuse)
    compacted: Optional[CompactedPayload] = None  # compacted egress (default)
    legacy_payload: Optional[List[BlockPayload]] = None  # compact=False path
    flush_slots: int = 0  # per-lane slots of the flush mini-block

    @property
    def payload(self) -> Optional[List[BlockPayload]]:
        """Per-block wire contributions (either egress path), or None when
        the run did not collect a payload."""
        if self.legacy_payload is not None:
            return self.legacy_payload
        if self.compacted is not None:
            return self.compacted.block_payloads()
        return None


@dataclasses.dataclass
class DecompressionResult:
    """One frame's reconstruction + measured decode wall time."""

    values: np.ndarray  # uint32[n_valid]
    wall_s: float
    n_tuples: int


# ------------------------------------------------------------- egress sink --
class _EgressSink:
    """Assembles a `CompactedPayload` from double-buffered async D2H fetches.

    `put_*` enqueues one unit's DEVICE handles and fetches the PREVIOUS
    unit: by the time unit k's scalars force a sync, unit k+1's dispatch is
    already in flight, so the device computes ahead of the host copies —
    the async egress overlap that replaces the old per-execution
    worst-case-buffer copy pass (DESIGN.md §13). Small arrays additionally
    start `copy_to_host_async` at enqueue time.

    Stream-order contract: 7-bit-packed metadata units (full blocks) must
    all arrive before raw-bitlen units (tail/flush), and every packed unit
    must cover a multiple of 32 symbols, so the packed segments splice into
    the frame's global metadata stream without re-alignment.
    """

    def __init__(self, pipe: "CompressionPipeline"):
        self.pipe = pipe
        self._pending = None
        self.block_bits: List[int] = []
        self.block_valid: List[int] = []
        self.sym_counts: List[int] = []
        self.segments: List[np.ndarray] = []
        self.metas: List[np.ndarray] = []
        self.meta_symbols = 0
        self.raw_bitlens: List[np.ndarray] = []
        self.d2h_bytes = 0

    # ------------------------------------------------- low-level (host) adds
    def add_unit(
        self,
        seg: np.ndarray,
        bits_list,
        valids,
        syms: int,
        meta: Optional[np.ndarray] = None,
        raw: Optional[np.ndarray] = None,
        extra_bytes: int = 0,
    ) -> None:
        """Record one fetched unit (`seg` exact payload words for `len(bits_list)`
        blocks of `syms` symbols each, plus its packed or raw metadata)."""
        self.segments.append(seg)
        self.block_bits.extend(int(b) for b in bits_list)
        self.block_valid.extend(int(v) for v in valids)
        n = len(self.block_bits) - len(self.block_valid)
        assert n == 0, "bits/valid counts diverged"
        self.sym_counts.extend([syms] * len(bits_list))
        meta_bytes = 0
        if meta is not None:
            assert not self.raw_bitlens, "packed metadata after raw metadata"
            self.metas.append(meta.reshape(-1))
            self.meta_symbols += syms * len(bits_list)
            meta_bytes = meta.nbytes
        if raw is not None:
            r = np.asarray(raw, np.int32).reshape(-1)
            self.raw_bitlens.append(r)
            meta_bytes = r.nbytes
        self.d2h_bytes += seg.nbytes + meta_bytes + extra_bytes
        self.pipe.d2h_payload_bytes += seg.nbytes
        self.pipe.d2h_meta_bytes += meta_bytes
        self.pipe.d2h_ctrl_bytes += extra_bytes

    # -------------------------------------------- double-buffered device puts
    @staticmethod
    def _start_host_copy(arrs) -> None:
        for a in arrs:
            a.copy_to_host_async()

    def put_chunk(self, tb, payload, total, meta, packed: bool, syms: int, valid: int):
        """One fused-scan chunk: tb int32[C], payload uint32[C*OW] (compacted,
        `total` words live), meta uint32[C, MW] packed or int32[C, syms] raw."""
        self._start_host_copy((tb, total, meta))
        self._flip(("chunk", tb, payload, total, meta, packed, syms, valid))

    def put_block(self, tb, words, blen, packed: bool, syms: int, valid: int):
        """One single-block unit (eager block / tail / flush): tb scalar,
        words uint32[OW] worst-case (host slices the live prefix), blen
        packed uint32[MW] or raw int32[...]."""
        self._start_host_copy((tb, blen))
        self._flip(("block", tb, words, blen, packed, syms, valid))

    def _flip(self, item) -> None:
        prev, self._pending = self._pending, item
        if prev is not None:
            self._fetch(prev)

    def flush_pending(self) -> None:
        if self._pending is not None:
            self._fetch(self._pending)
            self._pending = None

    def _fetch(self, item) -> None:
        if item[0] == "chunk":
            _, tb, payload, total, meta, packed, syms, valid = item
            tw = int(jax.device_get(total))  # syncs THIS unit only
            seg = np.asarray(payload[:tw])  # device slice: live words travel
            tb_np = np.asarray(tb, np.int64)
            meta_np = np.asarray(meta)
            self.add_unit(
                seg,
                tb_np,
                [valid] * tb_np.size,
                syms,
                meta=meta_np if packed else None,
                raw=None if packed else meta_np,
                extra_bytes=4 * tb_np.size + 4,
            )
        else:
            _, tb, words, blen, packed, syms, valid = item
            tbi = int(jax.device_get(tb))
            seg = np.asarray(words[: (tbi + 31) // 32])
            blen_np = np.asarray(blen)
            self.add_unit(
                seg,
                [tbi],
                [valid],
                syms,
                meta=blen_np if packed else None,
                raw=None if packed else blen_np,
                extra_bytes=4,
            )

    # ---------------------------------------------------------------- finish
    def finish(self) -> CompactedPayload:
        self.flush_pending()
        payload = (
            np.concatenate(self.segments) if self.segments else np.zeros(0, np.uint32)
        )
        raw = (
            np.concatenate(self.raw_bitlens)
            if self.raw_bitlens
            else np.zeros(0, np.int32)
        )
        if self.metas:
            meta_cat = np.concatenate(self.metas)
            # packed units cover whole 32-symbol multiples, so the host-
            # packed raw tail splices in word-aligned
            assert self.meta_symbols % 32 == 0
            packed_meta = np.concatenate([meta_cat, bits._pack_bitlens(raw)])
            bitlen = np.concatenate(
                [bits._unpack_bitlens(meta_cat, self.meta_symbols), raw]
            )
        else:
            packed_meta = None
            bitlen = raw
        return CompactedPayload(
            block_bits=np.asarray(self.block_bits, np.int64),
            block_valid=np.asarray(self.block_valid, np.int64),
            sym_counts=np.asarray(self.sym_counts, np.int64),
            payload=payload,
            bitlen=bitlen,
            packed_meta=packed_meta,
            d2h_bytes=self.d2h_bytes,
        )


# --------------------------------------------------------- blocked executor --
class BlockedExecutor:
    """Codec + plan + block shaping + chunked-scan machinery (both ways).

    Subclasses provide `_scan_body(state, xs) -> (state, ys)`; the base
    caches one jitted `lax.scan` per chunk length so repeated executions
    (sessions, best-of-N benchmarks) never re-trace."""

    def __init__(
        self,
        config: SpecLike,
        sample: Optional[np.ndarray] = None,
        codec: Optional[Codec] = None,
        plan: Optional[ExecutionPlan] = None,
    ):
        """`config` is any spec carrier with the EngineConfig attribute
        surface — the legacy `EngineConfig` or a `repro.cstream.JobSpec`.
        A pre-negotiated `plan`/`codec` (from `cstream.negotiate`) is
        consumed as-is; otherwise both are derived here exactly as the
        negotiation layer would."""
        self.config = config
        if codec is None:
            kwargs = dict(config.codec_kwargs)
            if config.calibrate and sample is not None:
                auto = calibrated_kwargs(config.codec, sample)
                for k, v in auto.items():
                    kwargs.setdefault(k, v)
            codec = make_codec(config.codec, **kwargs)
        self.codec: Codec = codec
        align = codec_align(self.codec)
        self.plan: ExecutionPlan = (
            plan if plan is not None else plan_execution(config, codec_align=align)
        )
        self._align = align
        #: stage-2 entropy coder applied at frame marshal ("none" | "rans");
        #: legacy EngineConfig carriers predate the field, hence getattr
        self.entropy: str = getattr(config, "entropy", None) or "none"
        #: wire integrity stamped at frame marshal ("none" | "crc32c");
        #: same getattr dance for legacy EngineConfig carriers
        self.integrity: str = getattr(config, "integrity", None) or "none"
        self._scan_fns: Dict[int, Any] = {}  # chunk length -> jitted scan
        self._warmed: set = set()  # (shapes, chunk, ...) already compiled
        #: kernel dispatches issued on timed paths (scan chunks, per-block
        #: steps, gang steps). The gang-vs-per-session bench compares this.
        self.dispatches: int = 0

    # ------------------------------------------------------------- plumbing
    def init_state(self, lanes: Optional[int] = None) -> Any:
        return self.codec.init_state(self.config.lanes if lanes is None else lanes)

    @property
    def block_tuples(self) -> int:
        return self.plan.block_tuples

    @property
    def align(self) -> int:
        """Per-lane tuple alignment the codec requires (PLA superwindows)."""
        return self._align

    def _merge_if_shared(self, state: Any) -> Any:
        if (
            self.config.state == StateStrategy.SHARED
            and self.codec.meta.state_kind == "dictionary"
        ):
            return merge_shared_dictionary(state)
        return state

    # --------------------------------------------------------------- shaping
    def shape_blocks(self, values: np.ndarray, max_blocks: Optional[int] = None) -> ShapedStream:
        """Cut a flat uint32 stream into (lanes, B) blocks.

        The tail that does not fill a whole block becomes a smaller aligned
        block, edge-padded (repeat of the last value) with a mask marking the
        real tuples — pad symbols are dropped from the bitstream for
        maskable codecs and trimmed by the frame's valid counts otherwise,
        so the accounting stays exact for short and bursty streams."""
        values = np.ascontiguousarray(values, np.uint32).ravel()
        bt = self.block_tuples
        lanes = self.config.lanes
        n_full = len(values) // bt
        if max_blocks is not None and n_full >= max_blocks:
            n_full = max_blocks
            values = values[: n_full * bt]
        blocks = values[: n_full * bt].reshape(n_full, lanes, bt // lanes)
        rem = len(values) - n_full * bt
        if rem == 0:
            # n_full == 0 is the legitimate empty stream: zero blocks, zero
            # valid tuples — execute() emits only the flush mini-block (if
            # the codec has one) and the frame decodes back to an empty
            # array, so 0-length sessions honor the fidelity contract too
            return ShapedStream(blocks, None, None, n_full * bt)
        # tail: smallest aligned (lanes, B_tail) block covering the remainder
        unit = lanes * self._align
        padded = ((rem + unit - 1) // unit) * unit
        tail_vals = np.full(padded, values[-1], np.uint32)
        tail_vals[:rem] = values[n_full * bt :]
        mask = np.zeros(padded, bool)
        mask[:rem] = True
        tail = tail_vals.reshape(lanes, padded // lanes)
        tail_mask = mask.reshape(lanes, padded // lanes)
        return ShapedStream(blocks, tail, tail_mask, n_full * bt + rem)

    # ------------------------------------------------------- scan machinery
    def _scan_body(self, state: Any, xs: Any):
        raise NotImplementedError

    def _scan_fn(self, chunk_len: int, key: str = "", body: Any = None):
        """Jitted scan over `chunk_len` blocks: ONE dispatch, state carried.

        Outputs are scanned out (not dropped) so XLA cannot dead-code-
        eliminate the work — fused and dispatch paths do the same compute,
        the fused path just dispatches it once. `key`/`body` let a subclass
        cache variants with different scan outputs (e.g. with/without the
        per-symbol bitlens only framing needs)."""
        cache_key = (chunk_len, key)
        fn = self._scan_fns.get(cache_key)
        if fn is None:
            scan_body = body if body is not None else self._scan_body

            def scan_chunk(state, xs):
                return jax.lax.scan(scan_body, state, xs)

            fn = jax.jit(scan_chunk)
            self._scan_fns[cache_key] = fn
        return fn

    def _chunks(self, n_blocks: int, chunk: Optional[int] = None):
        c = chunk or max(self.plan.scan_chunk, 1)
        return [(i, min(c, n_blocks - i)) for i in range(0, n_blocks, c)]


# ------------------------------------------------------ compression pipeline --
class CompressionPipeline(BlockedExecutor):
    """Ingress executor: encode + bit-pack + fused/dispatch execution paths."""

    def __init__(
        self,
        config: SpecLike,
        sample: Optional[np.ndarray] = None,
        codec: Optional[Codec] = None,
        plan: Optional[ExecutionPlan] = None,
    ):
        super().__init__(config, sample=sample, codec=codec, plan=plan)
        self._step = jax.jit(self.step)
        self._masked_step = jax.jit(self.masked_step)
        self._masked_meta7 = jax.jit(self.masked_step_meta7)
        self._flush_fn = None
        # probe once: does this codec emit trailing state symbols?
        probe = self.codec.flush(self.init_state())
        self._has_flush = probe is not None
        self._flush_slots = 0 if probe is None else int(probe.bitlen.shape[1])
        #: full blocks' symbol count divides the word size, so per-block
        #: 7-bit metadata packs on device and splices into the frame's
        #: global stream without re-alignment (DESIGN.md §13); odd
        #: geometries fall back to raw int32 bitlen transfer
        self._meta7_ok = self.plan.block_tuples % 32 == 0
        #: device->host egress traffic, by section (benchmarks and the
        #: byte-accounting tests read these; `reset_d2h` zeroes them)
        self.d2h_payload_bytes = 0
        self.d2h_meta_bytes = 0
        self.d2h_ctrl_bytes = 0
        self._decompressor: Optional["DecompressionPipeline"] = None

    def decompressor(self) -> "DecompressionPipeline":
        """Decode executor for this pipeline's frames, built once: every
        session sharing this pipeline (one gang signature) shares its
        compiled decode programs too."""
        if self._decompressor is None:
            self._decompressor = DecompressionPipeline(self.config, codec=self.codec)
        return self._decompressor

    @property
    def d2h_bytes(self) -> int:
        """Total egress (payload + metadata + counters) bytes fetched."""
        return self.d2h_payload_bytes + self.d2h_meta_bytes + self.d2h_ctrl_bytes

    def reset_d2h(self) -> None:
        self.d2h_payload_bytes = 0
        self.d2h_meta_bytes = 0
        self.d2h_ctrl_bytes = 0

    # -------------------------------------------------------------- core step
    def step(self, state: Any, block: jax.Array):
        """Encode one micro-batch block (lanes, B) and pack its bitstream."""
        return self.masked_step(state, block, None)

    def masked_step(self, state: Any, block: jax.Array, mask: Optional[jax.Array]):
        """`step` with pad slots (mask == False) dropped from the bitstream
        when the codec allows it (`meta.maskable`); non-maskable codecs ship
        their pad symbols so the decoder's state replay stays exact."""
        state, enc = self.codec.encode(state, block)
        state = self._merge_if_shared(state)
        lanes, B = block.shape
        bitlen = enc.bitlen
        if mask is not None and self.codec.meta.maskable:
            bitlen = jnp.where(mask, bitlen, 0)
        flat_codes = enc.codes.reshape(lanes * B, 2)
        flat_blen = bitlen.reshape(lanes * B)
        out_words = lanes * B * 2 + 2
        words, total_bits, _ = bits.pack_bits(flat_codes, flat_blen, out_words)
        return state, words, total_bits, flat_blen

    def _scan_body(self, state: Any, blk: jax.Array):
        """Hot-path scan body: bits + words only (PR-1 parity); the
        per-symbol bitlens are scanned out only when a frame is being
        collected (`_scan_body_payload`) — no extra output traffic on the
        timed benchmark paths."""
        state, words, tb, _ = self.step(state, blk)
        return state, (tb, words)

    def _scan_body_payload(self, state: Any, blk: jax.Array):
        state, words, tb, blen = self.step(state, blk)
        return state, (tb, words, blen)

    def masked_step_meta7(self, state: Any, block: jax.Array, mask: Optional[jax.Array]):
        """`masked_step` + on-device 7-bit metadata packing: the serving
        runtime's egress flush — ONE dispatch whose outputs are already
        wire-shaped (the host then fetches the live word prefix only)."""
        state, words, tb, blen = self.masked_step(state, block, mask)
        return state, words, tb, bits.pack_meta7(blen)

    # ------------------------------------------------ compacted egress fns
    def _egress_scan_fn(self, chunk_len: int):
        """Jitted scan-with-compaction over `chunk_len` blocks: ONE
        dispatch whose egress leaves the device wire-shaped.

        The compaction rides in the scan CARRY: each step writes its
        worst-case word buffer at the running word offset of a chunk-wide
        buffer (`dynamic_update_slice`, in-place under XLA), and the next
        step's live words overwrite the dead tail — so the per-block
        worst-case buffers are never materialized as scan outputs at all.
        The per-symbol bitlens scan out and 7-bit-pack in one vectorized
        pass after the scan (`bits.pack_meta7`)."""
        key = (chunk_len, "egress")
        fn = self._scan_fns.get(key)
        if fn is None:
            meta7 = self._meta7_ok

            def body(carry, blk):
                state, buf, off = carry
                state, words, tb, blen = self.step(state, blk)
                buf = jax.lax.dynamic_update_slice(buf, words, (off,))
                return (state, buf, off + (tb + 31) // 32), (tb, blen)

            def scan_compact(state, blks):
                n, lanes, per_lane = blks.shape
                cap = n * (lanes * per_lane * 2 + 2)
                carry0 = (state, jnp.zeros((cap,), jnp.uint32), jnp.int32(0))
                (state, buf, total), (tb, blen) = jax.lax.scan(body, carry0, blks)
                meta = jax.vmap(bits.pack_meta7)(blen) if meta7 else blen
                return state, tb, buf, total, meta

            fn = jax.jit(scan_compact)
            self._scan_fns[key] = fn
        return fn

    def _egress_step_fn(self):
        """Per-block egress step (eager strategy): step + metadata pack;
        single blocks need no word compaction (the host fetch slices the
        live prefix at the block's own offset 0)."""
        fn = self._scan_fns.get("egress_step")
        if fn is None:
            meta7 = self._meta7_ok

            def step_compact(state, blk):
                state, words, tb, blen = self.step(state, blk)
                meta = bits.pack_meta7(blen) if meta7 else blen
                return state, words, tb, meta

            fn = jax.jit(step_compact)
            self._scan_fns["egress_step"] = fn
        return fn

    # ------------------------------------------------------------- finalize
    def _flush_pack_body(self, state: Any):
        """The ONE definition of flush mini-block packing: `Codec.flush`'s
        trailing symbols -> (words, total_bits, bitlen). Jitted solo below
        and jit(vmap)'d for gangs — one body, so the two paths cannot
        desynchronize the wire layout."""
        enc = self.codec.flush(state)
        lanes, fs = enc.bitlen.shape
        words, tb, _ = bits.pack_bits(
            enc.codes.reshape(lanes * fs, 2),
            enc.bitlen.reshape(lanes * fs),
            lanes * fs * 2 + 2,
        )
        return words, tb, enc.bitlen

    def _pack_flush(self, state: Any):
        """Pack the codec's trailing state symbols (`Codec.flush`)."""
        if self._flush_fn is None:
            self._flush_fn = jax.jit(self._flush_pack_body)
        return self._flush_fn(state)

    @property
    def flush_slots(self) -> int:
        """Per-lane symbol slots the flush mini-block occupies (0 = none)."""
        return self._flush_slots

    # -------------------------------------------------------- execution paths
    def run_fused(
        self,
        blocks_dev: jax.Array,
        state: Any,
        chunk: Optional[int] = None,
        collect: bool = False,
    ):
        """Chunked-scan execution: (state, per-block bits, words, bitlens).

        `collect=True` scans the per-symbol bitlens out too (framing);
        otherwise the scan carries only bits + words, like the pre-egress
        hot path."""
        bits_out, words_out, blen_out = [], [], []
        body = self._scan_body_payload if collect else self._scan_body
        key = "payload" if collect else ""
        for start, length in self._chunks(blocks_dev.shape[0], chunk):
            self.dispatches += 1
            state, ys = self._scan_fn(length, key=key, body=body)(
                state, blocks_dev[start : start + length]
            )
            bits_out.append(ys[0])
            words_out.append(ys[1])
            blen_out.append(ys[2] if collect else None)
        return state, bits_out, words_out, blen_out

    def run_dispatch(self, blocks_dev: jax.Array, state: Any):
        """Per-block dispatch loop (eager strategy / Fig 10b baseline)."""
        bits_out, words_out, blen_out = [], [], []
        for i in range(blocks_dev.shape[0]):
            self.dispatches += 1
            state, words, tb, blen = self._step(state, blocks_dev[i])
            bits_out.append(tb)
            words_out.append(words)
            blen_out.append(blen)
        return state, bits_out, words_out, blen_out

    # -------------------------------------------------------- gang execution
    @staticmethod
    def stack_states(states: List[Any]) -> Any:
        """Stack per-session codec states along a new leading gang axis.

        Works for stateless codecs too: a `None` state is an empty pytree,
        so the stacked state is just `None` again."""
        return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *states)

    @staticmethod
    def unstack_state(states: Any, i: int) -> Any:
        """Slice one gang member's state back out of the stacked pytree."""
        return jax.tree_util.tree_map(lambda x: x[i], states)

    def _gang_step_fn(self, meta7: bool = False, mesh: Any = None):
        """Jitted vmapped masked step over a leading session axis: ONE
        dispatch compresses one micro-batch from EACH gang member. jit
        re-specializes per gang size automatically; every member keeps its
        own codec state, mask, and bitstream — the stacking is pure
        data parallelism across sessions (paper §3.4, applied ACROSS
        streams instead of within one). `meta7=True` is the egress-wave
        variant: the final output is the 7-bit-packed bitlen metadata
        instead of raw int32 bitlens (same dispatch count, wire-width
        transfer).

        `mesh` (a pure `("data",)` fleet mesh, DESIGN.md §14) additionally
        shards the session axis over the mesh devices via `jax.shard_map`:
        the vmapped body runs per shard over its local session slice, so one
        dispatch covers devices x gang sessions. The body is closed over —
        per-session state (including the shared-dictionary LWW merge, which
        acts WITHIN a session's lanes) never crosses a shard boundary, which
        is exactly why the sharded wave stays bit-identical to solo runs."""
        name = "gang_step_meta7" if meta7 else "gang_step"
        key = name if mesh is None else (name, mesh)
        fn = self._scan_fns.get(key)
        if fn is None:
            body = self.masked_step_meta7 if meta7 else self.masked_step
            fn = jax.vmap(body)
            if mesh is not None:
                from jax.sharding import PartitionSpec

                spec = PartitionSpec("data")
                fn = jax.shard_map(
                    fn,
                    mesh=mesh,
                    in_specs=spec,
                    out_specs=spec,
                    check_vma=False,
                )
            fn = jax.jit(fn)
            self._scan_fns[key] = fn
        return fn

    def gang_step(
        self,
        states: Any,
        blocks: jax.Array,
        masks: jax.Array,
        meta7: bool = False,
        mesh: Any = None,
    ):
        """One timed gang dispatch over stacked micro-batches.

        Args: stacked states (leading gang axis), blocks uint32[S, L, B],
        masks bool[S, L, B]. Returns (states, words[S, OW], total_bits[S],
        meta[S, ...], wall_s) — `meta` is raw bitlens int32[S, L*B], or the
        7-bit-packed uint32 stream per member when `meta7=True`. The first
        call at a given gang size compiles untimed (memoized), so measured
        costs stay compute.

        With `mesh` set the session axis shards over the mesh's "data" axis;
        the caller pads S to a multiple of `mesh.size` (the fleet dispatcher
        replicates a member into the pad slots and discards their outputs)."""
        if mesh is not None and getattr(mesh, "size", 1) <= 1:
            mesh = None  # a 1-device mesh IS the plain vmapped dispatch
        if mesh is not None and blocks.shape[0] % mesh.size != 0:
            raise ValueError(
                f"sharded gang wave of {blocks.shape[0]} sessions does not "
                f"divide the {mesh.size}-device mesh; pad the wave first"
            )
        fn = self._gang_step_fn(meta7, mesh=mesh)
        key = ("gang_step_meta7" if meta7 else "gang_step", tuple(blocks.shape), mesh)
        if key not in self._warmed:
            jax.block_until_ready(fn(states, blocks, masks))
            self._warmed.add(key)
        with spans.span("wave.step"):
            t0 = time.perf_counter()
            self.dispatches += 1
            states, words, total_bits, bitlen = jax.block_until_ready(
                fn(states, blocks, masks)
            )
            wall = time.perf_counter() - t0
        return states, words, total_bits, bitlen, wall

    def _gang_scan_body(self, states: Any, blks: jax.Array):
        """Scan body for offline gang runs: blks is (S, L, B) — the blocks
        at one stream position across all gang members."""
        states, words, tb, blen = jax.vmap(self.step)(states, blks)
        return states, (tb, words, blen)

    def _gang_egress_scan_fn(self, chunk_len: int):
        """Gang mirror of `_egress_scan_fn`: scan the vmapped body over
        `chunk_len` stream positions, then compact/pack PER MEMBER — each
        member's payload and metadata leave the device wire-shaped, so the
        per-member scatter slices compacted segments instead of copying
        full (chunk, S, OW) worst-case buffers."""
        key = (chunk_len, "gang_egress")
        fn = self._scan_fns.get(key)
        if fn is None:
            meta7 = self._meta7_ok

            def scan_compact(states, blks):
                states, (tb, words, blen) = jax.lax.scan(
                    self._gang_scan_body, states, blks
                )
                # (C, S, ·) -> (S, C, ·): compaction is per member
                payload, total = jax.vmap(bits.compact_payload)(
                    jnp.swapaxes(words, 0, 1), tb.T
                )
                mblen = jnp.swapaxes(blen, 0, 1)
                meta = jax.vmap(jax.vmap(bits.pack_meta7))(mblen) if meta7 else mblen
                return states, tb, payload, total, meta

            fn = jax.jit(scan_compact)
            self._scan_fns[key] = fn
        return fn

    def _pack_flush_gang(self, states: Any):
        """Vmapped `_flush_pack_body` for stacked states."""
        fn = self._scan_fns.get("gang_flush")
        if fn is None:
            fn = jax.jit(jax.vmap(self._flush_pack_body))
            self._scan_fns["gang_flush"] = fn
        return fn(states)

    def execute_gang(
        self,
        shaped_list: List[ShapedStream],
        states: Optional[List[Any]] = None,
        chunk: Optional[int] = None,
        finalize: bool = True,
        collect_payload: bool = False,
        compact: bool = True,
    ) -> Tuple[List[ExecutionResult], float]:
        """Run S same-geometry streams through ONE gang-batched execution.

        The chunked `lax.scan` of `run_fused` runs with a vmapped body: each
        scan step compresses stream position b of EVERY member in one
        dispatch, carrying all members' codec states. Members must share
        block geometry (full-block count, tail shape); their values, masks
        and states are independent. Returns (per-member ExecutionResults,
        gang wall seconds); each member's `wall_s` is the gang wall split
        evenly — the dispatch is shared, which is the whole point.

        `collect_payload=True` defaults to the compacted egress: every
        member's payload/metadata is compacted on device and fetched as
        exact slices (no full (chunk, S, OW) worst-case copies);
        `compact=False` keeps the legacy copy-everything collection as the
        oracle baseline."""
        S = len(shaped_list)
        if S == 0:
            return [], 0.0
        ref = shaped_list[0]
        for s in shaped_list[1:]:
            same_tail = (s.tail is None) == (ref.tail is None) and (
                s.tail is None or s.tail.shape == ref.tail.shape
            )
            if len(s.blocks) != len(ref.blocks) or not same_tail:
                raise ValueError(
                    "gang members must share block geometry "
                    f"({len(ref.blocks)} full + tail {None if ref.tail is None else ref.tail.shape}"
                    f" vs {len(s.blocks)} full + tail {None if s.tail is None else s.tail.shape})"
                )
        n_full = len(ref.blocks)
        blocks_dev = (
            jnp.asarray(np.stack([s.blocks for s in shaped_list], axis=1))
            if n_full
            else None
        )  # (n_full, S, L, B)
        tail_dev = mask_dev = None
        if ref.tail is not None:
            tail_dev = jnp.asarray(np.stack([s.tail for s in shaped_list]))
            mask_dev = jnp.asarray(np.stack([s.tail_mask for s in shaped_list]))
        if states is None:
            states = [self.init_state() for _ in range(S)]
        stacked = self.stack_states(states)

        # untimed compile pass (memoized per gang geometry and egress mode)
        egress = collect_payload and compact
        wkey = (
            "gang",
            S,
            None if blocks_dev is None else tuple(blocks_dev.shape),
            None if tail_dev is None else tuple(tail_dev.shape),
            chunk,
            egress,
        )
        if wkey not in self._warmed:
            if blocks_dev is not None:
                warm_state = self.stack_states([self.init_state() for _ in range(S)])
                for length in sorted({ln for _, ln in self._chunks(n_full, chunk)}):
                    fn = (
                        self._gang_egress_scan_fn(length)
                        if egress
                        else self._scan_fn(length, key="gang", body=self._gang_scan_body)
                    )
                    jax.block_until_ready(fn(warm_state, blocks_dev[:length]))
            if tail_dev is not None:
                jax.block_until_ready(
                    self._gang_step_fn()(stacked, tail_dev, mask_dev)
                )
            if finalize and self._has_flush:
                jax.block_until_ready(self._pack_flush_gang(stacked))
            self._warmed.add(wkey)

        if egress:
            return self._execute_gang_egress(
                shaped_list, stacked, blocks_dev, tail_dev, mask_dev,
                chunk, finalize, n_full,
            )

        bits_acc: List[Any] = []  # each (chunk, S) / (S,)
        words_acc: List[Any] = []
        blen_acc: List[Any] = []
        flush_out = None
        t0 = time.perf_counter()
        if blocks_dev is not None:
            for start, length in self._chunks(n_full, chunk):
                self.dispatches += 1
                stacked, ys = self._scan_fn(
                    length, key="gang", body=self._gang_scan_body
                )(stacked, blocks_dev[start : start + length])
                bits_acc.append(ys[0])
                words_acc.append(ys[1])
                blen_acc.append(ys[2])
        if tail_dev is not None:
            self.dispatches += 1
            stacked, twords, tb, tblen = self._gang_step_fn()(
                stacked, tail_dev, mask_dev
            )
            bits_acc.append(tb)
            words_acc.append(twords)
            blen_acc.append(tblen)
        if finalize and self._has_flush:
            flush_out = self._pack_flush_gang(stacked)
            bits_acc.append(flush_out[1])
        jax.block_until_ready(bits_acc)
        wall = time.perf_counter() - t0

        flush_slots = self.flush_slots if (finalize and self._has_flush) else 0
        # host copies once per device buffer (post-timing), then per-member
        # slicing below is pure NumPy views. Bits always travel (the
        # accounting needs them); the worst-case word/bitlen buffers cross
        # only on the legacy collect path — the compacted path above fetches
        # exact slices instead, and plain (non-collect) gang runs skip the
        # payload copies entirely.
        host_chunks = [
            (
                np.asarray(b, np.float64),
                np.asarray(w) if collect_payload else None,
                np.asarray(bl, np.int32) if collect_payload else None,
            )
            for b, w, bl in zip(bits_acc[: len(words_acc)], words_acc, blen_acc)
        ]
        host_flush = None
        if flush_out is not None:
            host_flush = (
                np.asarray(flush_out[0]),
                np.asarray(flush_out[1]),
                np.asarray(flush_out[2], np.int32),
            )
        results = []
        for i in range(S):
            member_bits = []
            member_words: List[np.ndarray] = []
            member_blen: List[np.ndarray] = []
            for b, w, bl in host_chunks:
                if b.ndim == 2:  # fused chunk: (chunk, S)
                    member_bits.append(b[:, i])
                    if collect_payload:
                        member_words.extend(w[:, i])
                        member_blen.extend(bl[:, i])
                else:  # tail gang step: (S,)
                    member_bits.append(b[i : i + 1])
                    if collect_payload:
                        member_words.append(w[i])
                        member_blen.append(bl[i])
            member_flush = None
            if host_flush is not None:
                fw, fb, fblen = host_flush
                member_flush = (fw[i], int(fb[i]), fblen[i])
                member_bits.append(np.asarray([float(member_flush[1])]))
            per_block = (
                np.concatenate([np.atleast_1d(b) for b in member_bits])
                if member_bits
                else np.zeros(0, np.float64)
            )
            payload = None
            if collect_payload:
                payload = self._collect_payload(
                    shaped_list[i],
                    member_words,
                    member_blen,
                    per_block,
                    member_flush,
                )
            results.append(
                ExecutionResult(
                    per_block_bits=per_block,
                    wall_s=wall / S,
                    n_tuples=shaped_list[i].n_valid,
                    state=self.unstack_state(stacked, i),
                    legacy_payload=payload,
                    flush_slots=flush_slots,
                )
            )
        return results, wall

    def _execute_gang_egress(
        self,
        shaped_list: List[ShapedStream],
        stacked: Any,
        blocks_dev: Optional[jax.Array],
        tail_dev: Optional[jax.Array],
        mask_dev: Optional[jax.Array],
        chunk: Optional[int],
        finalize: bool,
        n_full: int,
    ) -> Tuple[List[ExecutionResult], float]:
        """Gang execution with per-member device compaction (satellite of
        DESIGN.md §13): each chunk's dispatch hands back every member's
        payload already compacted, and the per-member scatter fetches
        exact slices — double-buffered so chunk k+1 (and the tail/flush
        dispatches) compute while chunk k's D2H drains."""
        S = len(shaped_list)
        bt = self.block_tuples
        lanes = self.config.lanes
        sinks = [_EgressSink(self) for _ in range(S)]
        pending = None

        def fetch(item) -> None:
            tb, payload, total, meta = item
            totals = np.asarray(total)
            tbh = np.asarray(tb, np.int64)  # (C, S)
            meta_np = np.asarray(meta)  # (S, C, MW packed | L*B raw)
            n_chunk = tbh.shape[0]
            for s in range(S):
                seg = np.asarray(payload[s, : int(totals[s])])
                sinks[s].add_unit(
                    seg,
                    tbh[:, s],
                    [bt] * n_chunk,
                    bt,
                    meta=meta_np[s] if self._meta7_ok else None,
                    raw=None if self._meta7_ok else meta_np[s],
                    extra_bytes=4 * n_chunk + 4,
                )

        t0 = time.perf_counter()
        if blocks_dev is not None:
            for start, length in self._chunks(n_full, chunk):
                self.dispatches += 1
                out = self._gang_egress_scan_fn(length)(
                    stacked, blocks_dev[start : start + length]
                )
                stacked = out[0]
                prev, pending = pending, out[1:]
                if prev is not None:
                    fetch(prev)  # overlaps the chunk just dispatched
        tail_out = None
        if tail_dev is not None:
            self.dispatches += 1
            stacked, twords, tbv, tblen = self._gang_step_fn()(
                stacked, tail_dev, mask_dev
            )
            tail_out = (twords, tbv, tblen)
        if pending is not None:
            fetch(pending)  # overlaps the tail/flush dispatches
        if tail_out is not None:
            twords, tbv, tblen = tail_out
            tbh = np.asarray(tbv, np.int64)
            tblen_np = np.asarray(tblen, np.int32)
            tail_syms = int(tail_dev.shape[1] * tail_dev.shape[2])
            for s in range(S):
                rem = shaped_list[s].n_valid - n_full * bt
                seg = np.asarray(twords[s, : (int(tbh[s]) + 31) // 32])
                sinks[s].add_unit(
                    seg, [int(tbh[s])], [rem], tail_syms,
                    raw=tblen_np[s], extra_bytes=4,
                )
        flush_happened = finalize and self._has_flush
        if flush_happened:
            fw, fb, fblen = self._pack_flush_gang(stacked)
            fbh = np.asarray(fb, np.int64)
            fblen_np = np.asarray(fblen, np.int32)
            for s in range(S):
                seg = np.asarray(fw[s, : (int(fbh[s]) + 31) // 32])
                sinks[s].add_unit(
                    seg, [int(fbh[s])], [0], lanes * self._flush_slots,
                    raw=fblen_np[s], extra_bytes=4,
                )
        comps = [sk.finish() for sk in sinks]
        wall = time.perf_counter() - t0

        flush_slots = self.flush_slots if flush_happened else 0
        results = [
            ExecutionResult(
                per_block_bits=c.block_bits.astype(np.float64),
                wall_s=wall / S,
                n_tuples=shaped_list[i].n_valid,
                state=self.unstack_state(stacked, i),
                compacted=c,
                flush_slots=flush_slots,
            )
            for i, c in enumerate(comps)
        ]
        return results, wall

    def warmup(
        self,
        blocks_dev: Optional[jax.Array],
        tail=None,
        tail_mask=None,
        fused: bool = True,
        chunk: Optional[int] = None,
        collect: bool = False,
        compact: bool = False,
    ) -> None:
        """Compile every kernel an `execute` call will hit (untimed).

        Memoized on shapes: the jit caches make recompilation free, but the
        warmup pass itself executes real blocks, so repeat `execute` calls
        (best-of-2 benchmarks, breakdown replays) must not re-pay it."""
        key = (
            None if blocks_dev is None else tuple(blocks_dev.shape),
            None if tail is None else tuple(tail.shape),
            chunk,
            fused,
            collect,
            compact,
        )
        if key in self._warmed:
            return
        state = self.init_state()
        if blocks_dev is not None and blocks_dev.shape[0] > 0:
            if fused:
                for length in sorted({ln for _, ln in self._chunks(blocks_dev.shape[0], chunk)}):
                    if collect and compact:
                        fn = self._egress_scan_fn(length)
                    else:
                        body = self._scan_body_payload if collect else self._scan_body
                        skey = "payload" if collect else ""
                        fn = self._scan_fn(length, key=skey, body=body)
                    jax.block_until_ready(fn(state, blocks_dev[:length]))
            elif collect and compact:
                jax.block_until_ready(self._egress_step_fn()(state, blocks_dev[0]))
            else:
                jax.block_until_ready(self._step(state, blocks_dev[0]))
        if tail is not None:
            jax.block_until_ready(self._masked_step(state, tail, tail_mask))
        if self._has_flush:
            jax.block_until_ready(self._pack_flush(state))
        self._warmed.add(key)

    def execute(
        self,
        shaped: ShapedStream,
        state: Any = None,
        fused: Optional[bool] = None,
        warmup: bool = True,
        chunk: Optional[int] = None,
        finalize: bool = True,
        collect_payload: bool = False,
        compact: bool = True,
    ) -> ExecutionResult:
        """Run one shaped stream through the codec; measure wall time.

        `fused=None` follows the plan (lazy -> fused scan, eager ->
        dispatch loop); pass an explicit bool to force a path (benchmarks
        compare both on identical blocks). `chunk` overrides the plan's scan
        fusion length. `finalize=True` closes the stream: `Codec.flush`'s
        trailing symbols (RLE's open run) are packed as a flush mini-block
        and counted. `collect_payload=True` additionally keeps every
        block's wire contribution so `frame_from` can build the frame —
        by default via the device-resident compaction path (wire-shaped
        double-buffered fetches, DESIGN.md §13); `compact=False` keeps the
        legacy worst-case-buffer collection as the measurable baseline and
        the `build_frame` oracle input."""
        if fused is True and chunk is None and self.plan.scan_chunk <= 1:
            # explicit fuse request against a per-block-dispatch plan (the
            # Fig 10b 'running' replay): the plan's chunk of 1 would just
            # re-pay the dispatches
            chunk = _FORCED_FUSE_CHUNK
        if fused is None:
            fused = self.plan.execution == ExecutionStrategy.LAZY
        if collect_payload and compact:
            return self._execute_egress(
                shaped, state=state, fused=fused, warmup=warmup, chunk=chunk,
                finalize=finalize,
            )
        blocks_dev = jnp.asarray(shaped.blocks) if len(shaped.blocks) else None
        tail_dev = jnp.asarray(shaped.tail) if shaped.tail is not None else None
        mask_dev = jnp.asarray(shaped.tail_mask) if shaped.tail is not None else None
        if warmup:
            self.warmup(
                blocks_dev, tail_dev, mask_dev, fused=fused, chunk=chunk,
                collect=collect_payload,
            )

        if state is None:
            state = self.init_state()
        bits_acc: List[Any] = []
        words_acc: List[Any] = []
        blen_acc: List[Any] = []
        flush_out = None
        t0 = time.perf_counter()
        if blocks_dev is not None:
            if fused:
                state, bits_acc, words_acc, blen_acc = self.run_fused(
                    blocks_dev, state, chunk, collect=collect_payload
                )
            else:
                state, bits_acc, words_acc, blen_acc = self.run_dispatch(blocks_dev, state)
        if tail_dev is not None:
            self.dispatches += 1
            state, twords, tb, tblen = self._masked_step(state, tail_dev, mask_dev)
            bits_acc.append(tb)
            words_acc.append(twords)
            blen_acc.append(tblen)
        if finalize and self._has_flush:
            flush_out = self._pack_flush(state)
            bits_acc.append(flush_out[1])
        jax.block_until_ready(bits_acc)
        wall = time.perf_counter() - t0

        per_block = (
            np.concatenate([np.atleast_1d(np.asarray(b, np.float64)) for b in bits_acc])
            if bits_acc
            else np.zeros(0, np.float64)
        )
        payload = None
        flush_slots = self.flush_slots if (finalize and self._has_flush) else 0
        if collect_payload:
            payload = self._collect_payload(shaped, words_acc, blen_acc, per_block, flush_out)
        return ExecutionResult(
            per_block_bits=per_block,
            wall_s=wall,
            n_tuples=shaped.n_valid,
            state=state,
            legacy_payload=payload,
            flush_slots=flush_slots,
        )

    def _execute_egress(
        self,
        shaped: ShapedStream,
        state: Any = None,
        fused: bool = True,
        warmup: bool = True,
        chunk: Optional[int] = None,
        finalize: bool = True,
    ) -> ExecutionResult:
        """`execute` with the device-resident compaction egress (the
        default `collect_payload` path, DESIGN.md §13).

        Each fused chunk (or eager block) leaves the device wire-shaped —
        compacted payload words + 7-bit-packed bitlen metadata — and is
        fetched through the double-buffered `_EgressSink`: chunk k+1's
        dispatch is in flight before chunk k's D2H syncs, so there is no
        per-chunk barrier and no worst-case-buffer host copy. The wall
        includes the interleaved fetches (they ARE the egress) but the
        dispatch count is unchanged versus the plain collect path: the
        compaction runs inside the same jitted executions."""
        blocks_dev = jnp.asarray(shaped.blocks) if len(shaped.blocks) else None
        tail_dev = jnp.asarray(shaped.tail) if shaped.tail is not None else None
        mask_dev = jnp.asarray(shaped.tail_mask) if shaped.tail is not None else None
        if warmup:
            self.warmup(
                blocks_dev, tail_dev, mask_dev, fused=fused, chunk=chunk,
                collect=True, compact=True,
            )
        if state is None:
            state = self.init_state()
        sink = _EgressSink(self)
        bt = self.block_tuples
        lanes = self.config.lanes
        rem = shaped.n_valid - len(shaped.blocks) * bt

        t0 = time.perf_counter()
        if blocks_dev is not None:
            if fused:
                for start, length in self._chunks(blocks_dev.shape[0], chunk):
                    self.dispatches += 1
                    state, tb, payload, total, meta = self._egress_scan_fn(length)(
                        state, blocks_dev[start : start + length]
                    )
                    sink.put_chunk(
                        tb, payload, total, meta,
                        packed=self._meta7_ok, syms=bt, valid=bt,
                    )
            else:
                step = self._egress_step_fn()
                for i in range(blocks_dev.shape[0]):
                    self.dispatches += 1
                    state, words, tb, meta = step(state, blocks_dev[i])
                    sink.put_block(
                        tb, words, meta, packed=self._meta7_ok, syms=bt, valid=bt
                    )
        if tail_dev is not None:
            self.dispatches += 1
            state, twords, tb, tblen = self._masked_step(state, tail_dev, mask_dev)
            sink.put_block(
                tb, twords, tblen, packed=False,
                syms=int(tail_dev.shape[0] * tail_dev.shape[1]), valid=rem,
            )
        if finalize and self._has_flush:
            fw, fb, fblen = self._pack_flush(state)
            sink.put_block(
                fb, fw, fblen, packed=False, syms=lanes * self._flush_slots, valid=0
            )
        comp = sink.finish()
        wall = time.perf_counter() - t0

        flush_slots = self.flush_slots if (finalize and self._has_flush) else 0
        return ExecutionResult(
            per_block_bits=comp.block_bits.astype(np.float64),
            wall_s=wall,
            n_tuples=shaped.n_valid,
            state=state,
            compacted=comp,
            flush_slots=flush_slots,
        )

    # ------------------------------------------------------------- framing
    def _collect_payload(
        self, shaped: ShapedStream, words_acc, blen_acc, per_block: np.ndarray, flush_out
    ) -> List[BlockPayload]:
        """Host copies of every block's wire contribution (post-timing).

        This is the legacy (compact=False) egress: every block's FULL
        worst-case word buffer and raw int32 bitlens cross device->host —
        the ~5-6x traffic the compaction path eliminates. The same d2h
        counters are charged here so the two paths compare under one
        meter."""
        n_full = len(shaped.blocks)
        bt = self.block_tuples
        rem = shaped.n_valid - n_full * bt
        # flatten fused chunk outputs into per-block rows
        words_np: List[np.ndarray] = []
        blen_np: List[np.ndarray] = []
        for w, b in zip(words_acc, blen_acc):
            w = np.asarray(w)
            b = np.asarray(b, np.int32)
            self.d2h_payload_bytes += w.nbytes
            self.d2h_meta_bytes += b.nbytes
            if w.ndim == 2:  # one fused chunk: (chunk, OW) / (chunk, L*B)
                words_np.extend(w)
                blen_np.extend(b)
            else:
                words_np.append(w)
                blen_np.append(b)
        payload = []
        for i in range(n_full):
            payload.append(
                BlockPayload(words_np[i], int(per_block[i]), blen_np[i], bt)
            )
        k = n_full
        if shaped.tail is not None:
            payload.append(
                BlockPayload(words_np[k], int(per_block[k]), blen_np[k], rem)
            )
            k += 1
        if flush_out is not None:
            payload.append(BlockPayload(*self._flush_entry(flush_out)))
        return payload

    @staticmethod
    def _flush_entry(flush_out) -> tuple:
        """Canonical flush-mini-block entry (words, nbits, bitlen, valid=0).

        The ONE place the flush block's frame layout is defined — reused by
        `_collect_payload` (engine path) and `flush_block_entry` (session
        egress), so the two paths cannot desynchronize."""
        fw, fb, fblen = flush_out
        return (np.asarray(fw), int(fb), np.asarray(fblen, np.int32).ravel(), 0)

    def flush_block_entry(self, state: Any):
        """Pack `Codec.flush`'s trailing symbols for a frame; None if the
        codec has no trailing state. Does not mutate `state`."""
        if not self._has_flush:
            return None
        return self._flush_entry(self._pack_flush(state))

    def _maybe_entropy(self, frame: bits.Frame) -> bits.Frame:
        """Apply wire feature stages at marshal time (dict id, entropy,
        integrity).

        Every egress path — solo fused/eager, gang, server waves, legacy
        compact=False — funnels through `marshal_frame`/`marshal_compacted`,
        so hooking here composes the stages with all of them (DESIGN.md
        §15/§17/§18). The frame keeps its raw fields; only serialization
        changes."""
        topic = getattr(self.codec, "dict_topic", None)
        if topic is not None:
            # seeded codec: stamp (topic, version) so the frame is
            # self-describing and decode can fetch the same seed
            frame.dict_id = (topic, self.codec.dict_version)
        if self.entropy == "rans":
            frame.apply_entropy()
        if self.integrity == "crc32c":
            # CRCs themselves are computed lazily at to_bytes time, over the
            # final serialized sections (post-entropy, post-dict)
            frame.integrity = "crc32c"
        return frame

    def marshal_frame(
        self,
        blocks,
        per_lane: int,
        n_full: int,
        tail_per_lane: int,
        flush_slots: int,
        n_valid: int,
    ) -> bits.Frame:
        """Single authority for frame marshalling: codec id and lane count
        come from this pipeline's config, callers only supply the block
        geometry and the (words, nbits, bitlen, valid) entries."""
        spans.count("egress.frames_marshalled")
        return self._maybe_entropy(bits.build_frame(
            codec_id=WIRE_CODEC_IDS[self.codec.name],
            lanes=self.config.lanes,
            per_lane=per_lane,
            n_full=n_full,
            tail_per_lane=tail_per_lane,
            flush_slots=flush_slots,
            n_valid=n_valid,
            blocks=blocks,
        ))

    def marshal_compacted(
        self,
        *,
        per_lane: int,
        n_full: int,
        tail_per_lane: int,
        flush_slots: int,
        n_valid: int,
        block_bits,
        block_valid,
        payload,
        bitlen=None,
        packed_meta=None,
    ) -> bits.Frame:
        """`marshal_frame`'s compacted twin: codec id and lane count still
        come from this pipeline's config; the caller hands over the
        already-wire-shaped payload/metadata (`Frame.from_compacted`)."""
        spans.count("egress.frames_marshalled")
        return self._maybe_entropy(bits.Frame.from_compacted(
            codec_id=WIRE_CODEC_IDS[self.codec.name],
            lanes=self.config.lanes,
            per_lane=per_lane,
            n_full=n_full,
            tail_per_lane=tail_per_lane,
            flush_slots=flush_slots,
            n_valid=n_valid,
            block_bits=block_bits,
            block_valid=block_valid,
            payload=payload,
            bitlen=bitlen,
            packed_meta=packed_meta,
        ))

    def frame_from(self, shaped: ShapedStream, result: ExecutionResult) -> bits.Frame:
        """Assemble the wire-format frame from a `collect_payload` run.

        Compacted results take the `Frame.from_compacted` fast path
        (header math only — the payload and metadata already arrived
        wire-shaped); legacy results go through `build_frame`, which
        survives as the oracle the equality tests compare against."""
        if result.compacted is not None:
            c = result.compacted
            return self.marshal_compacted(
                per_lane=self.block_tuples // self.config.lanes,
                n_full=len(shaped.blocks),
                tail_per_lane=0 if shaped.tail is None else shaped.tail.shape[1],
                flush_slots=result.flush_slots,
                n_valid=shaped.n_valid,
                block_bits=c.block_bits,
                block_valid=c.block_valid,
                payload=c.payload,
                bitlen=c.bitlen,
                packed_meta=c.packed_meta,
            )
        if result.legacy_payload is None:
            raise ValueError("execute(collect_payload=True) required for framing")
        return self.marshal_frame(
            blocks=[(p.words, p.nbits, p.bitlen, p.valid) for p in result.legacy_payload],
            per_lane=self.block_tuples // self.config.lanes,
            n_full=len(shaped.blocks),
            tail_per_lane=0 if shaped.tail is None else shaped.tail.shape[1],
            flush_slots=result.flush_slots,
            n_valid=shaped.n_valid,
        )

    def compress_to_frame(
        self, values: np.ndarray, state: Any = None, compact: bool = True
    ) -> bits.Frame:
        """One-call egress: shape, execute (fused per plan), finalize, frame.

        For the full encode -> frame -> decode circle use
        `CStreamEngine.roundtrip`, which caches its `DecompressionPipeline`
        (a fresh one per call would pay XLA retracing every time)."""
        shaped = self.shape_blocks(values)
        res = self.execute(shaped, state=state, collect_payload=True, compact=compact)
        return self.frame_from(shaped, res)


# ---------------------------------------------------- decompression pipeline --
class DecompressionPipeline(BlockedExecutor):
    """Egress executor: frame -> blocks -> fused chunked-scan decode.

    Shares the blocked-executor machinery (plan, chunking, scan caches)
    with the compression side. Pass the SAME codec instance (or an
    identically configured one) that produced the frame: the frame header
    identifies the codec family; quantizer parameters are session config,
    as in any negotiated wire protocol."""

    def __init__(
        self,
        config: SpecLike,
        codec: Optional[Codec] = None,
        sample: Optional[np.ndarray] = None,
        plan: Optional[ExecutionPlan] = None,
    ):
        super().__init__(config, sample=sample, codec=codec, plan=plan)
        self._tail_fn_jit = None  # jit retraces per block shape on its own
        self._stream_decode_fn = None
        #: poisoned-state latch: set to the first FrameError that made this
        #: decoder fail; further decode calls refuse until reset_quarantine()
        self.quarantined: Optional[bits.FrameError] = None

    # ------------------------------------------------------------ scan body
    def _decode_block(self, state: Any, words: jax.Array, bitlen2d: jax.Array):
        lanes, B = bitlen2d.shape
        codes, _ = bits.unpack_symbols(words, bitlen2d.reshape(lanes * B))
        enc = Encoded(codes.reshape(lanes, B, 2), bitlen2d)
        state, x = self.codec.decode(state, enc)
        return self._merge_if_shared(state), x

    def _scan_body(self, state: Any, xs: Any):
        words, bitlen2d = xs
        if self.codec.meta.scope == "stream":
            # unpack only; the cross-block expansion decode runs once, after
            # the scan, over the whole symbol stream
            lanes, B = bitlen2d.shape
            codes, _ = bits.unpack_symbols(words, bitlen2d.reshape(lanes * B))
            return state, codes.reshape(lanes, B, 2)
        return self._decode_block(state, words, bitlen2d)

    def _tail_fn(self):
        if self._tail_fn_jit is None:
            self._tail_fn_jit = jax.jit(self._scan_body)
        return self._tail_fn_jit

    def _stream_decode(self, codes: jax.Array, bitlen: jax.Array):
        """Single-dispatch expansion decode for stream-scope codecs."""
        if self._stream_decode_fn is None:

            def run(codes, bitlen):
                _, x = self.codec.decode(None, Encoded(codes, bitlen))
                return x

            self._stream_decode_fn = jax.jit(run)
        return self._stream_decode_fn(codes, bitlen)

    # ------------------------------------------------------------ frame prep
    def _split_frame(self, frame: bits.Frame):
        """Frame -> (full-block stacks, per-block extras), device-ready."""
        lanes = frame.lanes
        shapes = frame.block_shapes()
        seg_words = frame.block_words()
        seg_starts = np.concatenate([[0], np.cumsum(seg_words)]).astype(np.int64)
        sym_counts = [L * B for (L, B) in shapes]
        sym_starts = np.concatenate([[0], np.cumsum(sym_counts)]).astype(np.int64)

        def block_arrays(b: int):
            L, B = shapes[b]
            ow = L * B * 2 + 2  # executor's fixed worst-case width
            words = np.zeros(ow, np.uint32)
            seg = frame.payload[seg_starts[b] : seg_starts[b + 1]]
            words[: seg.size] = seg
            bl = frame.bitlen[sym_starts[b] : sym_starts[b + 1]].reshape(L, B)
            return words, bl

        return shapes, block_arrays

    # ------------------------------------------------------------ decompress
    @spans.spanned("collector.decode")
    def decompress(self, frame: bits.Frame, warmup: bool = True) -> DecompressionResult:
        """Reconstruct a frame's stream through the fused chunked executor.

        Decode failures latch the pipeline into quarantine: the first
        :class:`~repro.core.bits.FrameError` is stored on ``quarantined``
        and every later call refuses until :meth:`reset_quarantine` — a
        poisoned session must not silently keep emitting values from a
        stream whose framing it no longer trusts."""
        spans.count("decode.frames")
        self._check_quarantine()
        try:
            return self._decompress(frame, warmup=warmup)
        except bits.FrameError as err:
            self.quarantined = err
            raise
        except Exception as exc:  # corrupt bodies surface as shape/index blowups
            msg = " ".join(str(exc).split())
            err = bits.FrameDecodeError(
                f"frame decode failed ({type(exc).__name__}: {msg}); "
                "discard the frame and resynchronize the stream"
            )
            self.quarantined = err
            raise err from exc

    @spans.spanned("collector.ingest")
    def ingest(self, buf: Union[bytes, bytearray, memoryview]) -> DecompressionResult:
        """Parse raw wire bytes and decode them in one step.

        Parse-stage failures (truncation, CRC mismatch, bad header) latch
        the same quarantine as decode-stage ones, so a collector session
        fed a poisoned byte stream refuses further frames until the caller
        resynchronizes (e.g. via :class:`~repro.core.bits.FrameStream`)."""
        self._check_quarantine()
        try:
            frame = bits.parse_frame(buf)
        except bits.FrameError as err:
            self.quarantined = err
            raise
        spans.count("collector.frames_ingested")
        return self.decompress(frame)

    def reset_quarantine(self) -> None:
        """Clear the poisoned-state latch once the stream is resynchronized."""
        self.quarantined = None

    def _check_quarantine(self) -> None:
        if self.quarantined is not None:
            raise bits.FrameDecodeError(
                f"decoder is quarantined after a poisoned frame ({self.quarantined}); "
                "resynchronize the stream and call reset_quarantine() to resume"
            )

    def _decompress(self, frame: bits.Frame, warmup: bool = True) -> DecompressionResult:
        want = WIRE_CODEC_IDS.get(self.codec.name)
        if frame.codec_id != want:
            raise bits.FrameDecodeError(
                f"frame codec id {frame.codec_id} "
                f"({WIRE_CODEC_NAMES.get(frame.codec_id, '?')}) != pipeline codec "
                f"{self.codec.name!r}"
            )
        lanes = frame.lanes
        shapes, block_arrays = self._split_frame(frame)
        n_full = frame.n_full
        stream_scope = self.codec.meta.scope == "stream"

        # device prep (symmetric with execute's blocks_dev upload): stack the
        # uniform full blocks for the chunked scan, stage the extras
        if n_full:
            full_pairs = [block_arrays(b) for b in range(n_full)]
            full_words = jnp.asarray(np.stack([w for w, _ in full_pairs]))
            full_blens = jnp.asarray(np.stack([bl for _, bl in full_pairs]))
        else:
            full_words = full_blens = None
        extra_blocks = [
            (jnp.asarray(w), jnp.asarray(bl))
            for w, bl in (block_arrays(b) for b in range(n_full, len(shapes)))
        ]

        if warmup:
            # one full untimed pass on first sight of this frame shape: the
            # measured pass then pays compute, not XLA compilation (decode is
            # pure, so running it twice is free of side effects)
            key = (
                tuple(full_words.shape) if full_words is not None else None,
                tuple(bl.shape for _, bl in extra_blocks),
                "decomp",
            )
            if key not in self._warmed:
                self._run_blocks(frame, lanes, full_words, full_blens, extra_blocks, stream_scope)
                self._warmed.add(key)

        t0 = time.perf_counter()
        outs, xs = self._run_blocks(
            frame, lanes, full_words, full_blens, extra_blocks, stream_scope
        )
        wall = time.perf_counter() - t0

        values = self._assemble(frame, shapes, outs, xs)
        return DecompressionResult(values=values, wall_s=wall, n_tuples=frame.n_valid)

    def _initial_state(self, frame: bits.Frame, lanes: int):
        """Decode-side state seeding from the frame's declared dictionary.

        Frames are self-describing: a FEATURE_DICT frame names the exact
        `(topic, version)` its encoder was seeded with, so decode replays
        from the same table regardless of which dictionary (if any) this
        pipeline's codec instance carries. A plain frame from a seeded
        pipeline decodes cold — mixed segments across a hot-swap each get
        the seed their own header declares."""
        did = frame.dict_id
        codec_did = getattr(self.codec, "dict_topic", None)
        if did is None:
            if codec_did is not None:
                return self.codec.cold_state(lanes)
            return self.init_state(lanes)
        if codec_did == did[0] and getattr(self.codec, "dict_version", None) == did[1]:
            return self.init_state(lanes)  # codec already carries this seed
        from repro.core import dictstore

        try:
            trained = dictstore.resolve(did[0], did[1])
        except KeyError as e:
            raise bits.FrameDecodeError(
                f"frame references trained dictionary '{did[0]}:v{did[1]}' "
                f"which this registry cannot resolve ({e.args[0]}); publish it "
                f"or point CSTREAM_DICT_ROOT at the collector's registry"
            ) from e
        if self.codec.meta.state_kind != "dictionary":
            raise bits.FrameDecodeError(
                f"frame references trained dictionary '{trained.ref}' but "
                f"pipeline codec {self.codec.name!r} takes no dictionary"
            )
        if trained.idx_bits != self.codec.idx_bits:
            raise bits.FrameDecodeError(
                f"frame dictionary '{trained.ref}' has idx_bits="
                f"{trained.idx_bits}, decode codec has idx_bits={self.codec.idx_bits}"
            )
        return trained.seed_state(lanes)

    def _run_blocks(self, frame, lanes, full_words, full_blens, extra_blocks, stream_scope):
        """One decode pass over the staged blocks (the timed region)."""
        state = self._initial_state(frame, lanes)
        outs: List[Any] = []  # per-block decoded (L, B) or unpacked codes
        blens: List[Any] = []
        if full_words is not None:
            for start, length in self._chunks(full_words.shape[0]):
                state, ys = self._scan_fn(length)(
                    state,
                    (full_words[start : start + length], full_blens[start : start + length]),
                )
                outs.extend(ys[i] for i in range(length))
                blens.extend(full_blens[start + i] for i in range(length))
        for words, bl in extra_blocks:
            state, y = self._tail_fn()(state, (words, bl))
            outs.append(y)
            blens.append(bl)
        xs = None
        if stream_scope:
            # concatenate every block's symbols per lane (temporal order) and
            # expand in ONE dispatch — symbols may cover tuples of any block
            codes = jnp.concatenate([o.reshape(lanes, -1, 2) for o in outs], axis=1)
            blen = jnp.concatenate(blens, axis=1)
            xs = self._stream_decode(codes, blen)
            jax.block_until_ready(xs)
        else:
            jax.block_until_ready(outs)
        return outs, xs

    def _assemble(
        self, frame: bits.Frame, shapes, outs, stream_vals: Optional[jax.Array]
    ) -> np.ndarray:
        """Trim per-block pads (flat row-major suffix) and re-flatten."""
        n_data = frame.n_full + (1 if frame.tail_per_lane else 0)
        pieces = []
        if stream_vals is not None:
            xs = np.asarray(stream_vals)  # (L, total symbol slots)
            pos = 0
            for b in range(n_data):
                L, B = shapes[b]
                view = xs[:, pos : pos + B]
                pieces.append(view.ravel()[: int(frame.block_valid[b])])
                pos += B
        else:
            for b in range(n_data):
                view = np.asarray(outs[b])
                pieces.append(view.ravel()[: int(frame.block_valid[b])])
        values = (
            np.concatenate(pieces) if pieces else np.zeros(0, np.uint32)
        ).astype(np.uint32)
        return values[: frame.n_valid]
