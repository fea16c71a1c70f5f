"""Optional second compression stage: interleaved rANS over frame bytes.

EDPC-style entropy coding (PAPERS.md) composed behind the frame wire
format (DESIGN.md §15): the compacted payload and the 7-bit-packed bitlen
metadata are both still byte-skewed after stage 1, so an optional rANS
pass over each section recovers the residual entropy. The dataflow is
designed for parallel decode from the start:

  * the byte stream splits into fixed-size CHUNK_BYTES chunks, each
    encoded by N_LANES interleaved rANS coders (lane j owns bytes
    j, j+N, j+2N, ... of its chunk);
  * every (chunk, lane) stream's u16 word count travels with the frame,
    so the decoder derives all stream offsets with one exclusive cumsum —
    the decoupled offset stream that lets every decoder lane start in
    parallel with no sequential carry (the decode-side twin of the
    offset dataflow `bits.compact_payload` uses on the encode side);
  * one frequency table per section, quantized to a fixed 2^PROB_BITS
    denominator on device from a histogram pass.

State math (32-bit state, 16-bit renormalization, 12-bit probabilities):
the state x keeps the invariant x in [RANS_L, 2^32). The encoder — which
walks its symbols in REVERSE so the decoder runs forward — emits the low
16 bits exactly when `(x >> 20) >= f` (the overflow-safe spelling of
x >= f·2^20; at most one emission per step, so the scan stays
fixed-shape), then maps x -> (x/f)·2^12 + x mod f + cum. The decoder
reads the slot `x & 0xFFF`, looks the symbol up in the slot table,
inverts the map, and refills 16 bits when x drops below RANS_L (at most
one read per step: after the symbol step x >= f >= 1, and one refill
reaches >= 2^16). A symbol with quantized frequency 2^PROB_BITS never
emits, so constant streams cost only the table.

Every section carries a raw-fallback flag: when the encoded form (table
+ per-chunk states/counts + stream) is not smaller than the raw section,
the raw words ship verbatim — entropy coding never inflates a frame by
more than the few flag words.

This module is deliberately standalone (jax/numpy only) so `core.bits`
can import it for frame (de)serialization without a cycle; the Pallas
kernel mirrors live in `kernels/rans.py` with these scans as oracles.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

PROB_BITS = 12
PROB_SCALE = 1 << PROB_BITS  # 4096: fixed table denominator
RANS_L = 1 << 16  # lower bound of the state interval (16-bit renorm)
N_LANES = 8  # interleaved coders per chunk
CHUNK_BYTES = 4096  # bytes per independently-decodable chunk
ROWS = CHUNK_BYTES // N_LANES  # scan steps per chunk
ENTROPY_KIND_RANS = 1  # blob kind word

_U32 = jnp.uint32
_SCAN_UNROLL = 8


def _next_pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length() if n > 1 else 1


# ------------------------------------------------------------------ tables --
def quantize_freqs(hist: jax.Array) -> jax.Array:
    """Quantize a 256-bin byte histogram to frequencies summing to 2^12.

    Every present symbol (hist > 0) gets frequency >= 1 and the sum is
    exactly PROB_SCALE. All int32 math: counts are first downscaled below
    2^17 so `count * budget` stays under 2^30."""
    hist = hist.astype(jnp.int32)
    total = jnp.sum(hist)
    # integer bit length of the total (no float log2: exactness matters)
    v = total.astype(_U32)
    nbits = jnp.zeros((), jnp.int32)
    for shift in (16, 8, 4, 2, 1):
        big = v >= (np.uint32(1) << shift)
        nbits = jnp.where(big, nbits + shift, nbits)
        v = jnp.where(big, v >> shift, v)
    nbits = nbits + (v > 0).astype(jnp.int32)
    down = jnp.maximum(nbits - 17, 0).astype(_U32)
    scaled = jnp.where(hist > 0, jnp.maximum(hist >> down, 1), 0)
    t2 = jnp.maximum(jnp.sum(scaled), 1)
    npresent = jnp.sum((hist > 0).astype(jnp.int32))
    budget = PROB_SCALE - npresent  # >= 4096 - 256 > 0
    q = (scaled * budget) // t2 + (scaled > 0).astype(jnp.int32)
    # floor division under-allocates by at most `npresent`; hand the
    # remainder to the most probable symbol so the sum is exact
    q = q.at[jnp.argmax(q)].add(PROB_SCALE - jnp.sum(q))
    return q


def _histogram(syms: jax.Array, mask: jax.Array) -> jax.Array:
    idx = jnp.where(mask, syms, 0).astype(jnp.int32)
    return jnp.zeros(256, jnp.int32).at[idx].add(mask.astype(jnp.int32))


def _cum_freqs(freqs: jax.Array) -> jax.Array:
    f = freqs.astype(jnp.int32)
    return (jnp.cumsum(f) - f).astype(_U32)


def slot_table(freqs: jax.Array) -> jax.Array:
    """slot -> symbol lookup (int32[PROB_SCALE]) from the frequency table."""
    cum = jnp.cumsum(freqs.astype(jnp.int32)) - freqs.astype(jnp.int32)
    slots = jnp.arange(PROB_SCALE, dtype=jnp.int32)
    return (jnp.searchsorted(cum, slots, side="right") - 1).astype(jnp.int32)


# ------------------------------------------------------- one-chunk scans --
def encode_rows(
    syms: jax.Array, mask: jax.Array, freqs: jax.Array
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Interleaved rANS encode of one chunk's (T, N_LANES) byte grid.

    `syms` uint32 byte values, `mask` marks real bytes (masked steps are
    identity: no state change, no emission). Returns `(states, flags,
    vals)`: final lane states uint32[N_LANES], and per-row emission flags
    int32[T, N] / u16 values uint32[T, N] indexed by ORIGINAL row — the
    exclusive cumsum of `flags` down the rows is each emission's position
    in its lane's stream, already in decoder read order."""
    fr = freqs.astype(_U32)
    cum = _cum_freqs(freqs)

    def step(x, inp):
        s, m = inp
        s = s.astype(jnp.int32)
        f = fr[s]
        c = cum[s]
        f_safe = jnp.where(m & (f > 0), f, np.uint32(1))
        # renorm: x >= f·2^20 spelled overflow-safely (f·2^20 has zero
        # low bits, and f << 20 would wrap for f = PROB_SCALE)
        emit = m & ((x >> np.uint32(20)) >= f_safe)
        val = x & np.uint32(0xFFFF)
        x1 = jnp.where(emit, x >> np.uint32(16), x)
        x2 = ((x1 // f_safe) << np.uint32(PROB_BITS)) + (x1 % f_safe) + c
        x_new = jnp.where(m, x2, x)
        return x_new, (emit.astype(jnp.int32), jnp.where(emit, val, np.uint32(0)))

    init = jnp.full((N_LANES,), RANS_L, _U32)
    # encode in reverse row order so the decoder scans forward
    states, (flags_r, vals_r) = jax.lax.scan(
        step, init, (syms[::-1], mask[::-1]), unroll=_SCAN_UNROLL
    )
    return states, flags_r[::-1], vals_r[::-1]


def decode_rows(
    stream: jax.Array,
    freqs: jax.Array,
    states: jax.Array,
    offsets: jax.Array,
    mask: jax.Array,
    lut: jax.Array,
) -> jax.Array:
    """Forward decode of one chunk: (T, N_LANES) byte grid from the u16
    stream. `offsets` are each lane's ABSOLUTE start index into `stream`
    (the decoupled offset stream) — all lanes start in parallel. `lut` is
    `slot_table(freqs)`."""
    fr = freqs.astype(_U32)
    cum = _cum_freqs(freqs)
    cap = stream.shape[0]

    def step(carry, m):
        x, p = carry
        slot = x & np.uint32(PROB_SCALE - 1)
        sym = lut[slot.astype(jnp.int32)]
        x2 = fr[sym] * (x >> np.uint32(PROB_BITS)) + slot - cum[sym]
        need = m & (x2 < np.uint32(RANS_L))
        w = stream[jnp.clip(p, 0, cap - 1)]
        x3 = jnp.where(need, (x2 << np.uint32(16)) | w.astype(_U32), x2)
        x_new = jnp.where(m, x3, x)
        p_new = p + need.astype(jnp.int32)
        out = jnp.where(m, sym.astype(_U32), np.uint32(0))
        return (x_new, p_new), out

    init = (states.astype(_U32), offsets.astype(jnp.int32))
    _, syms = jax.lax.scan(step, init, mask, unroll=_SCAN_UNROLL)
    return syms


# ----------------------------------------------------- section (de)coders --
@functools.partial(jax.jit, static_argnames=("cp",))
def _encode_device(data: jax.Array, n: jax.Array, cp: int):
    """Encode `cp` chunks of padded byte data (uint32[cp*CHUNK_BYTES],
    values < 256; bytes at index >= n are padding). One frequency table
    over all real bytes; chunks encode under vmap; emissions scatter into
    one stream in (chunk, lane) order."""
    idx = jnp.arange(cp * CHUNK_BYTES, dtype=jnp.int32)
    mask_flat = idx < n
    freqs = quantize_freqs(_histogram(data, mask_flat))
    syms = data.reshape(cp, ROWS, N_LANES)
    mask = mask_flat.reshape(cp, ROWS, N_LANES)
    states, flags, vals = jax.vmap(lambda s, m: encode_rows(s, m, freqs))(
        syms, mask
    )
    counts = flags.sum(axis=1)  # (cp, N) u16s per lane stream
    cflat = counts.reshape(-1)
    off = (jnp.cumsum(cflat) - cflat).reshape(cp, N_LANES)
    rank = jnp.cumsum(flags, axis=1) - flags  # emission index within lane
    cap = cp * CHUNK_BYTES
    pos = jnp.where(flags > 0, off[:, None, :] + rank, cap)
    stream = (
        jnp.zeros(cap, _U32).at[pos.reshape(-1)].add(vals.reshape(-1), mode="drop")
    )
    return freqs, states, counts, stream, jnp.sum(cflat)


@functools.partial(jax.jit, static_argnames=("cp",))
def _decode_device(
    stream: jax.Array,
    freqs: jax.Array,
    states: jax.Array,
    counts: jax.Array,
    n: jax.Array,
    cp: int,
):
    lut = slot_table(freqs)
    cflat = counts.reshape(-1).astype(jnp.int32)
    off = (jnp.cumsum(cflat) - cflat).reshape(cp, N_LANES)
    idx = jnp.arange(cp * CHUNK_BYTES, dtype=jnp.int32)
    mask = (idx < n).reshape(cp, ROWS, N_LANES)
    syms = jax.vmap(
        lambda x0, p0, m: decode_rows(stream, freqs, x0, p0, m, lut)
    )(states, off, mask)
    return syms.reshape(-1)


def _words_to_bytes(words: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(words, np.uint32).astype("<u4").view(np.uint8)


def _bytes_to_words(b: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(b, np.uint8).view("<u4").astype(np.uint32)


def _pack_u16(vals: np.ndarray) -> np.ndarray:
    """Pack u16 values (held in uint32) two per word, little halves first."""
    v = np.ascontiguousarray(vals, np.uint32)
    if v.size % 2:
        v = np.concatenate([v, np.zeros(1, np.uint32)])
    return (v[0::2] | (v[1::2] << np.uint32(16))).astype(np.uint32)

def _unpack_u16(words: np.ndarray, n: int) -> np.ndarray:
    w = np.ascontiguousarray(words, np.uint32)
    out = np.empty(2 * w.size, np.uint32)
    out[0::2] = w & np.uint32(0xFFFF)
    out[1::2] = w >> np.uint32(16)
    return out[:n]


def encode_section(raw_words: np.ndarray) -> np.ndarray:
    """Serialize one frame section (uint32 words) with the rANS stage.

    Returns the self-describing section words: `[1, n_u16, n_chunks]` +
    128-word table (256 x 16-bit freqs) + per-chunk lane states + packed
    per-chunk lane counts + packed u16 stream — or `[0]` + the raw words
    verbatim when encoding would not shrink the section."""
    raw_words = np.ascontiguousarray(raw_words, np.uint32)
    raw = np.concatenate([np.zeros(1, np.uint32), raw_words])
    n = 4 * raw_words.size
    if n == 0:
        return raw
    data = _words_to_bytes(raw_words)
    nchunks = -(-n // CHUNK_BYTES)
    cp = _next_pow2(nchunks)
    padded = np.zeros(cp * CHUNK_BYTES, np.uint32)
    padded[:n] = data
    freqs, states, counts, stream, total = _encode_device(
        jnp.asarray(padded), jnp.int32(n), cp
    )
    total = int(total)
    # padding chunks past `nchunks` are fully masked: zero counts, states
    # still RANS_L — they carry no stream words and are dropped here. The
    # arrays are fetched whole and cut on the host: a device slice of
    # data-dependent length would compile one program per length
    states_np = np.asarray(states, np.uint32)[:nchunks].reshape(-1)
    counts_np = np.asarray(counts, np.uint32)[:nchunks].reshape(-1)
    table = _pack_u16(np.asarray(freqs, np.uint32))
    enc = np.concatenate(
        [
            np.array([ENTROPY_KIND_RANS, total, nchunks], np.uint32),
            table,
            states_np,
            _pack_u16(counts_np),
            _pack_u16(np.asarray(stream, np.uint32)[:total]),
        ]
    )
    return enc if enc.size < raw.size else raw


def decode_section(section: np.ndarray, raw_word_count: int) -> Tuple[np.ndarray, int]:
    """Inverse of `encode_section`. `raw_word_count` is the section's raw
    size, recomputed by the caller from the frame header (it never travels
    in the blob). Returns `(raw_words, section_words_consumed)`."""
    section = np.ascontiguousarray(section, np.uint32)
    if section.size < 1:
        raise ValueError("frame entropy section truncated (missing flag word)")
    flag = int(section[0])
    if flag == 0:
        if section.size < 1 + raw_word_count:
            raise ValueError("frame entropy section truncated (raw fallback)")
        return section[1 : 1 + raw_word_count].copy(), 1 + raw_word_count
    if flag != ENTROPY_KIND_RANS:
        raise ValueError(f"frame entropy section has unknown coder kind {flag}")
    if section.size < 3:
        raise ValueError("frame entropy section truncated (missing counts)")
    total, nchunks = int(section[1]), int(section[2])
    expect = -(-(4 * raw_word_count) // CHUNK_BYTES)
    if nchunks != expect:
        raise ValueError(
            f"frame entropy section inconsistent: {nchunks} chunks for "
            f"{raw_word_count} raw words (expected {expect})"
        )
    stream_words = -(-total // 2)
    p = 3
    end = p + 128 + 8 * nchunks + 4 * nchunks + stream_words
    if section.size < end:
        raise ValueError("frame entropy section truncated (stream)")
    freqs = _unpack_u16(section[p : p + 128], 256).astype(np.int32)
    p += 128
    if int(freqs.sum()) != PROB_SCALE:
        raise ValueError(
            "frame entropy section invalid: frequency table does not sum "
            f"to {PROB_SCALE}"
        )
    states = section[p : p + 8 * nchunks].reshape(nchunks, N_LANES)
    p += 8 * nchunks
    counts = _unpack_u16(section[p : p + 4 * nchunks], 8 * nchunks).reshape(
        nchunks, N_LANES
    )
    p += 4 * nchunks
    stream = _unpack_u16(section[p : p + stream_words], total)
    p += stream_words
    if int(counts.sum()) != total:
        raise ValueError(
            "frame entropy section inconsistent: lane counts vs stream size"
        )
    n = 4 * raw_word_count
    cp = _next_pow2(nchunks)
    states_pad = np.full((cp, N_LANES), RANS_L, np.uint32)
    states_pad[:nchunks] = states
    counts_pad = np.zeros((cp, N_LANES), np.uint32)
    counts_pad[:nchunks] = counts
    stream_pad = np.zeros(cp * CHUNK_BYTES, np.uint32)
    stream_pad[:total] = stream
    syms = _decode_device(
        jnp.asarray(stream_pad),
        jnp.asarray(freqs),
        jnp.asarray(states_pad),
        jnp.asarray(counts_pad),
        jnp.int32(n),
        cp,
    )
    data = np.asarray(syms, np.uint32)[:n].astype(np.uint8)
    return _bytes_to_words(data), p


# ------------------------------------------------------------- frame blob --
def encode_blob(packed_meta: np.ndarray, payload: np.ndarray) -> np.ndarray:
    """Entropy-code a frame's two sections into one self-describing blob:
    `[kind, n_lanes]` + encoded metadata section + encoded payload
    section. Section raw sizes are NOT stored — the decoder recomputes
    them from the frame header."""
    return np.concatenate(
        [
            np.array([ENTROPY_KIND_RANS, N_LANES], np.uint32),
            encode_section(packed_meta),
            encode_section(payload),
        ]
    )


def decode_blob(
    blob: np.ndarray, meta_words: int, payload_words: int
) -> Tuple[np.ndarray, np.ndarray]:
    """Inverse of `encode_blob`: returns `(packed_meta, payload)`."""
    blob = np.ascontiguousarray(blob, np.uint32)
    if blob.size < 2:
        raise ValueError("frame entropy blob truncated (missing kind header)")
    if int(blob[0]) != ENTROPY_KIND_RANS or int(blob[1]) != N_LANES:
        raise ValueError(
            f"frame entropy blob has unsupported coder kind {int(blob[0])} "
            f"/ {int(blob[1])} lanes (this build: kind {ENTROPY_KIND_RANS}, "
            f"{N_LANES} lanes)"
        )
    meta, used = decode_section(blob[2:], meta_words)
    payload, used2 = decode_section(blob[2 + used :], payload_words)
    if 2 + used + used2 != blob.size:
        raise ValueError(
            f"frame entropy blob length mismatch: {blob.size} words, "
            f"sections consumed {2 + used + used2}"
        )
    return meta, payload
