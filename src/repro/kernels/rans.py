"""Pallas TPU kernels: interleaved rANS entropy stage (DESIGN.md §15).

Kernel mirrors of the one-chunk scans in `core/entropy.py` (the oracles —
`encode_rows`/`decode_rows` there are what the production jit path runs;
these are the TPU-kernel forms, validated bit-for-bit in
tests/test_kernels.py):

  * `encode_rows` — grid-sequential over the chunk's (T, N_LANES) byte
    grid in REVERSE row order (rANS encodes backwards so decode runs
    forward). The 8 lane states live in an output ref with a constant
    index map (the frame_compact.py carry idiom); each step emits at most
    one u16 per lane, recorded as (flag, value) at the ORIGINAL row index
    so the caller's exclusive cumsum turns flags into stream positions.
  * `decode_rows` — forward grid; carries lane states AND the decoupled
    read pointers (each lane's absolute index into the shared u16 stream)
    in constant-index-map refs, so all lanes start in parallel from the
    offset stream with no sequential carry between lanes.

Frequency/cumulative/slot tables and the per-lane stream reads are all
one-hot broadcast-compare folds (vector-unit friendly): Mosaic lowers
neither a dynamic gather nor a dynamic slice of a vector value.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.entropy import N_LANES, PROB_BITS, PROB_SCALE, RANS_L


def _lookup(table: jax.Array, idx: jax.Array, width: int) -> jax.Array:
    """One-hot gather: table (width,), idx (N,) int32 -> (N,) table dtype.
    The fold sums in int32 (Mosaic reduces no unsigned type); exactly one
    term per row is nonzero, so the sum is the entry bit for bit."""
    onehot = (
        jax.lax.broadcasted_iota(jnp.int32, (idx.shape[0], width), 1)
        == idx[:, None]
    )
    t32 = jax.lax.bitcast_convert_type(table, jnp.int32)
    got = jnp.sum(jnp.where(onehot, t32[None, :], 0), axis=1)
    return jax.lax.bitcast_convert_type(got, table.dtype)


def _enc_kernel(syms_ref, mask_ref, fr_ref, cum_ref, state_ref, flags_ref, vals_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        state_ref[...] = jnp.full((N_LANES,), RANS_L, jnp.uint32)

    x = state_ref[...]
    s = syms_ref[...].reshape(-1).astype(jnp.int32)
    m = mask_ref[...].reshape(-1) > 0
    f = _lookup(fr_ref[...], s, 256)
    c = _lookup(cum_ref[...], s, 256)
    f_safe = jnp.where(m & (f > 0), f, jnp.uint32(1))
    # renorm: x >= f·2^20, spelled shift-wise so f = PROB_SCALE cannot wrap
    emit = m & ((x >> jnp.uint32(20)) >= f_safe)
    val = x & jnp.uint32(0xFFFF)
    x1 = jnp.where(emit, x >> jnp.uint32(16), x)
    x2 = ((x1 // f_safe) << jnp.uint32(PROB_BITS)) + (x1 % f_safe) + c
    state_ref[...] = jnp.where(m, x2, x)
    flags_ref[...] = emit.astype(jnp.int32)[None, :]
    vals_ref[...] = jnp.where(emit, val, jnp.uint32(0))[None, :]


def encode_rows(
    syms: jax.Array, mask: jax.Array, freqs: jax.Array, interpret: bool = False
):
    """rANS-encode one chunk's (T, N_LANES) byte grid.

    Returns `(states uint32[N], flags int32[T, N], vals uint32[T, N])`,
    exactly as `core.entropy.encode_rows` (the oracle)."""
    t_rows = syms.shape[0]
    fr = freqs.astype(jnp.uint32)
    fi = freqs.astype(jnp.int32)
    cum = (jnp.cumsum(fi) - fi).astype(jnp.uint32)
    if t_rows == 0:
        return (
            jnp.full((N_LANES,), RANS_L, jnp.uint32),
            jnp.zeros((0, N_LANES), jnp.int32),
            jnp.zeros((0, N_LANES), jnp.uint32),
        )
    rev = _row_spec(lambda i: t_rows - 1 - i)  # rANS encodes backwards
    states, flags, vals = pl.pallas_call(
        _enc_kernel,
        grid=(t_rows,),
        in_specs=[
            rev,
            rev,
            pl.BlockSpec((256,), lambda i: (0,)),
            pl.BlockSpec((256,), lambda i: (0,)),
        ],
        out_specs=[pl.BlockSpec((N_LANES,), lambda i: (0,)), rev, rev],
        out_shape=[
            jax.ShapeDtypeStruct((N_LANES,), jnp.uint32),
            jax.ShapeDtypeStruct((t_rows, 1, N_LANES), jnp.int32),
            jax.ShapeDtypeStruct((t_rows, 1, N_LANES), jnp.uint32),
        ],
        interpret=interpret,
    )(_rows(syms), _rows(mask), fr, cum)
    return states, flags.reshape(t_rows, N_LANES), vals.reshape(t_rows, N_LANES)


def _row_spec(row) -> pl.BlockSpec:
    """One (1, N_LANES) row of a (T, 1, N_LANES) grid per step: the block's
    last two dims equal the array's, which is what Mosaic's (8, 128)
    tiling rule accepts for a block this narrow."""
    return pl.BlockSpec((None, 1, N_LANES), lambda i: (row(i), 0, 0))


def _rows(grid: jax.Array) -> jax.Array:
    """(T, N_LANES) -> int32 (T, 1, N_LANES), the layout `_row_spec` tiles."""
    return grid.astype(jnp.int32)[:, None, :]


def _dec_kernel(
    stream_ref, fr_ref, cum_ref, lut_ref, x0_ref, p0_ref, mask_ref,
    syms_ref, x_ref, p_ref,
):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        x_ref[...] = x0_ref[...]
        p_ref[...] = p0_ref[...]

    x = x_ref[...]
    p = p_ref[...]
    m = mask_ref[...].reshape(-1) > 0
    slot = x & jnp.uint32(PROB_SCALE - 1)
    sym = _lookup(lut_ref[...], slot.astype(jnp.int32), PROB_SCALE)
    f = _lookup(fr_ref[...], sym, 256)
    c = _lookup(cum_ref[...], sym, 256)
    x2 = f * (x >> jnp.uint32(PROB_BITS)) + slot - c
    need = m & (x2 < jnp.uint32(RANS_L))
    stream = stream_ref[...]
    cap = stream.shape[0]
    pc = jnp.clip(p, 0, cap - 1)
    w = _lookup(stream, pc, cap)
    x3 = jnp.where(need, (x2 << jnp.uint32(16)) | w, x2)
    x_ref[...] = jnp.where(m, x3, x)
    p_ref[...] = p + need.astype(jnp.int32)
    syms_ref[...] = jnp.where(m, sym.astype(jnp.uint32), jnp.uint32(0))[None, :]


def decode_rows(
    stream: jax.Array,
    freqs: jax.Array,
    states: jax.Array,
    offsets: jax.Array,
    mask: jax.Array,
    interpret: bool = False,
) -> jax.Array:
    """Forward-decode one chunk to its (T, N_LANES) byte grid.

    `offsets` are each lane's absolute start index into `stream` — the
    decoupled offset stream; mirrors `core.entropy.decode_rows`."""
    t_rows = mask.shape[0]
    fi = freqs.astype(jnp.int32)
    fr = freqs.astype(jnp.uint32)
    cum = (jnp.cumsum(fi) - fi).astype(jnp.uint32)
    slots = jnp.arange(PROB_SCALE, dtype=jnp.int32)
    cum_i = jnp.cumsum(fi) - fi
    lut = (jnp.searchsorted(cum_i, slots, side="right") - 1).astype(jnp.int32)
    if t_rows == 0:
        return jnp.zeros((0, N_LANES), jnp.uint32)
    cap = stream.shape[0]
    syms, _, _ = pl.pallas_call(
        _dec_kernel,
        grid=(t_rows,),
        in_specs=[
            pl.BlockSpec((cap,), lambda i: (0,)),
            pl.BlockSpec((256,), lambda i: (0,)),
            pl.BlockSpec((256,), lambda i: (0,)),
            pl.BlockSpec((PROB_SCALE,), lambda i: (0,)),
            pl.BlockSpec((N_LANES,), lambda i: (0,)),
            pl.BlockSpec((N_LANES,), lambda i: (0,)),
            _row_spec(lambda i: i),
        ],
        out_specs=[
            _row_spec(lambda i: i),
            pl.BlockSpec((N_LANES,), lambda i: (0,)),
            pl.BlockSpec((N_LANES,), lambda i: (0,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t_rows, 1, N_LANES), jnp.uint32),
            jax.ShapeDtypeStruct((N_LANES,), jnp.uint32),
            jax.ShapeDtypeStruct((N_LANES,), jnp.int32),
        ],
        interpret=interpret,
    )(
        stream.astype(jnp.uint32),
        fr,
        cum,
        lut,
        states.astype(jnp.uint32),
        offsets.astype(jnp.int32),
        _rows(mask),
    )
    return syms.reshape(t_rows, N_LANES)
