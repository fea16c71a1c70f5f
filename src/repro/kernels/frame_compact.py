"""Pallas TPU kernels: device-resident frame compaction (DESIGN.md §13).

The egress mirror of kernels/bitpack.py: every scan step emits a fixed
worst-case word buffer (`OW = 2*symbols + 2` uint32) of which only the
`ceil(nbits/32)`-word prefix is live. These kernels turn the stacked
per-block buffers into the two wire-shaped arrays a frame transfers:

  * `compact_blocks` — exclusive-prefix-sum offsets over the per-block used
    word counts, then a compaction of every block's live prefix into one
    contiguous payload. One grid step owns one block and copies its live
    words to its word offset in the payload, which every step shares and
    the first step zeroes — the sequential-grid analogue of the carry-free
    scatter in the jnp formulation (`bits.compact_payload`, the oracle).
  * `pack_meta7_blocks` — per-block 7-bit bitlen packing (`bits.pack_meta7`
    oracle): the per-symbol bit lengths leave the device at their wire
    width (7 bits/symbol) instead of 32. 7-bit fields span at most two
    adjacent words, so the in-block fold ORs a 2-word window per symbol,
    mirroring the bitpack kernel's 3-word fold.

Both move single words to data-dependent offsets, which Mosaic lowers only
for scalar memory: the blocks and the payload live in SMEM and the scalar
core runs the loops, as in kernels/bitpack.py.

As with the other kernels here, the executor's fused scans use the jnp
formulations in `core/bits.py` (XLA fuses them into the scan dispatch); the
Pallas forms are the TPU-kernel mirrors, validated bit-for-bit against the
same oracles in tests/test_kernels.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import bits
from repro.kernels.bitpack import smem_row

_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)  # whole array, every step


def _compact_kernel(nw_ref, off_ref, words_ref, out_ref, *, cap: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():  # the untouched tail beyond total_words must read as zero
        def zero(j, carry):
            out_ref[j] = jnp.uint32(0)
            return carry

        jax.lax.fori_loop(0, cap, zero, 0)

    off = off_ref[i]

    def copy(j, carry):  # this block's live prefix, words_ref (1, OW)
        out_ref[off + j] = words_ref[0, j]
        return carry

    jax.lax.fori_loop(0, nw_ref[i], copy, 0)


def compact_blocks(
    words: jax.Array, nbits: jax.Array, interpret: bool = False
):
    """Compact (n, OW) worst-case word buffers into one contiguous payload.

    Returns (payload[(n*OW,)] uint32, total_words int32): the `total_words`
    prefix is the wire payload (block b at word offset `sum_{j<b}
    ceil(nbits[j]/32)`), the rest zeros.
    """
    n, ow = words.shape
    nw, offs = bits.block_word_counts(nbits)
    cap = n * ow
    kernel = functools.partial(_compact_kernel, cap=cap)
    payload = pl.pallas_call(
        kernel,
        grid=(n,),
        in_specs=[_SMEM, _SMEM, smem_row(ow)],
        out_specs=_SMEM,
        out_shape=jax.ShapeDtypeStruct((cap,), jnp.uint32),
        interpret=interpret,
    )(nw, offs, words.reshape(n, 1, ow))
    return payload, jnp.sum(nw).astype(jnp.int32)


def _meta7_kernel(blen_ref, out_ref, *, symbols: int, mw: int):
    # blen_ref (1, symbols); out_ref (1, mw)
    def zero(j, carry):
        out_ref[0, j] = jnp.uint32(0)
        return carry

    jax.lax.fori_loop(0, mw, zero, 0)

    def body(i, carry):
        off = 7 * i
        w = off // 32
        s = off % 32
        v = blen_ref[0, i].astype(jnp.uint32) & jnp.uint32(0x7F)
        out_ref[0, w] = out_ref[0, w] | bits._safe_lshift(v, s)

        @pl.when(w + 1 < mw)
        def _spill():  # the field's high bits (0 when it fits in word w)
            out_ref[0, w + 1] = out_ref[0, w + 1] | bits._safe_rshift(v, 32 - s)

        return carry

    jax.lax.fori_loop(0, symbols, body, 0)


def pack_meta7_blocks(bitlen: jax.Array, interpret: bool = False) -> jax.Array:
    """Pack (n, S) per-block bitlens at 7 bits/symbol into (n, ceil(7S/32))
    uint32 words. When S % 32 == 0 the rows concatenate into the frame's
    global metadata stream with no re-alignment (each block starts
    word-aligned at 7S/32 words)."""
    n, symbols = bitlen.shape
    mw = (7 * symbols + 31) // 32
    kernel = functools.partial(_meta7_kernel, symbols=symbols, mw=mw)
    packed = pl.pallas_call(
        kernel,
        grid=(n,),
        in_specs=[smem_row(symbols)],
        out_specs=smem_row(mw),
        out_shape=jax.ShapeDtypeStruct((n, 1, mw), jnp.uint32),
        interpret=interpret,
    )(bitlen.reshape(n, 1, symbols))
    return packed.reshape(n, mw)
