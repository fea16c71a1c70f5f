"""Pallas TPU kernel: block-local bitstream packing.

TPU adaptation of the paper's cache-aware micro-batching (Fig 11): each grid
step owns one block of symbols whose working set (codes + bitlens + the
accumulated bitstream) lives entirely in on-chip memory — the analogue of
the paper's L1D-resident micro-batch. Blocks start word-aligned (standard in
parallel compressors), so grid steps are independent and the grid maps onto
all cores/chips with zero cross-block carries.

Within a block the symbols are folded sequentially (`lax.fori_loop`) into
the block's word buffer using the 3-word shift decomposition of a <=64-bit
code. The fold reads and writes single words at data-dependent offsets,
which Mosaic lowers only for scalar memory, so the block lives in SMEM and
the scalar core runs the fold; each block is passed as a (1, width) row of
a (nblocks, 1, width) array, whose last two dims equal the block's as
Mosaic's tiling rule requires.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import bits

DEFAULT_BLOCK = 256


def _words_per_block(block: int) -> int:
    return 2 * block + 1  # worst case: 64 bits/symbol + spill word


def _pack_kernel(codes_ref, blen_ref, words_ref, nbits_ref, *, block: int):
    # codes_ref (1, 2*block) interleaved lo/hi words; blen_ref (1, block)
    wpb = _words_per_block(block)

    def zero(j, carry):
        words_ref[0, j] = jnp.uint32(0)
        return carry

    jax.lax.fori_loop(0, wpb, zero, 0)

    def body(i, off):
        n = blen_ref[0, i]
        c0 = codes_ref[0, 2 * i] & bits.mask_bits(jnp.minimum(n, 32))
        c1 = codes_ref[0, 2 * i + 1] & bits.mask_bits(jnp.maximum(n - 32, 0))
        w = off // 32
        lo, mid, hi = bits.code64_shift(c0, c1, off % 32)
        words_ref[0, w] = words_ref[0, w] | lo

        # a code ending in the block's last word spills nothing past it
        @pl.when(w + 1 < wpb)
        def _mid():
            words_ref[0, w + 1] = words_ref[0, w + 1] | mid

        @pl.when(w + 2 < wpb)
        def _hi():
            words_ref[0, w + 2] = words_ref[0, w + 2] | hi

        return off + n

    nbits_ref[0, 0] = jax.lax.fori_loop(0, block, body, jnp.int32(0))


def pack_blocks(codes: jax.Array, bitlen: jax.Array, block: int = DEFAULT_BLOCK, interpret: bool = False):
    """Pack (N, 2) uint32 codes with (N,) bitlens into per-block bitstreams.

    Returns (words[(nblocks, words_per_block)] uint32, nbits[(nblocks,)] int32).
    """
    n = codes.shape[0]
    assert n % block == 0, f"N={n} must be a multiple of block={block}"
    nblocks = n // block
    wpb = _words_per_block(block)
    kernel = functools.partial(_pack_kernel, block=block)
    words, nbits = pl.pallas_call(
        kernel,
        grid=(nblocks,),
        in_specs=[smem_row(2 * block), smem_row(block)],
        out_specs=[smem_row(wpb), smem_row(1)],
        out_shape=[
            jax.ShapeDtypeStruct((nblocks, 1, wpb), jnp.uint32),
            jax.ShapeDtypeStruct((nblocks, 1, 1), jnp.int32),
        ],
        interpret=interpret,
    )(codes.reshape(nblocks, 1, 2 * block), bitlen.reshape(nblocks, 1, block))
    return words.reshape(nblocks, wpb), nbits.reshape(nblocks)


def smem_row(width: int) -> pl.BlockSpec:
    """Grid step i's (1, width) row of a (nblocks, 1, width) array, in SMEM."""
    return pl.BlockSpec(
        (None, 1, width), lambda i: (i, 0, 0), memory_space=pltpu.SMEM
    )
