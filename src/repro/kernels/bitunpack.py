"""Pallas TPU kernel: block-local bitstream unpacking (decode-side mirror of
kernels/bitpack.py).

Same block design as the packer: each grid step owns one block of
symbols whose working set (the packed word segment + per-symbol bitlens +
the reconstructed codes) lives entirely in on-chip memory, and blocks start
word-aligned so grid steps are independent — the decode side of the paper's
cache-aware micro-batching, and the kernel form of EDPC's decoupled decode
dataflow: because the per-symbol bitlens travel as frame metadata, no grid
step ever parses a prefix to find its symbols.

Within a block the bit offsets are an exclusive scan of the bitlens
(`lax.fori_loop` carry, mirroring the packer's fold); each symbol then
gathers its 3-word window and shifts/masks the <=64-bit code back out. As
in the packer, the block lives in SMEM and the scalar core runs the fold:
the window reads sit at data-dependent word offsets.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import bits
from repro.kernels.bitpack import smem_row

DEFAULT_BLOCK = 256


def _words_per_block(block: int) -> int:
    return 2 * block + 1  # worst case: 64 bits/symbol + spill word


def _unpack_kernel(words_ref, blen_ref, codes_ref, *, block: int):
    # words_ref (1, wpb); blen_ref (1, block); codes_ref (1, 2*block)
    wpb = _words_per_block(block)

    def word(j):  # zero past the block's end: the spill guard
        return jnp.where(j < wpb, words_ref[0, jnp.minimum(j, wpb - 1)], jnp.uint32(0))

    def body(i, off):
        n = blen_ref[0, i]
        w = off // 32
        s = off % 32
        # the 3-word window covering any <=64-bit code at offset s
        g0, g1, g2 = word(w), word(w + 1), word(w + 2)
        r = 32 - s
        lo = bits._safe_rshift(g0, s) | bits._safe_lshift(g1, r)
        hi = bits._safe_rshift(g1, s) | bits._safe_lshift(g2, r)
        codes_ref[0, 2 * i] = lo & bits.mask_bits(jnp.minimum(n, 32))
        codes_ref[0, 2 * i + 1] = hi & bits.mask_bits(jnp.maximum(n - 32, 0))
        return off + n

    jax.lax.fori_loop(0, block, body, jnp.int32(0))


def unpack_blocks(words: jax.Array, bitlen: jax.Array, block: int = DEFAULT_BLOCK, interpret: bool = False):
    """Unpack per-block bitstreams back into (N, 2) uint32 codes.

    Args:
      words: uint32[nblocks, words_per_block] — per-block packed streams
        (the layout `kernels/bitpack.py:pack_blocks` emits).
      bitlen: int32[N] — per-symbol bit lengths, N = nblocks * block.

    Returns:
      codes: uint32[N, 2] — low/high words of each symbol (0 for 0-bit slots).
    """
    nblocks, wpb = words.shape
    assert wpb == _words_per_block(block), f"words width {wpb} != {_words_per_block(block)}"
    assert bitlen.shape[0] == nblocks * block, (bitlen.shape, nblocks, block)
    kernel = functools.partial(_unpack_kernel, block=block)
    codes = pl.pallas_call(
        kernel,
        grid=(nblocks,),
        in_specs=[smem_row(wpb), smem_row(block)],
        out_specs=smem_row(2 * block),
        out_shape=jax.ShapeDtypeStruct((nblocks, 1, 2 * block), jnp.uint32),
        interpret=interpret,
    )(words.reshape(nblocks, 1, wpb), bitlen.reshape(nblocks, 1, block))
    return codes.reshape(nblocks * block, 2)
