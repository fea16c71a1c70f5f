"""Pallas TPU kernel: Tdic32 hash-dictionary probe (frozen-table mode).

The dictionary (4096 x 4B = 16 KiB) is resident in on-chip memory for every
grid step — the paper sizes it for L1 [29]. The probe is a data-dependent
table lookup, which Mosaic lowers only as a scalar read, so the table, its
valid bits and each block of values live in SMEM and the scalar core runs
hash, lookup, compare and symbol materialization per value. Table *updates*
are merged once per micro-batch outside the kernel (deterministic
last-writer-wins, see core/algorithms/dictionary.py), which is what makes
the probe side embarrassingly parallel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.bitpack import smem_row

KNUTH = 2654435761  # Knuth multiplicative hash constant (python int: Pallas
DEFAULT_BLOCK = 512  # kernels must not capture traced jnp constants)


def hash_host(values: np.ndarray, idx_bits: int = 12) -> np.ndarray:
    """Host-side twin of the kernel's slot hash (training / table fills).

    Must stay bit-identical to ``_probe_kernel``'s ``h`` so tables built
    offline land in the slots the device probe reads.
    """
    v = np.asarray(values, dtype=np.uint32)
    return ((v * np.uint32(KNUTH)) >> np.uint32(32 - idx_bits)).astype(np.int64)


def _probe_kernel(
    x_ref, table_ref, valid_ref, c0_ref, c1_ref, blen_ref, *, idx_bits: int, block: int
):
    # x/c0/c1/blen refs (1, block); table_ref (TS,) uint32; valid_ref (TS,) int32
    def body(i, carry):
        x = x_ref[0, i]
        h = ((x * jnp.uint32(KNUTH)) >> jnp.uint32(32 - idx_bits)).astype(jnp.int32)
        hit = (valid_ref[h] > 0) & (table_ref[h] == x)
        c0_ref[0, i] = jnp.where(hit, jnp.uint32(1) | (h.astype(jnp.uint32) << 1), x << 1)
        c1_ref[0, i] = jnp.where(hit, jnp.uint32(0), x >> 31)
        blen_ref[0, i] = jnp.where(hit, 1 + idx_bits, 33).astype(jnp.int32)
        return carry

    jax.lax.fori_loop(0, block, body, 0)


def probe(
    x: jax.Array,
    table: jax.Array,
    valid: jax.Array,
    idx_bits: int = 12,
    block: int = DEFAULT_BLOCK,
    interpret: bool = False,
):
    """Vectorized dictionary probe.

    x: (N,) uint32; table: (2**idx_bits,) uint32; valid: (2**idx_bits,) uint8.
    Returns (c0, c1, bitlen) symbol slots (see algorithms/base.py).
    """
    n = x.shape[0]
    ts = 1 << idx_bits
    assert n % block == 0 and table.shape == (ts,) and valid.shape == (ts,)
    nb = n // block
    kernel = functools.partial(_probe_kernel, idx_bits=idx_bits, block=block)
    whole = pl.BlockSpec(memory_space=pltpu.SMEM)  # whole table, every step
    c0, c1, blen = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[smem_row(block), whole, whole],
        out_specs=[smem_row(block)] * 3,
        out_shape=[
            jax.ShapeDtypeStruct((nb, 1, block), jnp.uint32),
            jax.ShapeDtypeStruct((nb, 1, block), jnp.uint32),
            jax.ShapeDtypeStruct((nb, 1, block), jnp.int32),
        ],
        interpret=interpret,
    )(x.reshape(nb, 1, block), table, valid.astype(jnp.int32))
    return c0.reshape(n), c1.reshape(n), blen.reshape(n)
