"""Public jit'd wrappers for the Pallas kernels.

On the CPU backend the kernels execute in `interpret=True` mode — the
kernel body runs under the Pallas interpreter for correctness validation; on
TPU the same call sites compile to Mosaic (tests/test_tpu_compile.py).
`interpret` resolves automatically from the backend.
"""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels import bitpack as _bitpack
from repro.kernels import bitunpack as _bitunpack
from repro.kernels import delta_nuq as _delta_nuq
from repro.kernels import dict_hash as _dict_hash
from repro.kernels import frame_compact as _frame_compact


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


@partial(jax.jit, static_argnames=("block",))
def pack_blocks(codes, bitlen, block: int = _bitpack.DEFAULT_BLOCK):
    return _bitpack.pack_blocks(codes, bitlen, block=block, interpret=_interpret())


@partial(jax.jit, static_argnames=("block",))
def unpack_blocks(words, bitlen, block: int = _bitunpack.DEFAULT_BLOCK):
    """Decode-side mirror of `pack_blocks` (kernels/bitunpack.py)."""
    return _bitunpack.unpack_blocks(words, bitlen, block=block, interpret=_interpret())


@jax.jit
def frame_compact(words, nbits):
    """Gather-compact stacked worst-case word buffers into one wire-shaped
    payload (kernels/frame_compact.py). Returns (payload, total_words)."""
    return _frame_compact.compact_blocks(words, nbits, interpret=_interpret())


@jax.jit
def pack_meta7(bitlen):
    """Pack (n, S) per-block bitlens at 7 bits/symbol into uint32 words
    (kernels/frame_compact.py, decode-metadata mirror of the frame wire)."""
    return _frame_compact.pack_meta7_blocks(bitlen, interpret=_interpret())


@jax.jit
def rans_encode(syms, mask, freqs):
    """Interleaved rANS encode of one chunk's (T, 8) byte grid
    (kernels/rans.py). Returns (states, flags, vals)."""
    from repro.kernels import rans as _rans

    return _rans.encode_rows(syms, mask, freqs, interpret=_interpret())


@jax.jit
def rans_decode(stream, freqs, states, offsets, mask):
    """Forward interleaved rANS decode of one chunk (kernels/rans.py):
    lanes start in parallel from the decoupled offset stream."""
    from repro.kernels import rans as _rans

    return _rans.decode_rows(
        stream, freqs, states, offsets, mask, interpret=_interpret()
    )


@partial(jax.jit, static_argnames=("qbits", "dmax", "mu", "sublanes", "t_tile"))
def adpcm_encode(x, qbits: int = 8, dmax: float = 1.0, mu: float = 255.0,
                 sublanes: int = _delta_nuq.DEFAULT_SUBLANES,
                 t_tile: int = _delta_nuq.DEFAULT_T):
    return _delta_nuq.encode(
        x, qbits=qbits, dmax=dmax, mu=mu, sublanes=sublanes, t_tile=t_tile,
        interpret=_interpret(),
    )


@partial(jax.jit, static_argnames=("qbits", "dmax", "mu", "sublanes", "t_tile"))
def adpcm_decode(codes, qbits: int = 8, dmax: float = 1.0, mu: float = 255.0,
                 sublanes: int = _delta_nuq.DEFAULT_SUBLANES,
                 t_tile: int = _delta_nuq.DEFAULT_T):
    return _delta_nuq.decode(
        codes, qbits=qbits, dmax=dmax, mu=mu, sublanes=sublanes, t_tile=t_tile,
        interpret=_interpret(),
    )


@partial(jax.jit, static_argnames=("idx_bits", "block"))
def dict_probe(x, table, valid, idx_bits: int = 12, block: int = _dict_hash.DEFAULT_BLOCK):
    return _dict_hash.probe(
        x, table, valid, idx_bits=idx_bits, block=block, interpret=_interpret()
    )


@partial(jax.jit, static_argnames=("window", "causal", "bq", "bk"))
def flash_attention_fwd(q, k, v, window=None, causal: bool = True,
                        bq: int = 512, bk: int = 1024):
    """Pallas flash attention (fwd): VMEM-resident scores (§Perf B4)."""
    from repro.kernels import flash_attn as _flash

    return _flash.flash_fwd(
        q, k, v, window=window, causal=causal, bq=bq, bk=bk, interpret=_interpret()
    )
