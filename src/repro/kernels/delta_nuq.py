"""Pallas TPU kernel: fused block-ADPCM (delta + mu-law NUQ) encode/decode.

The ADPCM hot loop (paper §3.1.4) is a sequential nonlinear recurrence. The
TPU-native layout puts `SUBLANES` independent substreams in the vector lanes
(the paper's private-state threads mapped onto the VPU) and loops over time
inside the kernel while the whole working set stays in VMEM. Each grid step
handles a (SUBLANES, T) tile; every substream starts from a raw reference
sample, so tiles are independent and the grid scales across cores/chips.

Mosaic lowers neither a dynamic column slice nor a scatter, so step t reads
its column as a one-hot lane reduction and writes it as a one-hot select
over the tile (`_column`). The kernel mirrors the jnp scans in
`kernels/ref.py`; no served path calls it yet.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_SUBLANES = 8
DEFAULT_T = 128


def _encode_tile(x, qbits: int, dmax: float, mu: float):
    """Shared tile body: x (S, T) float32 -> (codes uint32, xhat float32)."""
    S, T = x.shape
    levels = (1 << (qbits - 1)) - 1
    log1p_mu = jnp.log1p(mu)

    def quant(d):
        sign = (d < 0).astype(jnp.uint32)
        y = jnp.log1p(mu * jnp.abs(d) / dmax) / log1p_mu
        # via int32: Mosaic has no direct float <-> uint32 cast
        mag = jnp.clip(jnp.round(y * levels), 0, levels).astype(jnp.int32).astype(jnp.uint32)
        return (sign << (qbits - 1)) | mag

    def dequant(c):
        sign = (c >> (qbits - 1)) & jnp.uint32(1)
        mag = (c & jnp.uint32(levels)).astype(jnp.int32).astype(jnp.float32) / levels
        d = (jnp.power(1.0 + mu, mag) - 1.0) / mu * dmax
        return jnp.where(sign == 1, -d, d)

    t_idx = jax.lax.broadcasted_iota(jnp.int32, (S, T), 1)
    xbits = jax.lax.bitcast_convert_type(x, jnp.int32)

    def body(t, carry):
        xhat, codes = carry
        xt = jax.lax.bitcast_convert_type(_column(xbits, t_idx, t), jnp.float32)
        d = jnp.clip(xt - xhat, -dmax, dmax)
        c = quant(d)
        xhat = xhat + dequant(c)
        codes = jnp.where(t_idx == t, c[:, None], codes)
        return xhat, codes

    # substream bootstrap: first sample is the raw (bitcast) fp32 reference
    xhat0 = x[:, 0]
    codes0 = jnp.where(t_idx == 0, jax.lax.bitcast_convert_type(x, jnp.uint32), 0)
    xhat, codes = jax.lax.fori_loop(1, T, body, (xhat0, codes0.astype(jnp.uint32)))
    return codes


def _column(v, t_idx, t):
    """Column `t` of the int32 tile `v` as a one-hot lane reduction (Mosaic
    lowers no dynamic lane slice): exact, as every other term is zero."""
    return jnp.sum(jnp.where(t_idx == t, v, 0), axis=1)


def _encode_kernel(x_ref, codes_ref, *, qbits: int, dmax: float, mu: float):
    codes_ref[...] = _encode_tile(x_ref[...].astype(jnp.float32), qbits, dmax, mu)


def _decode_kernel(codes_ref, x_ref, *, qbits: int, dmax: float, mu: float):
    codes = codes_ref[...]
    S, T = codes.shape
    levels = (1 << (qbits - 1)) - 1

    def dequant(c):
        sign = (c >> (qbits - 1)) & jnp.uint32(1)
        mag = (c & jnp.uint32(levels)).astype(jnp.int32).astype(jnp.float32) / levels
        d = (jnp.power(1.0 + mu, mag) - 1.0) / mu * dmax
        return jnp.where(sign == 1, -d, d)

    t_idx = jax.lax.broadcasted_iota(jnp.int32, (S, T), 1)
    cbits = jax.lax.bitcast_convert_type(codes, jnp.int32)

    def body(t, carry):
        xhat, out = carry
        ct = jax.lax.bitcast_convert_type(_column(cbits, t_idx, t), jnp.uint32)
        xhat = xhat + dequant(ct)
        out = jnp.where(t_idx == t, xhat[:, None], out)
        return xhat, out

    xhat0 = jax.lax.bitcast_convert_type(codes[:, 0], jnp.float32)  # raw reference
    out0 = jnp.where(t_idx == 0, jax.lax.bitcast_convert_type(codes, jnp.float32), 0.0)
    _, out = jax.lax.fori_loop(1, T, body, (xhat0, out0))
    x_ref[...] = out


def encode(
    x: jax.Array,
    qbits: int = 8,
    dmax: float = 1.0,
    mu: float = 255.0,
    sublanes: int = DEFAULT_SUBLANES,
    t_tile: int = DEFAULT_T,
    interpret: bool = False,
):
    """x: (S, T) float32 substreams -> (S, T) uint32 codes (code[:, 0] = raw ref)."""
    S, T = x.shape
    assert S % sublanes == 0 and T % t_tile == 0, (S, T, sublanes, t_tile)
    kernel = functools.partial(_encode_kernel, qbits=qbits, dmax=dmax, mu=mu)
    return pl.pallas_call(
        kernel,
        grid=(S // sublanes, T // t_tile),
        in_specs=[pl.BlockSpec((sublanes, t_tile), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((sublanes, t_tile), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((S, T), jnp.uint32),
        interpret=interpret,
    )(x)


def decode(
    codes: jax.Array,
    qbits: int = 8,
    dmax: float = 1.0,
    mu: float = 255.0,
    sublanes: int = DEFAULT_SUBLANES,
    t_tile: int = DEFAULT_T,
    interpret: bool = False,
):
    S, T = codes.shape
    assert S % sublanes == 0 and T % t_tile == 0
    kernel = functools.partial(_decode_kernel, qbits=qbits, dmax=dmax, mu=mu)
    return pl.pallas_call(
        kernel,
        grid=(S // sublanes, T // t_tile),
        in_specs=[pl.BlockSpec((sublanes, t_tile), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((sublanes, t_tile), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((S, T), jnp.float32),
        interpret=interpret,
    )(codes)
