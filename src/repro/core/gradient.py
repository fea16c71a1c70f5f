"""Error-feedback compressed gradient synchronization (production path #2).

CStream's lossy NUQ codec applied to the distributed-optimizer boundary:
cross-pod gradient all-reduce carries uint8/uint4 mu-law codes + per-chunk
absmax scales instead of fp32 — a 4-8x reduction of the slowest wire in a
multi-pod job (the inter-pod links, DESIGN.md §8).  Error feedback keeps
the quantization residual locally and re-injects it next step, the
standard convergence-preserving trick (1-bit Adam / EF-SGD lineage) and
the direct analogue of ADPCM's "carry the reconstruction error in the
state" (paper §3.1.2).

Layering:
  quantize_tensor / dequantize_tensor   — chunked absmax mu-law codec
  compressed_allreduce_mean             — inside shard_map: all_gather codes
  compressed_grad_sync                  — top-level: shard_map over ONE mesh
                                          axis (the pod axis), other axes auto
  ef_step                               — error-feedback state update
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.algorithms.nuq import mulaw_decode_unsigned, mulaw_encode_unsigned


@dataclasses.dataclass(frozen=True)
class GradCompressionConfig:
    qbits: int = 8  # 8 (uint8) or 4 (packed pairs)
    chunk: int = 2048  # values per absmax scale
    error_feedback: bool = True
    mu: float = 255.0


# ------------------------------------------------------------ leaf codec --
def quantize_tensor(x: jax.Array, cfg: GradCompressionConfig) -> Tuple[jax.Array, jax.Array, int]:
    """x (any shape) -> (codes uint8[ceil(n*qbits/8)], scales f32[n_chunks], n)."""
    flat = x.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    pad = (-n) % cfg.chunk
    flat = jnp.pad(flat, (0, pad))
    ch = flat.reshape(-1, cfg.chunk)
    scale = jnp.max(jnp.abs(ch), axis=1) + 1e-12  # (n_chunks,)
    xn = ch / scale[:, None]
    sign = (xn < 0).astype(jnp.uint32)
    mag = mulaw_encode_unsigned(jnp.abs(xn), cfg.qbits - 1, 1.0, cfg.mu)
    codes = ((sign << (cfg.qbits - 1)) | mag).reshape(-1)
    if cfg.qbits == 8:
        packed = codes.astype(jnp.uint8)
    elif cfg.qbits == 4:
        c = codes.astype(jnp.uint8)
        packed = c[0::2] | (c[1::2] << 4)
    else:
        raise ValueError(f"qbits must be 4 or 8, got {cfg.qbits}")
    return packed, scale, n


def dequantize_tensor(
    packed: jax.Array, scale: jax.Array, n: int, shape, cfg: GradCompressionConfig, dtype=jnp.float32
) -> jax.Array:
    if cfg.qbits == 8:
        codes = packed.astype(jnp.uint32)
    else:
        lo = (packed & 0x0F).astype(jnp.uint32)
        hi = (packed >> 4).astype(jnp.uint32)
        codes = jnp.stack([lo, hi], axis=1).reshape(-1)
    sign = (codes >> (cfg.qbits - 1)) & jnp.uint32(1)
    mag_mask = jnp.uint32((1 << (cfg.qbits - 1)) - 1)
    mag = mulaw_decode_unsigned(codes & mag_mask, cfg.qbits - 1, 1.0, cfg.mu, round_int=False)
    xn = jnp.where(sign == 1, -mag, mag).reshape(-1, cfg.chunk)
    flat = (xn * scale[:, None]).reshape(-1)[:n]
    return flat.reshape(shape).astype(dtype)


def roundtrip(x: jax.Array, cfg: GradCompressionConfig) -> jax.Array:
    packed, scale, n = quantize_tensor(x, cfg)
    return dequantize_tensor(packed, scale, n, x.shape, cfg, x.dtype)


def wire_bytes(x: jax.Array, cfg: GradCompressionConfig) -> int:
    """Bytes on the wire for one tensor (codes + scales)."""
    n = x.size
    pad_n = n + ((-n) % cfg.chunk)
    return pad_n * cfg.qbits // 8 + (pad_n // cfg.chunk) * 4


# -------------------------------------------------- collective (in smap) --
def compressed_allreduce_mean(x: jax.Array, axis_name: str, cfg: GradCompressionConfig) -> jax.Array:
    """Mean over `axis_name` carrying quantized codes on the wire.

    Must run inside shard_map.  all_gather moves qbits/32 of the fp32
    volume; each device dequantizes and averages locally (the gather-based
    equivalent of a ring all-reduce for small world sizes like pod counts)."""
    packed, scale, n = quantize_tensor(x, cfg)
    all_packed = jax.lax.all_gather(packed, axis_name)  # (ndev, ...)
    all_scale = jax.lax.all_gather(scale, axis_name)
    ndev = all_packed.shape[0]
    deq = jax.vmap(lambda p, s: dequantize_tensor(p, s, n, x.shape, cfg))(all_packed, all_scale)
    return jnp.mean(deq, axis=0).astype(x.dtype)


# ----------------------------------------------------- top-level wrapper --
def compressed_grad_sync(
    grads: Any,
    mesh,
    axis: str = "pod",
    cfg: GradCompressionConfig = GradCompressionConfig(),
    param_specs: Optional[Any] = None,
):
    """Synchronize a gradient pytree across ONE mesh axis with compression.

    Other mesh axes stay automatic (FSDP/TP sharding untouched): shard_map
    is entered manually only over `axis` (axis_names = {axis}); partial-manual
    specs may only reference that axis, so param_specs are filtered down to
    their `axis` components (grads are unreduced-but-identical-shaped across
    pods — check_vma=False admits the per-pod local views)."""

    def filter_spec(spec) -> P:
        if spec is None:
            return P()
        return P(*[(a if a == axis else None) for a in spec])

    if param_specs is None:
        specs = jax.tree_util.tree_map(lambda _: P(), grads)
    else:
        specs = jax.tree_util.tree_map(
            filter_spec, param_specs, is_leaf=lambda s: isinstance(s, P) or s is None
        )

    def sync(g):
        return jax.tree_util.tree_map(
            lambda x: compressed_allreduce_mean(x, axis, cfg), g
        )

    fn = jax.shard_map(
        sync,
        mesh=mesh,
        in_specs=(specs,),
        out_specs=specs,
        check_vma=False,
        axis_names=frozenset({axis}),
    )
    # partial-manual shard_map requires a jit context with the mesh current;
    # inside a jitted train step this inlines, outside it jits here.
    return jax.jit(fn)(grads)


# ---------------------------------------------------------- error feedback --
def ef_init(grads_shape: Any) -> Any:
    """Zero residual pytree (same treedef/shapes as the gradients)."""
    return jax.tree_util.tree_map(
        lambda g: jnp.zeros(g.shape, jnp.float32)
        if hasattr(g, "shape")
        else jnp.zeros_like(g),
        grads_shape,
    )


def ef_step(grads: Any, residual: Any, cfg: GradCompressionConfig) -> Tuple[Any, Any]:
    """(grads+residual) -> (quantized view g_hat, new residual).

    Apply BEFORE the compressed collective so what travels the wire is the
    error-compensated gradient; the residual never leaves the device."""

    def one(g, r):
        tot = g.astype(jnp.float32) + r
        g_hat = roundtrip(tot, cfg)
        return g_hat.astype(g.dtype), tot - g_hat

    flat_g, treedef = jax.tree_util.tree_flatten(grads)
    flat_r = treedef.flatten_up_to(residual)
    outs = [one(g, r) for g, r in zip(flat_g, flat_r)]
    return treedef.unflatten([o[0] for o in outs]), treedef.unflatten([o[1] for o in outs])
