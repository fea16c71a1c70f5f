"""NUQ-compressed KV cache — CStream's lossy codec applied to the decode path.

This is production path #3 for the paper's technique (DESIGN.md §3): the KV
cache is the dominant HBM term for `decode_32k` / `long_500k`, and the same
mu-law non-uniform quantizer that drives LEB128-NUQ / ADPCM compresses it
4x (8-bit codes + per-block scales) with per-block calibration, exactly the
paper's "lossy compression with bounded information loss" trade.

Layout: codes uint8[L, B, S, K, Dh] + scales float32[L, B, S//G, K] with
per-(group, head) absmax calibration over G=128-token groups.  Appends are
pure `dynamic_update_slice` (shape-stable, shardable over batch/seq axes);
reads dequantize on the fly inside blocked attention, so the full-precision
KV never exists in HBM — only in VMEM-sized tiles.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.algorithms.nuq import mulaw_decode_unsigned, mulaw_encode_unsigned

SCALE_GROUP = 128  # tokens per quantization scale group


def _build_dequant_table(qbits: int = 8) -> "np.ndarray":
    """All 2^qbits signed mu-law reconstructions, precomputed: dequantization
    becomes one 256-entry gather + a scale multiply (fuses to a single
    boundary in the compute dtype; no transcendentals in the decode loop —
    §Perf C3)."""
    import numpy as np

    codes = np.arange(1 << qbits, dtype=np.uint32)
    sign = (codes >> (qbits - 1)) & 1
    mag_mask = (1 << (qbits - 1)) - 1
    levels = (1 << (qbits - 1)) - 1
    y = (codes & mag_mask).astype(np.float64) / levels
    mag = (np.power(1.0 + 255.0, y) - 1.0) / 255.0
    return np.where(sign == 1, -mag, mag).astype(np.float32)


_DEQUANT_TABLE_8 = _build_dequant_table(8)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class QuantKVCache:
    """One layer-stacked quantized KV cache."""

    k_codes: jax.Array  # uint8 [L, B, W, K, Dh]
    v_codes: jax.Array  # uint8 [L, B, W, K, Dh]
    k_scale: jax.Array  # f32   [L, B, W // G, K]
    v_scale: jax.Array  # f32   [L, B, W // G, K]
    length: jax.Array  # int32 [] tokens currently valid (ring if > W)

    @property
    def window(self) -> int:
        return self.k_codes.shape[2]


def init_cache(n_layers: int, batch: int, window: int, kv_heads: int, head_dim: int) -> QuantKVCache:
    G = min(SCALE_GROUP, window)
    return QuantKVCache(
        k_codes=jnp.zeros((n_layers, batch, window, kv_heads, head_dim), jnp.uint8),
        v_codes=jnp.zeros((n_layers, batch, window, kv_heads, head_dim), jnp.uint8),
        k_scale=jnp.ones((n_layers, batch, window // G, kv_heads), jnp.float32),
        v_scale=jnp.ones((n_layers, batch, window // G, kv_heads), jnp.float32),
        length=jnp.zeros((), jnp.int32),
    )


# ----------------------------------------------------------- quant / deq --
def quantize_block(x: jax.Array, qbits: int = 8) -> Tuple[jax.Array, jax.Array]:
    """x (B, S, K, Dh) -> (codes uint8, scale f32 per (B, S//G, K)).

    Signed mu-law: 1 sign bit + (qbits-1) magnitude, absmax-calibrated per
    group — the kvcache instantiation of nuq.mulaw_encode_signed with a
    data-dependent dmax (the engine codecs use static calibration instead)."""
    B, S, K, Dh = x.shape
    G = min(SCALE_GROUP, S)
    xg = x.reshape(B, S // G, G, K, Dh).astype(jnp.float32)
    scale = jnp.max(jnp.abs(xg), axis=(2, 4)) + 1e-6  # (B, S//G, K)
    xn = xg / scale[:, :, None, :, None]
    sign = (xn < 0).astype(jnp.uint32)
    mag = mulaw_encode_unsigned(jnp.abs(xn), qbits - 1, 1.0)
    codes = ((sign << (qbits - 1)) | mag).astype(jnp.uint8)
    return codes.reshape(B, S, K, Dh), scale


def dequantize_block(codes: jax.Array, scale: jax.Array, qbits: int = 8, dtype=jnp.bfloat16) -> jax.Array:
    """codes (B, S, K, Dh) + scale (B, S//G, K) -> values (B, S, K, Dh)."""
    B, S, K, Dh = codes.shape
    G = min(SCALE_GROUP, S)
    c = codes.astype(jnp.uint32).reshape(B, S // G, G, K, Dh)
    sign_bit = (c >> (qbits - 1)) & jnp.uint32(1)
    mag_mask = jnp.uint32((1 << (qbits - 1)) - 1)
    mag = mulaw_decode_unsigned(c & mag_mask, qbits - 1, 1.0, round_int=False)
    xn = jnp.where(sign_bit == 1, -mag, mag)
    x = xn * scale[:, :, None, :, None]
    return x.reshape(B, S, K, Dh).astype(dtype)


def dequantize_block_kmajor(
    codes: jax.Array, scale: jax.Array, ring_w: int, qbits: int = 8, dtype=jnp.bfloat16
) -> jax.Array:
    """codes (B, C, K, Dh) + scale (B, C//G, K) -> values (B, K, C, Dh).

    Transposes the uint8 CODES into the attention layout before widening —
    the layout copy moves 1/4 (vs bf16) or 1/8 (vs f32) of the bytes the
    dequantize-then-transpose order would (§Perf C2)."""
    B, C, K, Dh = codes.shape
    G = min(SCALE_GROUP, ring_w)
    ct = jnp.moveaxis(codes, 2, 1).reshape(B, K, C // G, G, Dh)
    table = jnp.asarray(_DEQUANT_TABLE_8 if qbits == 8 else _build_dequant_table(qbits))
    xn = jnp.take(table, ct.astype(jnp.int32), axis=0)
    st = jnp.moveaxis(scale, 2, 1)[:, :, :, None, None]  # (B, K, C//G, 1, 1)
    return (xn * st).astype(dtype).reshape(B, K, C, Dh)


# ----------------------------------------------------------------- writes --
def prefill_layer(
    cache: QuantKVCache, layer: jax.Array, k: jax.Array, v: jax.Array
) -> QuantKVCache:
    """Write a full prefill (B, S<=W, K, Dh) for one layer at position 0."""
    S = k.shape[1]
    G = min(SCALE_GROUP, cache.window)
    pad = (-S) % G
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc, ks = quantize_block(k)
    vc, vs = quantize_block(v)
    zero = jnp.zeros((), jnp.int32)
    return QuantKVCache(
        k_codes=jax.lax.dynamic_update_slice(cache.k_codes, kc[None], (layer, zero, zero, zero, zero)),
        v_codes=jax.lax.dynamic_update_slice(cache.v_codes, vc[None], (layer, zero, zero, zero, zero)),
        k_scale=jax.lax.dynamic_update_slice(cache.k_scale, ks[None], (layer, zero, zero, zero)),
        v_scale=jax.lax.dynamic_update_slice(cache.v_scale, vs[None], (layer, zero, zero, zero)),
        length=jnp.asarray(S, jnp.int32),
    )


def append_token_layer(
    cache_layer: dict, k_t: jax.Array, v_t: jax.Array, pos: jax.Array
) -> dict:
    """Append one token (B, 1, K, Dh) to a single layer's cache slice (ring).

    Per-token writes quantize against the *current group scale* (scales are
    only re-calibrated per group at prefill; a decode append reuses the last
    scale — absmax growth within a group is clipped, matching the bounded-
    error contract of the mu-law codec)."""
    W = cache_layer["k_codes"].shape[1]
    slot = pos % W
    g = jnp.minimum(slot // min(SCALE_GROUP, W), cache_layer["k_scale"].shape[1] - 1)
    B = k_t.shape[0]

    def write(codes, scale, x):
        s = scale[:, g, :]  # (B, K)
        xn = jnp.clip(x[:, 0].astype(jnp.float32) / s[..., None], -1.0, 1.0)
        sign = (xn < 0).astype(jnp.uint32)
        mag = mulaw_encode_unsigned(jnp.abs(xn), 7, 1.0)
        c = ((sign << 7) | mag).astype(jnp.uint8)
        return jax.lax.dynamic_update_slice(
            codes, c[:, None], (jnp.zeros((), jnp.int32), slot, jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32))
        )

    return {
        "k_codes": write(cache_layer["k_codes"], cache_layer["k_scale"], k_t),
        "v_codes": write(cache_layer["v_codes"], cache_layer["v_scale"], v_t),
        "k_scale": cache_layer["k_scale"],
        "v_scale": cache_layer["v_scale"],
    }


# ------------------------------------------------------------------ reads --
def _flash_quant_stats(
    q: jax.Array,  # (B, 1, H, Dh)
    cache_layer: dict,  # local view: codes (B, Wl, K, Dh), scales (B, Wl//G, K)
    pos: jax.Array,
    window: Optional[int],
    kv_block: int,
    softcap: Optional[float],
    slot_base: jax.Array | int = 0,
    ring_w: Optional[int] = None,
):
    """Blocked flash stats over a (possibly shard-local) quantized ring
    slice.  `slot_base` is the slice's first global slot; `ring_w` the full
    ring size (for position reconstruction).  Returns unnormalized
    (m, l, acc) f32."""
    from repro.models.layers import _chunk_attn_update

    B, _, H, Dh = q.shape
    W = cache_layer["k_codes"].shape[1]
    K = cache_layer["k_codes"].shape[2]
    G = H // K
    ring = ring_w or W
    q_ = jnp.moveaxis(q, 2, 1)  # (B, H, 1, Dh)

    # block size: a multiple of the scale group that divides the slice
    G_eff = min(SCALE_GROUP, W)
    C = G_eff
    for cand in range(min(kv_block, W), G_eff - 1, -G_eff):
        if W % cand == 0:
            C = cand
            break
    n_blocks = W // C
    slots = slot_base + jnp.arange(W)
    # ring reconstruction: slot s holds absolute position p = s before the
    # ring wraps, else the latest p <= pos with p % ring == s.
    abs_pos = jnp.where(pos >= ring, pos - ((pos - slots) % ring), slots)
    valid = (abs_pos <= pos) & (slots < ring)
    if window is not None:
        valid = valid & (abs_pos > pos - window)

    m0 = jnp.full((B, K, G, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, K, G, 1), jnp.float32)
    a0 = jnp.zeros((B, K, G, 1, Dh), jnp.float32)

    kc = jnp.moveaxis(cache_layer["k_codes"].reshape(B, n_blocks, C, K, Dh), 1, 0)
    vc = jnp.moveaxis(cache_layer["v_codes"].reshape(B, n_blocks, C, K, Dh), 1, 0)
    vmask = valid.reshape(n_blocks, 1, C)  # broadcast over batch
    g_per_blk = C // G_eff
    ks = jnp.moveaxis(cache_layer["k_scale"].reshape(B, n_blocks, g_per_blk, K), 1, 0)
    vs = jnp.moveaxis(cache_layer["v_scale"].reshape(B, n_blocks, g_per_blk, K), 1, 0)

    def body(carry, blk):
        m, l, acc = carry
        kcb, vcb, ksb, vsb, mk = blk
        k_blk = dequantize_block_kmajor(kcb, ksb, ring)  # (B,K,C,Dh)
        v_blk = dequantize_block_kmajor(vcb, vsb, ring)
        mask = jnp.broadcast_to(mk, (B, 1, C))  # (B, Sq=1, C)
        m, l, acc = _chunk_attn_update(q_, k_blk, v_blk, mask, m, l, acc, softcap)
        return (m, l, acc), None

    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kc, vc, ks, vs, vmask))
    return m, l, acc


def decode_attention_quant(
    q: jax.Array,  # (B, 1, H, Dh) current-token queries (RoPE applied)
    cache_layer: dict,  # one layer: codes (B, W, K, Dh), scales (B, W//G, K)
    pos: jax.Array,  # int32 [] absolute position of the new token
    window: Optional[int],
    kv_block: int = 2048,
    softcap: Optional[float] = None,
) -> jax.Array:
    """Blocked decode attention over the quantized cache (single view)."""
    B, _, H, Dh = q.shape
    m, l, acc = _flash_quant_stats(q, cache_layer, pos, window, kv_block, softcap)
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return jnp.moveaxis(out.reshape(B, H, 1, Dh), 1, 2).astype(q.dtype)


def decode_attend_dlse(
    q: jax.Array,  # (B, 1, H, Dh)
    cache_layer: dict,  # (B, W, K, Dh) codes + (B, W//G, K) scales, W->model
    k_t: jax.Array,  # (B, 1, K, Dh) new token key (RoPE applied)
    v_t: jax.Array,  # (B, 1, K, Dh)
    pos: jax.Array,
    window: Optional[int],
    kv_block: int = 2048,
    softcap: Optional[float] = None,
):
    """Distributed-LSE decode (DESIGN.md §8, §Perf C1): the ring's seq dim is
    sharded over the model axis; each shard appends the token if the slot is
    its own, scans ONLY its local slice, and the (m, l, acc) triples merge
    with a log-sum-exp reduction over the model axis — the wire carries
    3 tiny stats tensors instead of the whole dequantized cache (the
    auto-SPMD baseline all-gathered 22 GB of codes per step).

    Falls back to the single-view path when no mesh/logical mapping is
    active.  Returns (attn_out (B,1,H,Dh), new_cache_layer)."""
    from jax.sharding import PartitionSpec as P

    from repro.models import partition

    B, _, H, Dh = q.shape
    W = cache_layer["k_codes"].shape[1]
    K = cache_layer["k_codes"].shape[2]
    G = H // K

    m_entry = partition._AXES.get("model") if partition._AXES else None
    d_entry = partition._AXES.get("data") if partition._AXES else None
    data_ok = B > 1
    try:
        mesh = jax.sharding.get_abstract_mesh()
        names = m_entry if isinstance(m_entry, tuple) else (m_entry,)
        n_model = 1
        for a in names:
            n_model *= mesh.shape[a]
    except Exception:
        m_entry = None
        n_model = 1

    def append_local(cl, kt_l, vt_l, slot_base, w_local):
        """Write (kt, vt) into this shard's slice iff the slot is ours."""
        slot = pos % W
        local = jnp.clip(slot - slot_base, 0, w_local - 1)
        mine = (slot >= slot_base) & (slot < slot_base + w_local)
        g = jnp.minimum(local // min(SCALE_GROUP, W), cl["k_scale"].shape[1] - 1)
        zero = jnp.zeros((), jnp.int32)

        def write(codes, scale, x):
            Bl = x.shape[0]
            s = jax.lax.dynamic_index_in_dim(scale, g, axis=1, keepdims=False)  # (B, K)
            xn = jnp.clip(x[:, 0].astype(jnp.float32) / s[..., None], -1.0, 1.0)
            sign = (xn < 0).astype(jnp.uint32)
            mag = mulaw_encode_unsigned(jnp.abs(xn), 7, 1.0)
            c_new = ((sign << 7) | mag).astype(jnp.uint8)[:, None]
            existing = jax.lax.dynamic_slice(codes, (zero, local, zero, zero), (Bl, 1, K, Dh))
            return jax.lax.dynamic_update_slice(
                codes, jnp.where(mine, c_new, existing), (zero, local, zero, zero)
            )

        return {
            "k_codes": write(cl["k_codes"], cl["k_scale"], kt_l),
            "v_codes": write(cl["v_codes"], cl["v_scale"], vt_l),
            "k_scale": cl["k_scale"],
            "v_scale": cl["v_scale"],
        }

    if m_entry is None or n_model == 1 or W % n_model != 0 or not isinstance(m_entry, str):
        cl = append_local(cache_layer, k_t, v_t, 0, W)
        return decode_attention_quant(q, cl, pos, window, kv_block, softcap), cl

    W_local = W // n_model

    def local(q_l, cl, kt_l, vt_l):
        slot_base = jax.lax.axis_index(m_entry) * W_local
        cl = append_local(cl, kt_l, vt_l, slot_base, W_local)
        m, l, acc = _flash_quant_stats(
            q_l, cl, pos, window, kv_block, softcap, slot_base=slot_base, ring_w=W
        )
        # LSE merge across model shards: 3 tiny tensors on the wire
        m_g = jax.lax.pmax(m, m_entry)
        w = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * w, m_entry)
        acc_g = jax.lax.psum(acc * w[..., None], m_entry)
        out = acc_g / jnp.maximum(l_g[..., None], 1e-30)
        Bl = q_l.shape[0]
        out = jnp.moveaxis(out.reshape(Bl, H, 1, Dh), 1, 2).astype(q_l.dtype)
        return out, cl

    dax = d_entry if data_ok else None
    cache_specs = {
        "k_codes": P(dax, m_entry, None, None),
        "v_codes": P(dax, m_entry, None, None),
        "k_scale": P(dax, m_entry, None),
        "v_scale": P(dax, m_entry, None),
    }
    manual = frozenset(
        a
        for e in (m_entry, dax)
        if e
        for a in (e if isinstance(e, tuple) else (e,))
    )
    tok_spec = P(dax, None, None, None)
    out, new_cl = jax.shard_map(
        local,
        in_specs=(tok_spec, cache_specs, tok_spec, tok_spec),
        out_specs=(tok_spec, cache_specs),
        axis_names=manual,
        check_vma=False,
    )(q, cache_layer, k_t, v_t)
    return out, new_cl


def cache_bytes(cache: QuantKVCache) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(cache))
