"""Mixture-of-Experts FFN with capacity-bounded top-k routing.

Dispatch is the dense one-hot-cumsum scheme (Mesh-TF/MaxText style): each
(token, choice) computes its rank within its expert via a cumulative sum,
ranks >= capacity are dropped, and tokens are scattered into an
(E, capacity, D) buffer for batched per-expert matmuls.  Sharded, the
scatter is the all-to-all of expert parallelism; the buffer carries a
"model"-axis hint when E divides the model axis (qwen3-moe), otherwise the
expert FFN inner dim is TP-sharded (mixtral) — DESIGN.md §8.

Expert-parallel MoE is also where the paper's *asymmetry-aware scheduling*
insight re-appears at pod scale: the router's load-balancing loss plays the
role of CStream's workload-distribution ratio, keeping per-core (per-expert
-shard) work balanced (DESIGN.md §2).
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models import partition


# --------------------------------------------------- unique scatter/gather --
# Dispatch slots are UNIQUE per (expert, slot) — per-shard ranks guarantee
# no collisions — so dispatch is scatter-SET and its transpose is a plain
# gather (and vice versa).  Spelling both directions without scatter-ADD
# matters: XLA upcasts bf16 scatter-add accumulators to f32, which was
# materializing every dispatch buffer (and its cotangent) at 2x width and
# f32-sized collectives (§Perf A3).  Dropped tokens carry the sentinel
# slot c == C: out-of-bounds, so writes drop and reads fill zero.
@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def unique_scatter(src, e, c, E, C):
    """src (N, D), unique (e, c) with sentinel c==C dropped -> buf (E, C, D)."""
    buf = jnp.zeros((E, C, src.shape[-1]), src.dtype)
    return buf.at[e, c].set(src, mode="drop")


def _us_fwd(src, e, c, E, C):
    return unique_scatter(src, e, c, E, C), (e, c)


def _us_bwd(E, C, res, dbuf):
    e, c = res
    return dbuf.at[e, c].get(mode="fill", fill_value=0), None, None


unique_scatter.defvjp(_us_fwd, _us_bwd)


@jax.custom_vjp
def unique_gather(buf, e, c):
    """buf (E, C, D), (e, c) with sentinel c==C reading zeros -> (N, D)."""
    return buf.at[e, c].get(mode="fill", fill_value=0)


def _ug_fwd(buf, e, c):
    return unique_gather(buf, e, c), (e, c, buf.shape)


def _ug_bwd(res, dg):
    e, c, shape = res
    dbuf = jnp.zeros(shape, dg.dtype).at[e, c].set(dg, mode="drop")
    return dbuf, None, None


unique_gather.defvjp(_ug_fwd, _ug_bwd)


def _dispatch_indices(sel_flat: jax.Array, E: int, C_local: int):
    """Local (expert, slot) for each (token, choice); sentinel slot C_local
    for capacity overflow."""
    oh = jax.nn.one_hot(sel_flat, E, dtype=jnp.int32)
    pos = jnp.cumsum(oh, axis=0) - oh
    rank = jnp.take_along_axis(pos, sel_flat[:, None], axis=1)[:, 0]
    keep = rank < C_local
    slot = jnp.where(keep, rank, C_local)
    e = jnp.where(keep, sel_flat, 0)
    return e, slot


def _data_axes_and_shards():
    """(physical data-axis entry for PartitionSpec, shard count) or (None, 1)
    when no mesh/logical mapping is active (single-device smoke tests)."""
    entry = partition._AXES.get("data") if partition._AXES else None
    if entry is None:
        return None, 1
    try:
        mesh = jax.sharding.get_abstract_mesh()
        names = entry if isinstance(entry, tuple) else (entry,)
        n = 1
        for a in names:
            n *= mesh.shape[a]
        return entry, n
    except Exception:
        return None, 1


def init_moe(key, cfg, dtype):
    D, F, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 4)
    scale_in = 1.0 / jnp.sqrt(D)
    scale_out = 1.0 / jnp.sqrt(F)
    return {
        "router": (jax.random.normal(ks[0], (D, E)) * scale_in).astype(jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (E, D, F)) * scale_in).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (E, D, F)) * scale_in).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (E, F, D)) * scale_out).astype(dtype),
    }


def capacity(tokens: int, cfg) -> int:
    cap = int(cfg.capacity_factor * tokens * cfg.n_experts_per_token / cfg.n_experts)
    return max(8, -(-cap // 8) * 8)  # round up to 8 for tiling


def moe_ffn(params, cfg, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """x (B, S, D) -> (y (B, S, D), aux load-balance loss scalar)."""
    B, S, D = x.shape
    E, k = cfg.n_experts, cfg.n_experts_per_token
    T = B * S
    xt = x.reshape(T, D)

    logits = (xt.astype(jnp.float32) @ params["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)  # (T, E)
    gate_vals, sel = jax.lax.top_k(probs, k)  # (T, k)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # load-balance aux (Switch): E * sum_e f_e * p_e
    f = jnp.mean(
        jnp.sum(jax.nn.one_hot(sel, E, dtype=jnp.float32), axis=1), axis=0
    )  # fraction routed per expert
    p_mean = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(f * p_mean)

    # Dispatch/combine run PER DATA SHARD under shard_map (§Perf A4): the
    # scatter/gather indices are shard-local by construction (per-shard
    # ranks, per-shard capacity — the standard per-device-capacity EP
    # formulation), but SPMD cannot prove that and reshards the operands
    # into cross-shard permute/gather chains (A2/A3 measured 400+ GB).
    # shard_map makes the locality explicit; the expert matmuls stay in
    # auto-SPMD land between the two maps.
    ep = cfg.n_experts % 16 == 0
    e_ax = "model" if ep else None
    dax, n_shards = _data_axes_and_shards()
    if dax is not None and T % n_shards != 0:
        dax, n_shards = None, 1  # e.g. batch-1 decode: tokens can't shard
    C_local = max(8, -(-capacity(T, cfg) // n_shards))
    C = n_shards * C_local
    dtype = x.dtype
    c_ax = "data" if dax is not None else None

    def disp_local(xt_l, sel_l):
        Tl = xt_l.shape[0]
        e, slot = _dispatch_indices(sel_l.reshape(Tl * k), E, C_local)
        src = jnp.repeat(xt_l, k, axis=0)
        return unique_scatter(src, e, slot, E, C_local), e, slot

    def comb_local(out_buf_l, e_l, slot_l, gv_l):
        gathered = unique_gather(out_buf_l, e_l, slot_l)
        w = gv_l.reshape(-1).astype(dtype)
        return jnp.sum((gathered * w[:, None]).reshape(-1, k, D), axis=1)

    if dax is not None:
        from jax.sharding import PartitionSpec as P

        axis_names = frozenset(dax if isinstance(dax, tuple) else (dax,))
        buf, e_idx, slot = jax.shard_map(
            disp_local,
            in_specs=(P(dax, None), P(dax, None)),
            out_specs=(P(None, dax, None), P(dax), P(dax)),
            axis_names=axis_names,
            check_vma=False,
        )(xt, sel)
    else:
        buf, e_idx, slot = disp_local(xt, sel)
    buf = partition.hint(buf, e_ax, c_ax, None)

    # ZeRO-style per-use weight gather: storage is FSDP'd over data; the
    # einsum operand must NOT contract a data-sharded dim (SPMD would
    # partial-sum it into per-layer activation all-reduces, §Perf A1), so
    # re-hint the bf16 slice to its compute sharding first (§Perf A5).
    w_gate = partition.hint(params["w_gate"], e_ax, None, None if ep else "model")
    w_up = partition.hint(params["w_up"], e_ax, None, None if ep else "model")
    w_down = partition.hint(params["w_down"], e_ax, None if ep else "model", None)

    # batched per-expert SwiGLU
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w_gate)) * jnp.einsum(
        "ecd,edf->ecf", buf, w_up
    )
    h = partition.hint(h, e_ax, c_ax, None if ep else "model")
    out_buf = jnp.einsum("ecf,efd->ecd", h, w_down)
    out_buf = partition.hint(out_buf, e_ax, c_ax, None)

    if dax is not None:
        from jax.sharding import PartitionSpec as P

        axis_names = frozenset(dax if isinstance(dax, tuple) else (dax,))
        yt = jax.shard_map(
            comb_local,
            in_specs=(P(None, dax, None), P(dax), P(dax), P(dax, None)),
            out_specs=P(dax, None),
            axis_names=axis_names,
            check_vma=False,
        )(out_buf, e_idx, slot, gate_vals)
    else:
        yt = comb_local(out_buf, e_idx, slot, gate_vals)
    return yt.reshape(B, S, D), aux
