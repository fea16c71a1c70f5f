"""CStreamEngine — deprecated shim over the unified job API (DESIGN.md §12).

The engine predates `repro.cstream`: it exposed compression through an
`EngineConfig` constructor plus `compress/roundtrip/gang_compress` methods.
All of that behavior now lives in the job API's negotiation + execution
layers (`repro/api.py`): the engine converts its `EngineConfig` (+ optional
calibration sample) into a resolved `JobSpec` via
`JobSpec.from_engine_config`, negotiates the same `Plan` the new surface
would, and delegates every run to the same `run_compress` /
`run_gang_compress` / `run_roundtrip` implementations `StreamHandle` uses —
so the shim is bit-identical to the new surface by construction (and the
API tests assert frames/records/metrics equality anyway).

Migration (see DESIGN.md §12 for the full table):

    CStreamEngine(cfg, sample).compress(v)   -> cstream.open(spec).push(v).flush()
    CStreamEngine(cfg, sample).roundtrip(v)  -> cstream.open(spec.replace(egress=True)) ...
    CStreamEngine(cfg).gang_compress(vs)     -> cstream.gang_compress(spec, vs)

`sharded_compress_fn` (the pjit scale-out path) is not deprecated; it lives
here unchanged.
"""
from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import api
from repro.api import (  # noqa: F401  (canonical homes are repro.api / repro.cstream)
    CompressResult,
    GangCompressResult,
    RoundtripResult,
    queueing_delay_s,
)
from repro.core import bits
from repro.core.algorithms import make_codec
from repro.core.pipeline import (
    CompressionPipeline,
    DecompressionPipeline,
    lww_select,
    merge_shared_dictionary,
)
from repro.core.strategies import (  # noqa: F401  (re-exported for callers)
    EngineConfig,
    ExecutionStrategy,
    SchedulingStrategy,
    StateStrategy,
    block_costs,
    schedule_blocks,
)

# Backward-compatible alias: the merge predates the pipeline extraction and
# is referenced by tests/callers under its old private name.
_merge_shared_dictionary = merge_shared_dictionary


class CStreamEngine:
    """Deprecated: declare a `repro.cstream.JobSpec` and `cstream.open` it.

    Kept as a bit-identical facade — construction negotiates the equivalent
    JobSpec/Plan, and every method body is the shared api-layer runner."""

    def __init__(self, config: EngineConfig, sample: Optional[np.ndarray] = None):
        api.warn_deprecated_shim("CStreamEngine", "cstream.open(JobSpec(...))")
        self.config = config
        self.spec = api.JobSpec.from_engine_config(config, sample=sample)
        self.plan = api.negotiate(self.spec)
        self.pipeline = CompressionPipeline(
            config, codec=self.plan.codec, plan=self.plan.execution
        )
        self.codec = self.pipeline.codec
        self._step = self.pipeline._step
        self._decompressor: Optional[DecompressionPipeline] = None

    @property
    def decompressor(self) -> DecompressionPipeline:
        """Lazily built egress executor sharing this engine's codec."""
        if self._decompressor is None:
            self._decompressor = DecompressionPipeline(
                self.config, codec=self.codec, plan=self.plan.execution
            )
        return self._decompressor

    # ------------------------------------------------------------- shaping
    def _block_tuples(self) -> int:
        return self.pipeline.block_tuples

    def _blocks(self, values: np.ndarray) -> np.ndarray:
        """Full blocks of the stream (legacy view; tail handling lives in
        `pipeline.shape_blocks`)."""
        return self.pipeline.shape_blocks(values).blocks

    # ------------------------------------------------------------- compress
    def compress(
        self,
        values: np.ndarray,
        arrival_rate_tps: Optional[float] = None,
        max_blocks: Optional[int] = None,
        breakdown: bool = False,
        emit_frame: bool = False,
    ) -> CompressResult:
        """Compress a stream; with `emit_frame=True` the result additionally
        carries the self-describing wire-format `bits.Frame` (the payload a
        consumer decodes with `decompress`)."""
        return api.run_compress(
            self.pipeline,
            self.spec,
            values,
            arrival_rate_tps=arrival_rate_tps,
            max_blocks=max_blocks,
            breakdown=breakdown,
            emit_frame=emit_frame,
        )

    # ----------------------------------------------------------------- gang
    def gang_compress(
        self,
        streams: List[np.ndarray],
        emit_frames: bool = False,
    ) -> GangCompressResult:
        """Compress S independent streams through gang-batched dispatches
        (see `api.run_gang_compress` / DESIGN.md §11)."""
        if not streams:
            raise ValueError("gang_compress needs at least one stream")
        return api.run_gang_compress(
            self.pipeline, self.spec, streams, emit_frames=emit_frames
        )

    # --------------------------------------------------------------- egress
    def decompress(self, frame: bits.Frame) -> np.ndarray:
        """Reconstruct a framed bitstream (fused chunked-scan decode)."""
        return self.decompressor.decompress(frame).values

    def roundtrip(
        self,
        values: np.ndarray,
        arrival_rate_tps: Optional[float] = None,
        max_blocks: Optional[int] = None,
    ) -> RoundtripResult:
        """Compress to the wire frame, decode it back, check fidelity."""
        return api.run_roundtrip(
            self.pipeline,
            self.decompressor,
            self.spec,
            values,
            arrival_rate_tps=arrival_rate_tps,
            max_blocks=max_blocks,
        )

    # -------------------------------------------------- lossy fidelity check
    def roundtrip_nrmse(self, values: np.ndarray) -> float:
        """NRMSE through the framed wire roundtrip (0.0 when bit-exact)."""
        return self.roundtrip(values).fidelity.nrmse


# ----------------------------------------------------------- sharded engine --
def sharded_compress_fn(
    codec_name: str,
    mesh,
    axis: str = "data",
    shared_state: bool = False,
    **codec_kwargs,
):
    """Build a pjit-able compression step distributed over a mesh axis.

    Private mode (default): each device owns its lane group and codec state —
    the paper's private-state strategy at pod scale, zero per-batch
    collectives beyond the bit-count psum. Shared mode (dictionary codecs):
    tables are merged across devices every micro-batch via the same
    last-writer-wins `lww_select` the local engine uses — the
    collective-latency analogue of the paper's lock contention, visible in
    the dry-run roofline. Used by launch/dryrun.py and the gradient path.
    """
    from jax.sharding import PartitionSpec as P

    codec = make_codec(codec_name, **codec_kwargs)

    def shard_step(state, block):  # per-device view: (lanes_local, B)
        state, enc = codec.encode(state, block)
        if shared_state and codec.meta.state_kind == "dictionary":
            state = merge_shared_dictionary(state)  # lanes within the device
            # cross-device last-writer-wins: the collective analogue of the
            # paper's lock-guarded shared table — same merge, gathered rows
            tables = jax.lax.all_gather(state["table"][0], axis)  # (ndev, TS)
            valids = jax.lax.all_gather(state["valid"][0], axis)
            tss = jax.lax.all_gather(state["ts"][0], axis)
            table, valid, ts = lww_select(tables, valids, tss)
            lanes = state["table"].shape[0]
            ts_size = table.shape[-1]
            state = {
                "table": jnp.broadcast_to(table, (lanes, ts_size)),
                "valid": jnp.broadcast_to(valid, (lanes, ts_size)),
                "ts": jnp.broadcast_to(ts, (lanes, ts_size)),
                "clock": jnp.broadcast_to(jax.lax.pmax(state["clock"][0], axis), (lanes,)),
            }
        lanes, B = block.shape
        words, total_bits, _ = bits.pack_bits(
            enc.codes.reshape(lanes * B, 2),
            enc.bitlen.reshape(lanes * B),
            lanes * B * 2 + 2,
        )
        total_bits = jax.lax.psum(total_bits, axis)
        return state, words, total_bits

    return jax.jit(
        jax.shard_map(
            shard_step,
            mesh=mesh,
            in_specs=(P(axis), P(axis, None)),
            out_specs=(P(axis), P(axis), P()),
        )
    )
