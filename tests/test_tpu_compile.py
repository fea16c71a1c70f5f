"""Compile the served path and every Pallas kernel for a described TPU v5e.

Nothing runs: each program is lowered and compiled by the TPU compiler for
a `v5e:2x2` topology that is described, not attached, so what the chip's
compiler refuses (unaligned tiling, unsupported primitives, programs that
do not partition) fails here at no chip time. Results and timings need a
chip run (`chip_smoke.py`).

The topology is described inside a module fixture, never while a module is
imported: only one process may load the TPU library, and a worker that
describes it at import time would make the others collect different tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

LANES = 4
FLUSH_TUPLES = 2048  # 8 KiB micro-batches of 4-byte tuples (Fig 11)
GANG = 256  # sessions per gang wave


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep these out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe means skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args) -> str:
    """Compile `fn` for the described chip; return the optimized HLO text."""
    return jax.jit(fn).lower(*args).compile().as_text()


def _shapes(tree, sharding, lead=()):
    """ShapeDtypeStructs of `tree`'s leaves, with `lead` prepended."""
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(lead + tuple(x.shape), x.dtype, sharding=sharding),
        tree,
    )


def _pipeline(codec: str, **extra):
    from repro import cstream
    from repro.core.pipeline import CompressionPipeline

    spec = cstream.JobSpec(
        codec=codec,
        egress=True,
        gang=True,
        lanes=LANES,
        micro_batch_bytes=4 * FLUSH_TUPLES,
        flush_tuples=FLUSH_TUPLES,
        **extra,
    )
    plan = cstream.negotiate(spec)
    return CompressionPipeline(plan.spec, codec=plan.codec, plan=plan.execution)


def _gang_args(pipe, sharding, sessions=GANG):
    per_lane = FLUSH_TUPLES // LANES
    states = _shapes(pipe.init_state(), sharding, (sessions,))
    blocks = jax.ShapeDtypeStruct((sessions, LANES, per_lane), jnp.uint32, sharding=sharding)
    masks = jax.ShapeDtypeStruct((sessions, LANES, per_lane), jnp.bool_, sharding=sharding)
    return states, blocks, masks


# ------------------------------------------------------------ served path --
@pytest.mark.parametrize("codec", ["tcomp32", "rle", "tdic32"])
def test_egress_gang_step_compiles(one_chip, codec):
    """The egress wave (vmapped masked step, 7-bit metadata) at 256 sessions."""
    pipe = _pipeline(codec)
    fn = pipe._gang_step_fn(meta7=True)
    compiled = fn.lower(*_gang_args(pipe, one_chip)).compile()
    mem = compiled.memory_analysis()
    assert mem is None or mem.temp_size_in_bytes < 16 * 2**30


def test_sharded_gang_step_compiles(topo):
    """The fleet wave: the session axis sharded over a 4-chip data mesh."""
    mesh = Mesh(np.asarray(topo.devices), ("data",), axis_types=(AxisType.Auto,))
    data = NamedSharding(mesh, PartitionSpec("data"))
    pipe = _pipeline("tdic32")
    fn = pipe._gang_step_fn(meta7=True, mesh=mesh)
    compiled = fn.lower(*_gang_args(pipe, data)).compile()
    words = compiled.output_shardings[1]
    assert words.spec == PartitionSpec("data")
    assert len(words.device_set) == 4


@pytest.mark.parametrize("codec", ["tcomp32", "rle", "tdic32"])
def test_decode_step_compiles(one_chip, codec):
    """The collector's chunked-scan decode of one frame's full blocks."""
    from repro.core.pipeline import DecompressionPipeline

    pipe = _pipeline(codec)
    dec = DecompressionPipeline(pipe.config, codec=pipe.codec, plan=pipe.plan)
    per_lane = FLUSH_TUPLES // LANES
    ow = LANES * per_lane * 2 + 2  # the executor's worst-case block width
    chunk = 4
    state = _shapes(dec.init_state(LANES), one_chip)
    xs = (
        jax.ShapeDtypeStruct((chunk, ow), jnp.uint32, sharding=one_chip),
        jax.ShapeDtypeStruct((chunk, LANES, per_lane), jnp.int32, sharding=one_chip),
    )
    _compile(dec._scan_fn(chunk), state, xs)


def test_rans_section_compiles(one_chip):
    """The rANS entropy section's device encode and decode at one 4 KiB chunk."""
    from repro.core import entropy

    cap = entropy.CHUNK_BYTES
    u32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.uint32, sharding=one_chip)
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32, sharding=one_chip)
    enc = functools.partial(entropy._encode_device, cp=1)
    _compile(enc, u32((cap,)), i32(()))
    dec = functools.partial(entropy._decode_device, cp=1)
    _compile(
        dec, u32((cap,)), u32((256,)), u32((1, entropy.N_LANES)),
        u32((1, entropy.N_LANES)), i32(()),
    )


# --------------------------------------------------------------- kernels --
def _kernel_cases():
    """(name, fn, [(shape, dtype), ...]) at the widths tests/test_kernels.py
    validates in interpret mode."""
    from repro.kernels import bitpack, bitunpack, delta_nuq, dict_hash, flash_attn
    from repro.kernels import frame_compact, rans

    u32, i32, f32 = jnp.uint32, jnp.int32, jnp.float32
    p = functools.partial
    cases = []
    for n, b in [(256, 64), (512, 128), (1024, 256), (2048, 512)]:
        cases.append(("bitpack", p(bitpack.pack_blocks, block=b), [((n, 2), u32), ((n,), i32)]))
        cases.append(
            ("bitunpack", p(bitunpack.unpack_blocks, block=b),
             [((n // b, 2 * b + 1), u32), ((n,), i32)])
        )
    for n, ow in [(1, 34), (4, 130), (16, 258), (32, 66)]:
        cases.append(("frame_compact", frame_compact.compact_blocks, [((n, ow), u32), ((n,), i32)]))
    for n, s in [(1, 32), (4, 256), (8, 96), (3, 148)]:
        cases.append(("pack_meta7", frame_compact.pack_meta7_blocks, [((n, s), i32)]))
    for t in (1, 16, 512):
        cases.append(
            ("rans_encode", rans.encode_rows, [((t, 8), i32), ((t, 8), jnp.bool_), ((256,), i32)])
        )
    for t, cap in [(1, 16), (16, 128), (512, 4096)]:
        cases.append(
            ("rans_decode", rans.decode_rows,
             [((cap,), u32), ((256,), i32), ((8,), u32), ((8,), i32), ((t, 8), jnp.bool_)])
        )
    for s, t, sl, tt in [(8, 128, 8, 128), (16, 256, 8, 128), (32, 512, 16, 256)]:
        for q in (4, 8):
            enc = p(delta_nuq.encode, qbits=q, dmax=1.0, sublanes=sl, t_tile=tt)
            cases.append(("delta_nuq_encode", enc, [((s, t), f32)]))
    for q in (6, 8):
        dec = p(delta_nuq.decode, qbits=q, dmax=0.1, t_tile=128)
        cases.append(("delta_nuq_decode", dec, [((8, 128), u32)]))
    for n, b, ib in [(512, 128, 12), (1024, 512, 12), (512, 256, 10)]:
        ts = 1 << ib
        probe = p(dict_hash.probe, idx_bits=ib, block=b)
        cases.append(("dict_probe", probe, [((n,), u32), ((ts,), u32), ((ts,), jnp.uint8)]))
    for b, s, h, k, dh, window, bq, bk, dt in [
        (2, 64, 4, 2, 32, None, 16, 32, f32),
        (1, 128, 8, 8, 16, 48, 32, 64, f32),
        (2, 96, 6, 2, 64, None, 32, 32, f32),
        (1, 64, 4, 1, 128, None, 64, 64, f32),
        (1, 64, 4, 2, 32, None, 32, 32, jnp.bfloat16),
    ]:
        flash = p(flash_attn.flash_fwd, window=window, bq=bq, bk=bk)
        cases.append(("flash_attn", flash, [((b, s, h, dh), dt)] + [((b, s, k, dh), dt)] * 2))
    return cases


_KERNELS = [
    "bitpack", "bitunpack", "frame_compact", "pack_meta7", "rans_encode",
    "rans_decode", "delta_nuq_encode", "delta_nuq_decode", "dict_probe", "flash_attn",
]


def test_every_kernel_module_is_covered():
    """A kernel added to repro.kernels needs a compile case here."""
    import pkgutil

    import repro.kernels

    modules = {m.name for m in pkgutil.iter_modules(repro.kernels.__path__)}
    covered = {"bitpack", "bitunpack", "frame_compact", "rans", "delta_nuq", "dict_hash", "flash_attn"}
    assert modules - {"ops", "ref"} == covered
    assert {name for name, _, _ in _kernel_cases()} == set(_KERNELS)


@pytest.mark.parametrize("kernel", _KERNELS)
def test_pallas_kernel_compiles(one_chip, kernel):
    """Each kernel becomes a Mosaic custom call at every tested width."""
    cases = [(fn, args) for name, fn, args in _kernel_cases() if name == kernel]
    assert cases
    for fn, args in cases:
        structs = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in args]
        assert "tpu_custom_call" in _compile(fn, *structs), (kernel, args)
