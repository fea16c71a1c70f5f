"""A gang wave's own spans and counters (`ServerCore._run_wave`, DESIGN.md
§19): one wave of five sessions on one device and, in a child process, on
four virtual CPU devices, where the wave is padded to eight rows; and the
name the flush-step program carries into a device trace."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro import cstream
from repro.runtime import spans

WAVE_COUNTERS = ("wave.slots", "wave.pad_slots", "wave.sharded")
WAVE_SPANS = ("wave.assemble", "wave.step", "wave.egress", "wave.commit")
#: one wave: five sessions that each fill one flush with equal arrivals
MEMBERS = 5


def _one_wave(d):
    """Serve one flush of each of five egress sessions on `d`; returns the
    recorder's counters over the run and the report's dispatch stats."""
    spec = cstream.JobSpec(codec="tcomp32", egress=True, gang=True, flush_tuples=512)
    handles = [d.open(spec, topic=f"t{i}") for i in range(MEMBERS)]
    for i, h in enumerate(handles):
        h.push(np.arange(512, dtype=np.uint32) * (i + 1), timestamps=np.zeros(512))
    before = spans.snapshot()
    d.run()
    got = spans.since(before)["counters"]
    return {k: got.get(k, 0) for k in WAVE_COUNTERS}, list(d.report().dispatch_stats.values())


_FOUR_DEVICES = textwrap.dedent(
    """
    import json
    import jax
    from jax.sharding import Mesh
    import numpy as np
    from repro import cstream
    from tests.test_wave_spans import _one_wave, _program_name

    assert jax.device_count() == 4
    counters, stats = _one_wave(cstream.Dispatcher(gang=True, mesh=4))
    mesh = Mesh(np.asarray(jax.devices()), ("data",))
    print(json.dumps({
        "counters": counters,
        "stats": [[s.n_waves, s.n_solo, s.max_wave, s.padded_slots] for s in stats],
        "names": [_program_name(m, mesh) for m in (False, True)],
    }))
    """
)


@pytest.fixture(scope="module")
def four_devices():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4").strip()
    env["PYTHONPATH"] = os.pathsep.join(filter(None, [os.path.join(root, "src"), root,
                                                      env.get("PYTHONPATH")]))
    p = subprocess.run([sys.executable, "-c", _FOUR_DEVICES], cwd=root, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stdout + p.stderr
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_a_wave_on_one_device_counts_its_members_and_no_pad():
    counters, stats = _one_wave(cstream.Dispatcher(gang=True))
    assert counters == {"wave.slots": 5, "wave.pad_slots": 0, "wave.sharded": 0}
    assert [(s.n_waves, s.n_solo, s.max_wave) for s in stats] == [(1, 0, MEMBERS)]
    assert counters["wave.pad_slots"] == sum(s.padded_slots for s in stats)


def test_a_wave_on_four_devices_counts_its_pad_slots_once(four_devices):
    assert four_devices["counters"] == {"wave.slots": 8, "wave.pad_slots": 3, "wave.sharded": 1}
    # one wave of five members, padded by three replicas of member 0
    assert four_devices["stats"] == [[1, 0, MEMBERS, 3]]
    assert four_devices["counters"]["wave.pad_slots"] == sum(s[3] for s in four_devices["stats"])


def test_the_wave_spans_nest_under_server_wave(monkeypatch):
    parents = []
    enter = spans._Span.__enter__

    def record(self):
        stack = getattr(spans._local, "stack", None) or [None]
        parents.append((self.name, getattr(stack[-1], "name", None)))
        return enter(self)

    monkeypatch.setattr(spans._Span, "__enter__", record)
    _one_wave(cstream.Dispatcher(gang=True))
    seen = {n: p for n, p in parents if n in WAVE_SPANS}
    assert seen == {n: "server.wave" for n in WAVE_SPANS}


def _program_name(meta7, mesh):
    """The HLO module name of the gang's flush-step program: the name the
    device trace gives it (`jit_` and all)."""
    from repro.core.pipeline import CompressionPipeline

    d = cstream.Dispatcher(gang=True)
    h = d.open(cstream.JobSpec(codec="tcomp32", egress=True, gang=True, flush_tuples=512),
               topic="n")
    sess = d.sessions[h.topic]
    pipe: CompressionPipeline = sess.pipeline
    s = mesh.size if mesh is not None else 2
    states = pipe.stack_states([sess.state] * s)
    blocks = np.zeros((s, sess.lanes, sess.capacity // sess.lanes), np.uint32)
    masks = np.ones(blocks.shape, bool)
    text = pipe._gang_step_fn(meta7, mesh=mesh).lower(states, blocks, masks).as_text()
    return text.split("module @", 1)[1].split(" ", 1)[0]


@pytest.mark.parametrize("meta7", [False, True], ids=["raw", "meta7"])
@pytest.mark.parametrize("sharded", [False, True], ids=["vmap", "shard_map"])
def test_the_gang_step_program_keeps_masked_step_in_its_name(meta7, sharded):
    """The readers of the flush step's device time find its program by
    `masked_step` in the trace's name, sharded over a mesh or not."""
    import jax
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",)) if sharded else None
    name = _program_name(meta7, mesh)
    assert "masked_step" in name
    assert name.endswith("meta7") == meta7


def test_the_four_device_sharded_program_keeps_masked_step_in_its_name(four_devices):
    assert four_devices["names"] == ["jit_masked_step", "jit_masked_step_meta7"]
