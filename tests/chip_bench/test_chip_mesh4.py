"""The `lossless.mesh4` cell driven on four virtual CPU devices at a tiny
size, in a child process (the device count is fixed when JAX starts): its
sharded gang waves need pad slots, every rotation comes back exact, its
frames are byte-identical to a one-device run of the same seed and
sessions, and the control and a planted fault are not correct."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
#: two sessions per group: every signature's wave holds 2 or 6 members, so
#: each pads to a multiple of the four devices
SESSIONS = 28
SEED = 2**32 + 4099

_CHILD = textwrap.dedent(
    """
    import json
    import sys

    import jax
    import numpy as np

    from benchmarks.chip import fleet, registry, run
    from benchmarks.chip.harness import Cell
    from repro.core import bits
    from repro.core.pipeline import DecompressionPipeline
    from repro.runtime import spans

    sessions, seed = int(sys.argv[1]), int(sys.argv[2])
    assert jax.device_count() == 4
    wires = []
    to_bytes = bits.Frame.to_bytes

    def recording(self, *a, **kw):
        b = to_bytes(self, *a, **kw)
        wires.append(b)
        return b

    bits.Frame.to_bytes = recording

    def cell(chips, control=False):
        wl = registry.workload("lossless.mesh4")
        wl.config = dict(wl.config, sessions=sessions)
        wl.chips = chips
        c = Cell(wl, seed, seconds=0, control=control)
        c.setup()
        # the trained dictionary is the same for every cell of the process
        fleet.train_dictionaries = lambda config: None
        return c

    def window(c):
        del wires[:]
        c.run_window()  # seconds=0: one step
        c.check()
        return list(wires), run.result(c, c.wl, True, {}, registry.load_peaks("TPU v5 lite"))

    out = {}
    before = spans.snapshot()
    mesh = cell(4)
    mesh_wires, line = window(mesh)
    counted = spans.since(before)["counters"]
    out["mesh"] = {
        "correct": line["correct"], "checks": line["checks"], "metrics": line["metrics"],
        "devices": mesh.report.devices,
        "counters": {k: counted.get(k, 0) for k in ("wave.slots", "wave.pad_slots", "wave.sharded")},
        "padded_slots": sum(s.padded_slots for s in mesh.report.dispatch_stats.values()),
        "waves": sum(s.n_waves for s in mesh.report.dispatch_stats.values()),
    }

    ingest = DecompressionPipeline.ingest

    def altered(self, buf):
        res = ingest(self, buf)
        if res.values.size and not out.get("altered"):
            out["altered"] = True
            res.values = res.values.copy()
            res.values[0] ^= np.uint32(1 << 20)
        return res

    DecompressionPipeline.ingest = altered
    _, line = window(mesh)
    DecompressionPipeline.ingest = ingest
    out["answer_altered"] = {"correct": line["correct"], "failed": line["failed"]}

    one = cell(1)
    one_wires, line = window(one)
    out["one"] = {"correct": line["correct"], "devices": one.report.devices}
    out["wires"] = {"n": len(mesh_wires), "bytes": sum(map(len, mesh_wires)),
                    "equal": mesh_wires == one_wires}

    _, line = window(cell(4, control=True))
    out["control"] = {"correct": line["correct"], "checks": line["checks"]}
    print(json.dumps(out))
    """
)


@pytest.fixture(scope="module")
def mesh4():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4").strip()
    env["PYTHONPATH"] = os.pathsep.join(filter(None, [str(ROOT / "src"), str(ROOT),
                                                      env.get("PYTHONPATH")]))
    p = subprocess.run([sys.executable, "-c", _CHILD, str(SESSIONS), str(SEED)], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-4000:] + p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_the_sharded_cell_is_correct(mesh4):
    m = mesh4["mesh"]
    assert m["devices"] == 4
    assert m["correct"] is True
    assert m["checks"] == {"undecoded": {"value": 0, "limit": 0},
                           "mismatched": {"value": 0, "limit": 0}}


def test_its_waves_are_sharded_and_padded(mesh4):
    c = mesh4["mesh"]["counters"]
    assert c["wave.sharded"] == mesh4["mesh"]["waves"] > 0
    assert c["wave.pad_slots"] > 0
    assert c["wave.pad_slots"] == mesh4["mesh"]["padded_slots"]
    assert c["wave.slots"] % 4 == 0


def test_its_layer_metrics_read_the_mesh(mesh4):
    got = {k: v["value"] for k, v in mesh4["mesh"]["metrics"].items()}
    c = mesh4["mesh"]["counters"]
    assert got["mesh_pad_share"] == pytest.approx(100.0 * c["wave.pad_slots"] / c["wave.slots"])
    assert 0 < got["mesh_pad_share"] < 100
    assert got["flushes_per_dispatch.mesh4"] >= 1
    assert got["server_host_s_per_mb.mesh4"] > 0
    assert "compiles_in_window.mesh4" in got
    # no device trace on the CPU
    assert "device_idle_share.mesh4" not in got and "flush_step_roofline.mesh4" not in got


def test_its_frames_are_byte_identical_to_one_device(mesh4):
    assert mesh4["one"] == {"correct": True, "devices": 1}
    assert mesh4["wires"]["n"] > 0 and mesh4["wires"]["bytes"] > 0
    assert mesh4["wires"]["equal"] is True


def test_a_planted_fault_is_not_correct(mesh4):
    assert mesh4["altered"] is True
    assert mesh4["answer_altered"]["correct"] is False
    assert mesh4["answer_altered"]["failed"] > 0


def test_the_control_is_not_correct(mesh4):
    assert mesh4["control"]["correct"] is False
    assert mesh4["control"]["checks"]["mismatched"]["value"] > 0
