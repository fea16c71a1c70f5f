"""Elastic scaling: remesh a running job to a different device count.

Checkpoints are mesh-independent (checkpoint/manager.py stores global
arrays in chunked slabs), so elasticity is a *policy* layer:

  plan_mesh(n_devices)       — pick (data, model) [(pod, data, model)]
                               factors for the devices that are actually
                               healthy, preferring the model axis at 16
                               (the TP degree every arch was validated at)
                               and folding the remainder into data/pod;
  reshard(tree, old->new)    — device_put onto the new mesh's shardings
                               (load_checkpoint does the same from disk);
  ElasticSession             — drives shrink/grow across segment restarts:
                               on failure of K nodes, re-plan with N-K,
                               restore, continue — tested on CPU meshes.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh

from repro.models import partition
from repro.runtime import sharding as shpol


def plan_mesh(
    n_devices: int,
    prefer_model: int = 16,
    multi_pod_at: int = 512,
    profile: str = "lm",
) -> Tuple[Tuple[int, ...], Tuple[str, ...]]:
    """Factor the healthy device count into a mesh shape.

    profile="lm" (default): keeps the model axis at the largest power-of-two
    divisor <= prefer_model (TP degree changes force a different expert/head
    partition; we avoid exceeding the validated 16), splits off a pod axis
    for very large jobs.

    profile="cstream": pure data-axis mesh, `(n,), ("data",)` for ANY device
    count including non-powers-of-two. The serving fleet shards gang waves
    over sessions — there is no model axis to keep 16-wide, and the LM
    factoring would reject prime counts like 3/5/7 survivors of a device
    loss into a degenerate (n, 1) shape carrying a dead "model" name."""
    if n_devices < 1:
        raise ValueError(f"plan_mesh needs >= 1 device, got {n_devices}")
    if profile == "cstream":
        return (n_devices,), ("data",)
    if profile != "lm":
        raise ValueError(f"unknown mesh profile {profile!r}; use 'lm' or 'cstream'")
    model = 1
    for cand in (prefer_model, 8, 4, 2, 1):
        if n_devices % cand == 0:
            model = cand
            break
    rest = n_devices // model
    if n_devices >= multi_pod_at and rest % 2 == 0:
        return (2, rest // 2, model), ("pod", "data", "model")
    return (rest, model), ("data", "model")


def logical_mapping(axis_names: Tuple[str, ...]) -> dict:
    if "pod" in axis_names:
        return {"data": ("pod", "data"), "model": "model"}
    if "model" not in axis_names:  # cstream fleet mesh: data axis only
        return {"data": "data"}
    return {"data": "data", "model": "model"}


def make_mesh_for(n_devices: int, devices=None, profile: str = "lm"):
    """Mesh + logical mapping for `n_devices`. `devices` pins an explicit
    (healthy) device list — required when meshing a strict subset of the
    visible devices, e.g. after a device loss."""
    shape, names = plan_mesh(n_devices, profile=profile)
    # Auto axes (jax.make_mesh defaults to Explicit ones): a sharded wave
    # output stays indexable member by member
    axis_types = (AxisType.Auto,) * len(names)
    if devices is None and n_devices == jax.device_count():
        # every visible device: let jax lay the mesh out along the topology
        return jax.make_mesh(shape, names, axis_types), logical_mapping(names)
    if devices is None:
        devices = jax.devices()[:n_devices]
    # a strict subset (e.g. the survivors of a device loss) is laid out as
    # given: a topology-ordered builder may refuse or reorder it
    mesh = Mesh(np.asarray(devices, dtype=object).reshape(shape), names, axis_types)
    return mesh, logical_mapping(names)


def reshard(tree: Any, logical_specs: Any, mesh, mapping: dict) -> Any:
    """device_put a live pytree onto a (new) mesh per its logical specs."""
    with partition.logical_axes(mapping):
        shardings = shpol.resolve(logical_specs, mesh)
    return jax.tree_util.tree_map(jax.device_put, tree, shardings)


@dataclasses.dataclass
class ElasticSession:
    """Tracks the current mesh and re-plans when the healthy set changes."""

    n_devices: int
    mesh: Any = None
    mapping: Optional[dict] = None
    profile: str = "lm"
    devices: Any = None  # explicit healthy device list (None = first n visible)

    def __post_init__(self):
        if self.mesh is None:
            self.mesh, self.mapping = make_mesh_for(
                self.n_devices, devices=self.devices, profile=self.profile
            )

    def resize(self, new_n: int, devices=None):
        """Shrink (node loss) or grow (nodes returned). Returns self.

        `devices` pins the surviving device list explicitly — after a loss
        the healthy set is NOT a prefix of `jax.devices()`, so the fleet
        recovery path must name the survivors it re-meshes onto."""
        self.n_devices = new_n
        self.devices = devices
        self.mesh, self.mapping = make_mesh_for(
            new_n, devices=devices, profile=self.profile
        )
        return self

    def shardings_for(self, logical_specs: Any) -> Any:
        with partition.logical_axes(self.mapping):
            return shpol.resolve(logical_specs, self.mesh)
