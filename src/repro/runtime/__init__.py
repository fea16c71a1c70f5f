"""The serving runtime (`server`), its recorder of spans and counters
(`spans`) and the sharding policy (`sharding`).

The names below load on first use: the core's modules record into
`repro.runtime.spans`, and an eager import of `server` here would import
the core from inside its own import.
"""
import importlib

_HOMES = {
    "ServerCore": "server",
    "ServerReport": "server",
    "SessionReport": "server",
    "StreamServer": "server",
    "StreamSession": "server",
    "batch_specs": "sharding",
    "cache_specs": "sharding",
    "param_specs": "sharding",
    "physical_specs": "sharding",
    "resolve": "sharding",
}


def __getattr__(name: str):
    home = _HOMES.get(name)
    if home is None:
        raise AttributeError(f"module 'repro.runtime' has no attribute {name!r}")
    return getattr(importlib.import_module(f"repro.runtime.{home}"), name)
