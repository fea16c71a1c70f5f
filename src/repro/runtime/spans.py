"""The program's spans and counters: where the served path spends its host
time, and how often it does each piece of work.

`span(name)` times a block with the host clock. Per name it keeps the
calls, the total seconds, and the self seconds: the total less the time of
the spans opened inside it on the same thread. Each span is also a
`jax.profiler.TraceAnnotation` named `cstream.<name>`, so with the profiler
on it lands in the trace on the same clock as the device's programs.
`spanned(name)` makes every call of a function one such span.
`count(name, n)` adds to a counter. `snapshot()` copies both out as plain
dicts, and `since(before)` is what happened after an earlier snapshot.

The recorder is always on and has no switch: a span costs a few
microseconds with the profiler off, so spans wrap calls, never a per-tuple
or per-run loop. A loop keeps a local count and records it once.
"""
from __future__ import annotations

import collections
import functools
import threading
import time
from typing import Any, Callable, Dict, Optional, TypeVar, cast

import jax

F = TypeVar("F", bound=Callable[..., Any])

_lock = threading.Lock()
_local = threading.local()
_calls: Dict[str, int] = collections.defaultdict(int)
_total_s: Dict[str, float] = collections.defaultdict(float)
_self_s: Dict[str, float] = collections.defaultdict(float)
_counters: Dict[str, int] = collections.defaultdict(int)


class _Span:
    __slots__ = ("name", "_ann", "_t0", "_child_s")

    def __init__(self, name: str):
        self.name = name
        self._ann = jax.profiler.TraceAnnotation("cstream." + name)

    def __enter__(self) -> "_Span":
        stack = getattr(_local, "stack", None)
        if stack is None:
            stack = _local.stack = []
        stack.append(self)
        self._child_s = 0.0
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        dur = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        stack = _local.stack
        stack.pop()
        if stack:
            stack[-1]._child_s += dur
        with _lock:
            _calls[self.name] += 1
            _total_s[self.name] += dur
            _self_s[self.name] += dur - self._child_s
        return False


def span(name: str) -> _Span:
    """`with span("server.report"): ...` times the block under `name`; it
    closes and counts when the block raises too."""
    return _Span(name)


def spanned(name: str) -> Callable[[F], F]:
    """Decorator: every call of the function is one `span(name)`."""

    def wrap(fn: F) -> F:
        @functools.wraps(fn)
        def inner(*args: Any, **kwargs: Any) -> Any:
            with _Span(name):
                return fn(*args, **kwargs)

        return cast(F, inner)

    return wrap


def count(name: str, n: int = 1) -> None:
    """Add `n` to the counter `name`."""
    with _lock:
        _counters[name] += n


def snapshot() -> dict:
    """{"spans": {name: {"calls", "total_s", "self_s"}}, "counters": {name: n}}."""
    with _lock:
        return {
            "spans": {
                n: {"calls": c, "total_s": _total_s[n], "self_s": _self_s[n]}
                for n, c in _calls.items()
            },
            "counters": dict(_counters),
        }


def since(before: dict, after: Optional[dict] = None) -> dict:
    """`after` (now, by default) less `before`, in `snapshot()`'s form; a
    span or counter that did not move is left out."""
    after = snapshot() if after is None else after
    zero = {"calls": 0, "total_s": 0.0, "self_s": 0.0}
    spans = {}
    for n, a in after["spans"].items():
        b = before["spans"].get(n, zero)
        if a["calls"] != b["calls"]:
            spans[n] = {k: a[k] - b[k] for k in zero}
    counters = {
        n: v - before["counters"].get(n, 0)
        for n, v in after["counters"].items()
        if v != before["counters"].get(n, 0)
    }
    return {"spans": spans, "counters": counters}
