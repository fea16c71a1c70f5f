"""What the program's own recorder (`repro.runtime.spans`) has counted in
this process, for the readers of its counters.

`Cell.context()` hands a reader no program counters, so these readers ask
the recorder directly. The recorder counts from the start of the process:
the warm-up step, the window and any traced steps. Every step of a cell
has the same arrivals, waves and frames, so a ratio of two counters is the
window's. A program without the recorder gives None.
"""
from __future__ import annotations

from typing import Optional


def ratio(num: str, den: str) -> Optional[float]:
    """Counter `num` over counter `den`; None where `den` is zero or the
    program has no recorder."""
    try:
        from repro.runtime import spans
    except ImportError:
        return None
    c = spans.snapshot()["counters"]
    return c.get(num, 0) / c[den] if c.get(den) else None
