"""Serving runtime (ServerCore.run's event replay): tuples per step of the
walk, a step being one run of the merged arrival order at which a flush or
a gang edge poll acts; the program's `replay.tuples` over `replay.steps`
(program.py). A program that counts no steps reads None."""
from benchmarks.chip import program


def read(ctx):
    return program.ratio("replay.tuples", "replay.steps")
