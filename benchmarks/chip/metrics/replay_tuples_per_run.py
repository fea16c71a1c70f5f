"""Serving runtime (ServerCore.run's replay): tuples per run of equal topic
in the merged arrival order, each run one offer_many call; the program's
`replay.tuples` over `replay.runs` (program.py)."""
from benchmarks.chip import program


def read(ctx):
    return program.ratio("replay.tuples", "replay.runs")
