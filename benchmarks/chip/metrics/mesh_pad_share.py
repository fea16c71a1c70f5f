"""Gang step (runtime/server.py _run_wave on a fleet mesh): pad replicas
over the rows the gang waves dispatched, in %, from the program's counters
`wave.pad_slots` and `wave.slots`; None where the program counts no
waves."""
from benchmarks.chip import program


def read(ctx):
    r = program.ratio("wave.pad_slots", "wave.slots")
    return None if r is None else 100.0 * r
