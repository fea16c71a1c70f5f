"""Framing (CompressionPipeline.marshal_frame / marshal_compacted): frames
marshalled per frame StreamHandle.frames() delivers; the program's
`egress.frames_marshalled` over `handle.frames_delivered` (program.py)."""
from benchmarks.chip import program


def read(ctx):
    return program.ratio("egress.frames_marshalled", "handle.frames_delivered")
