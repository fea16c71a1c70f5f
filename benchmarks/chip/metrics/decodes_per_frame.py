"""Collector (DecompressionPipeline.decompress): frame decodes per frame the
collector ingests; the program's `decode.frames` over
`collector.frames_ingested` (program.py)."""
from benchmarks.chip import program


def read(ctx):
    return program.ratio("decode.frames", "collector.frames_ingested")
