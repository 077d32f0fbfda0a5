"""Commit: mean time of one ``market.checkpoint.full`` span (a full
checkpoint: snapshot and write), from the program's own spans."""
from bench import program_spans


def read(run):
    return program_spans.per_call(run, "market.checkpoint.full", 1e3)
