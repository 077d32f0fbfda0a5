"""Ingest: mean time of one ``market.wal_append`` span (framing and writing
a submit's or withdraw's WAL record), from the program's own spans."""
from bench import program_spans


def read(run):
    return program_spans.per_call(run, "market.wal_append", 1e6)
