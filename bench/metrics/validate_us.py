"""Ingest: mean time of one ``market.validate`` span (``_pack_row`` and the
quantity check of a submit), from the program's own spans."""
from bench import program_spans


def read(run):
    return program_spans.per_call(run, "market.validate", 1e6)
