"""Tick host path: ``market.drain`` time (the pending queue into the host
book) per window tick, from the program's own spans."""
from bench import program_spans


def read(run):
    return program_spans.per_tick_ms(run, "market.drain")
