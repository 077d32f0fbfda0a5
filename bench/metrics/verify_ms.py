"""Verify: ``market.verify`` time (``verify_system``) per window tick, from
the program's own spans."""
from bench import program_spans


def read(run):
    return program_spans.per_tick_ms(run, "market.verify")
