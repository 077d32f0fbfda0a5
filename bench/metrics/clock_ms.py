"""Clock: ``market.clock`` time (the settle and its escalations, up to the
read of ``converged``) per window tick, from the program's own spans."""
from bench import program_spans


def read(run):
    return program_spans.per_tick_ms(run, "market.clock")
