"""Stats: ``market.stats`` time (surplus, the won/chosen/payment fetches, γ,
ψ, ``EpochStats``) per window tick, from the program's own spans."""
from bench import program_spans


def read(run):
    return program_spans.per_tick_ms(run, "market.stats")
