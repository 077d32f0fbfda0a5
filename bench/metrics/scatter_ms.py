"""Book upload: ``market.scatter`` time (``MarketBook.device_problem``: the
delta row scatter or a full upload) per window tick, from the program's own
spans."""
from bench import program_spans


def read(run):
    return program_spans.per_tick_ms(run, "market.scatter")
