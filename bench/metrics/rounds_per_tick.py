"""Clock: mean ``EpochStats.rounds`` over the window's ticks (a program counter)."""


def read(run):
    ticks = run.ticks
    return sum(t.rounds for t in ticks) / len(ticks) if ticks else None
