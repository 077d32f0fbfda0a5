"""Ingest: mean host time of one ``submit``/``withdraw`` call (validate + WAL
append + queue), from the benchmark's own spans around each call."""


def read(run):
    s = run.submit_s
    return float(s.mean() * 1e6) if s.size else None
