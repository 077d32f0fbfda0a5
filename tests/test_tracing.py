"""Spans and the compile counter of the served market (``repro.tracing``).

A tiny durable service is driven through binding ticks with submits and
withdraws while the profiler collects; its ``market.*`` spans are read back
from the trace.  The same drive without the profiler must give the same
bits: the spans add no synchronisation and change no result."""
import dataclasses
import glob
import os
import time

import jax
import numpy as np

from repro import tracing
from repro.core import fleet_economy
from repro.serve import ServiceConfig
from repro.serve.market import BidDelta, MarketService

AGENTS, CLUSTERS = 300, 3
NEW_KEYS = 250  # enough fresh keys in the last tick to double the book's slots

TICK_CHILDREN = ["drain", "scatter", "clock", "verify", "stats", "commit"]
PARENT = {
    **{f"market.{c}": ("market.tick",) for c in TICK_CHILDREN},
    "market.validate": ("market.submit",),
    "market.wal_append": ("market.submit", "market.withdraw"),
    "market.commit.wait": ("market.commit",),
    "market.checkpoint.full": ("market.commit",),
    "market.checkpoint.delta": ("market.commit",),
    "market.wal.truncate": ("market.commit",),
    "market.checkpoint.snapshot": ("market.checkpoint.full", "market.checkpoint.delta"),
    "market.checkpoint.write": ("market.checkpoint.full", "market.checkpoint.delta"),
}
ALL = sorted(set(PARENT) | {"market.tick", "market.submit", "market.withdraw",
                            "market.wal.sync"})


def _service(directory):
    eco = fleet_economy(AGENTS, CLUSTERS, seed=0)
    # after the bootstrap full, a delta checkpoint at each of the first three
    # ticks (the warm-up tick and two driven ones), a full at the fourth
    cfg = ServiceConfig(
        wal_path=os.path.join(directory, "market.wal"),
        checkpoint_dir=os.path.join(directory, "ckpt"),
        checkpoint_interval=1,
        checkpoint_full_every=3,
    )
    return eco, MarketService.from_economy(eco, config=cfg)


def _drive(eco, svc):
    """Three binding ticks of re-prices and withdraws; the last one also
    brings enough fresh keys to double the book.  Returns each tick's
    EpochStats, with the ``perf_counter`` interval of the last tick."""
    keys, idx, val, mask, pi = eco.export_bid_rows()
    live = np.flatnonzero(mask.any(axis=1))
    rng = np.random.default_rng(7)

    def bid(i, key, scale):
        nb = int(mask[i].sum())
        return BidDelta(key, [(idx[i, b], val[i, b]) for b in range(nb)], pi[i, :nb] * scale)

    out, last = [], None
    for t in range(3):
        for i in rng.choice(live, size=20, replace=False):
            assert svc.submit(bid(i, keys[i], np.float32(rng.uniform(0.9, 1.1))))
        for i in rng.choice(live, size=3, replace=False):
            svc.withdraw(keys[i])
        if t == 2:
            for j in range(NEW_KEYS):
                assert svc.submit(bid(live[j % live.size], f"new-{j}", np.float32(1.0)))
        t0 = time.perf_counter()
        out.append(svc.tick())
        last = (t0, time.perf_counter())
    svc.flush()
    return out, last


def _spans(log_dir):
    (path,) = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"), recursive=True)
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for n, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(tracing.PREFIX):
                    s = int(e.start_ns)
                    out.append((e.name, s, s + int(e.duration_ns), (plane.name, n),
                                dict(e.stats)))
    return sorted(out, key=lambda x: x[1])


def _profiled_run(tmp_path):
    eco, svc = _service(str(tmp_path / "svc"))
    svc.tick()  # the first settle compiles outside the trace
    with jax.profiler.trace(str(tmp_path / "trace")):
        stats, _ = _drive(eco, svc)
    return svc, stats, _spans(tmp_path / "trace")


def test_spans_nest_and_name_the_tick_that_drains_a_bid(tmp_path):
    svc, _, spans = _profiled_run(tmp_path)
    names = {s[0] for s in spans}
    assert set(ALL) <= names, sorted(set(ALL) - names)

    # each child lies inside a parent of the expected name on its thread
    for name, s, e, thread, _ in spans:
        if name in PARENT:
            assert any(p[0] in PARENT[name] and p[3] == thread and p[1] <= s and e <= p[2]
                       for p in spans), name

    ticks = [x for x in spans if x[0] == "market.tick"]
    assert len(ticks) == 3 and all(t[4]["dry_run"] == 0 for t in ticks)
    for t in ticks:
        kids = [x for x in spans if x[0] in PARENT and PARENT[x[0]] == ("market.tick",)
                and t[1] <= x[1] and x[2] <= t[2]]
        assert [k[0] for k in kids] == [f"market.{c}" for c in TICK_CHILDREN]
    kinds = [x[0] for x in spans if x[0].startswith("market.checkpoint.")
             and x[0].split(".")[-1] in ("full", "delta")]
    assert kinds == ["market.checkpoint.delta", "market.checkpoint.delta",
                     "market.checkpoint.full"]
    scatters = [x[4] for x in spans if x[0] == "market.scatter"]
    assert [s["kind"] for s in scatters] == ["delta", "delta", "full"]
    assert scatters[-1]["bucket"] == svc.book.rows_cap

    # a request and the tick that drains it carry the same epoch
    for name, s, e, _, st in spans:
        if name in ("market.submit", "market.withdraw"):
            drained_by = next(t for t in ticks if t[1] >= e)
            assert st["epoch"] == drained_by[4]["epoch"]
    assert [t[4]["epoch"] for t in ticks] == [1, 2, 3]


def test_the_compile_counter_names_the_program_a_doubled_book_recompiles(tmp_path):
    jax.clear_caches()  # no program of another test's book stands in for ours
    eco, svc = _service(str(tmp_path / "svc"))
    svc.tick()
    rows_cap = svc.book.rows_cap
    seen = len(tracing.compiles())
    _, (t0, t1) = _drive(eco, svc)
    assert svc.book.rows_cap == 2 * rows_cap
    in_tick = [(f, d) for end, f, d in tracing.compiles()[seen:] if t0 <= end <= t1]
    assert all(d > 0 for _, d in in_tick)
    assert "jit(_clock_auction_csr_padded)" in {f for f, _ in in_tick}, in_tick


def _same(a, b) -> bool:
    if isinstance(a, np.ndarray) or isinstance(b, np.ndarray):
        a, b = np.asarray(a), np.asarray(b)
        return a.dtype == b.dtype and a.shape == b.shape and a.tobytes() == b.tobytes()
    if isinstance(a, float) and isinstance(b, float) and np.isnan(a) and np.isnan(b):
        return True
    return type(a) is type(b) and a == b


def test_a_profiled_run_is_bit_identical_to_an_unprofiled_one(tmp_path):
    svc_p, stats_p, _ = _profiled_run(tmp_path / "p")
    eco, svc = _service(str(tmp_path / "u" / "svc"))
    svc.tick()
    stats_u, _ = _drive(eco, svc)
    assert len(stats_p) == len(stats_u) == 3
    for sp, su in zip(stats_p, stats_u):
        dp, du = dataclasses.asdict(sp), dataclasses.asdict(su)
        assert dp.keys() == du.keys()
        off = [k for k in dp if not _same(dp[k], du[k])]
        assert not off, off
    (prices_p, epoch_p), (prices_u, epoch_u) = svc_p.poll_prices(), svc.poll_prices()
    assert epoch_p == epoch_u and _same(prices_p, prices_u)
    arrays_p, meta_p = svc_p.book.export_state()
    arrays_u, meta_u = svc.book.export_state()
    assert meta_p == meta_u and arrays_p.keys() == arrays_u.keys()
    assert all(_same(arrays_p[k], arrays_u[k]) for k in arrays_p)


def test_the_service_entry_point_profiles_its_ticks(tmp_path, capsys, monkeypatch):
    from repro import compile_cache
    from repro.serve import market

    # the entry point turns on the persistent compilation cache for the whole
    # process; keep this test's process as the other tests expect it
    monkeypatch.setattr(compile_cache, "configure", lambda: None)
    assert market.main(["--agents", "200", "--ticks", "2", "--durable-dir",
                        str(tmp_path / "svc"), "--profile", str(tmp_path / "trace")]) == 0
    ticks = [s for s in _spans(tmp_path / "trace") if s[0] == "market.tick"]
    assert [t[4]["epoch"] for t in ticks] == [0, 1]
    assert "tick 1:" in capsys.readouterr().out


def test_the_snapshot_span_counts_the_accounts_it_encoded(tmp_path, monkeypatch):
    eco, svc = _service(str(tmp_path / "svc"))
    svc.tick()
    book, expected = svc.book, []
    for name, full in (("export_state", True), ("export_dirty_state", False)):
        def counted(*args, _export=getattr(book, name), _full=full, **kwargs):
            # the oracle: the book's slot keys and raw accounts, not its mirror
            slots = range(book.rows_cap) if _full else sorted(book._ckpt_dirty)
            keys = [book._slot_key[s] for s in slots if book._slot_key[s] is not None]
            expected.append({"accounts": len(keys),
                             "raw": sum(len(book._accounts[k]) == 2 for k in keys)})
            return _export(*args, **kwargs)
        monkeypatch.setattr(book, name, counted)
    with jax.profiler.trace(str(tmp_path / "trace")):
        _drive(eco, svc)
    snaps = [x[4] for x in _spans(tmp_path / "trace") if x[0] == "market.checkpoint.snapshot"]
    assert [{k: s[k] for k in ("accounts", "raw")} for s in snaps] == expected
    # two deltas of re-prices alone, then a full over packed and raw accounts
    assert len(expected) == 3 and all(0 < e["raw"] == e["accounts"] for e in expected[:2])
    assert expected[2]["accounts"] == book.num_rows
    assert 0 < expected[2]["raw"] < expected[2]["accounts"]
