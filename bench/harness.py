"""One run of one benchmark cell: build the market, warm up, drive the
measured window open loop, check what the window produced, report.

The cell's pieces are files found by name (see ``run_cell.py``): the
deployment (``configs/<config>.json``), the traffic mix
(``traffic/<mix>.json``), the limits of the correctness check
(``limits/<cell>.json``) and one reader per per-layer metric
(``metrics/<metric>.py``).  This module is the same for every cell.

Everything here runs in one process with one thread, which owns the chip.
From the program it uses only the served path: ``fleet_economy`` and
``MarketService.from_economy`` to stand the market up, ``submit`` /
``withdraw`` / ``tick`` / ``poll_prices`` in the window, the device book
(``MarketBook.device_problem``) and a restart from the WAL and checkpoints
afterwards.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import os
import shutil
import sys
import time

import numpy as np

from bench import reference, traffic
from bench import trace as tracemod

BENCH = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(BENCH, "work")  # per-run WAL, checkpoints, trace; wiped per run
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CONTROL_TICKS = 6  # ticks at which a control run settles in bfloat16

clock = time.perf_counter


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


@dataclasses.dataclass
class Tick:
    start: float
    end: float
    boundary: int  # ops [0, boundary) were submitted before this tick
    rounds: int
    converged: bool
    prices: np.ndarray
    psi: np.ndarray
    polled_before: tuple  # (prices, epoch) poll_prices served just before
    polled_after: tuple
    in_window: bool


class Compiles:
    """Counts the backend compiles (or persistent-cache loads) JAX makes.
    JAX keeps its listeners for the life of the process, so there is one."""

    _one = None

    def __init__(self) -> None:
        import jax

        self.events: list[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    @classmethod
    def get(cls) -> "Compiles":
        if cls._one is None:
            cls._one = cls()
        return cls._one

    def _on(self, event: str, duration: float, **_) -> None:
        if event == BACKEND_COMPILE:
            self.events.append(duration)

    def since(self, mark: int) -> tuple[int, float]:
        return len(self.events) - mark, float(sum(self.events[mark:]))


def configure_cache() -> None:
    """JAX's persistent compilation cache in the directory the entry point
    gave ``$JAX_COMPILATION_CACHE_DIR`` (``<checkout>/.jax_cache``), for every
    program however short its compile, so a run after the first in a
    checkout compiles nothing."""
    import jax

    from repro import compile_cache

    compile_cache.configure()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class Cell:
    """The market of one run and everything the window records."""

    def __init__(self, config: dict, mix: dict, seed: int, seconds: float) -> None:
        from repro.core import fleet_economy
        from repro.core.auction import ClockConfig
        from repro.serve import ServiceConfig
        from repro.serve.market import BidDelta, MarketService

        self.BidDelta = BidDelta
        self.MarketService = MarketService
        self.config, self.mix, self.seed, self.seconds = config, mix, seed, float(seconds)
        shutil.rmtree(WORK, ignore_errors=True)
        os.makedirs(WORK)
        clock_cfg = ClockConfig(**config["clock"])
        self.svc_cfg = ServiceConfig(
            clock=clock_cfg,
            wal_path=os.path.join(WORK, "market.wal"),
            checkpoint_dir=os.path.join(WORK, "ckpt"),
            **config["service"],
        )
        t = clock()
        # the deployment is the configuration's, the same for every seed; the
        # seed draws the traffic (a market drawn from the seed changed the
        # clock's work by 2x between seeds on the chip)
        self.eco = fleet_economy(int(config["agents"]), int(config["clusters"]),
                                 seed=int(config["market_seed"]), clock=clock_cfg)
        self.svc = MarketService.from_economy(self.eco, config=self.svc_cfg)
        # the reserve the service quotes before its first tick (float32)
        self.quote, _ = self.svc.poll_prices()
        self.keys, self.idx, self.val, self.mask, self.pi = self.eco.export_bid_rows()
        self.nbundles = self.mask.sum(axis=1)
        book = self.svc.book
        log(f"market: {book.num_rows} rows in {book.rows_cap} slots x {book.num_bundles} "
            f"bundles x {book.k_bound} pools, R = {book.num_resources}, nnz cap "
            f"{book.nnz_cap}; built in {clock() - t:.2f} s")
        self.ops = traffic.Ops.empty()  # every op, in the order it is sent
        self.n_sent = 0  # ops[:n_sent] have been sent
        self.ok = np.zeros(0, bool)
        self.ticks: list[Tick] = []

    # -- the client -----------------------------------------------------------

    def payloads(self, ops: traffic.Ops) -> list:
        """What the client sends for each op: a BidDelta or a key to withdraw."""
        out = []
        for a, kind, s in zip(ops.agent.tolist(), ops.kind.tolist(), ops.scale.tolist()):
            if kind == traffic.WITHDRAW:
                out.append(self.keys[a])
                continue
            nb = int(self.nbundles[a])
            bundles = [(self.idx[a, b], self.val[a, b]) for b in range(nb)]
            out.append(self.BidDelta(self.keys[a], bundles, self.pi[a, :nb] * np.float32(s)))
        return out

    def send(self, payload) -> bool:
        if isinstance(payload, str):
            return self.svc.withdraw(payload)
        return self.svc.submit(payload)

    def tick(self, in_window: bool, span) -> Tick:
        polled_before = self.svc.poll_prices()
        t0 = clock()
        with span("bench.tick"):
            stats = self.svc.tick()
        t1 = clock()
        rec = Tick(t0, t1, self.n_sent, int(stats.rounds), bool(stats.converged),
                   np.array(stats.prices), np.array(stats.psi), polled_before,
                   self.svc.poll_prices(), in_window)
        self.ticks.append(rec)
        return rec

    def _extend(self, ops: traffic.Ops) -> int:
        first = len(self.ops)
        self.ops = traffic.Ops.concat([self.ops, ops])
        self.ok = np.concatenate([self.ok, np.zeros(len(ops), bool)])
        return first

    # -- set-up ---------------------------------------------------------------

    def schedule(self) -> None:
        """Draw the window's ops and build their payloads (set-up work)."""
        agents = np.flatnonzero(self.nbundles > 0)
        self.sched = traffic.window_schedule(self.mix, agents, self.seconds, self.seed)
        self.window_payloads = self.payloads(self.sched.ops)

    def buckets(self) -> list[int]:
        """Power-of-two delta-scatter buckets the window's ticks can reach."""
        period = float(self.mix["tick"]["every_s"])
        edges = np.arange(0.0, self.seconds + period, period)
        bounds = np.searchsorted(self.sched.due, edges)
        n = traffic.distinct_per_batch(self.sched.ops, bounds)
        n = n[n > 0]
        lo, hi = traffic.pow2_ceil(n.min()) // 2, traffic.pow2_ceil(n.max()) * 2
        sizes = {1 << j for j in range(lo.bit_length() - 1, hi.bit_length())}
        return sorted(b for b in sizes if 1 <= b <= self.svc.book.rows_cap)

    def warm_up(self, skip=()) -> set:
        """Settle the bridged book, then one tick per scatter bucket the window
        can reach (but those in ``skip``), each re-pricing that many distinct
        agents.  These ops go through the served path like any other and the
        reference applies them.  Returns the buckets warmed."""
        rng = np.random.default_rng(np.random.SeedSequence([self.seed, 2, len(self.ticks)]))
        agents = np.flatnonzero(self.nbundles > 0)
        lo, hi = (float(x) for x in self.mix["reprice_scale"])
        null = contextlib.nullcontext
        if not self.ticks:
            t = clock()
            self.tick(False, lambda _: null())
            log(f"warm-up: first settle {clock() - t:.2f} s, {self.ticks[-1].rounds} rounds")
        todo = [d for d in self.buckets() if d not in skip]
        for d in todo:
            pick = rng.choice(agents, size=d, replace=False)
            ops = traffic.Ops(pick.astype(np.int64), np.zeros(d, np.int8),
                              rng.uniform(lo, hi, d).astype(np.float32))
            first = self._extend(ops)
            for j, p in enumerate(self.payloads(ops)):
                self.ok[first + j] = self.send(p)
            self.n_sent = len(self.ops)
            t = clock()
            self.tick(False, lambda _: null())
            log(f"warm-up: bucket {d}: tick {clock() - t:.3f} s, {self.ticks[-1].rounds} rounds")
        return set(todo)

    # -- the window -----------------------------------------------------------

    def drive(self, span) -> tuple[float, float]:
        """The measured window; returns (start, end) on the host clock."""
        first = self._extend(self.sched.ops)
        n = len(self.sched.ops)
        pay = self.window_payloads
        self.base = first
        self.sent = np.full(n, np.nan)
        self.acked = np.full(n, np.nan)
        self.due = np.full(n, np.nan)
        ok = self.ok
        send = self.send
        T = self.seconds
        due = self.sched.due
        self.due[:] = due
        period = float(self.mix["tick"]["every_s"])
        t0 = clock()
        next_tick = t0 + period
        i = 0
        while True:
            now = clock()
            if now >= next_tick:
                rec = self.tick(True, span)
                if rec.start - t0 >= T:
                    return t0, rec.end
                next_tick += period
                next_tick = max(next_tick, clock())  # a late tick: the next at once
                continue
            if i < n and t0 + due[i] <= now:
                with span("bench.ingest"):
                    while i < n and t0 + due[i] <= now < next_tick:
                        self.sent[i] = now
                        ok[first + i] = send(pay[i])
                        now = clock()
                        self.acked[i] = now
                        i += 1
                    self.n_sent = first + i
                continue
            wake = min(t0 + due[i] if i < n else np.inf, next_tick)
            with span("bench.idle"):
                time.sleep(max(wake - clock(), 0.0))

    # -- results --------------------------------------------------------------

    def end_to_end(self, t0: float, t1: float) -> dict:
        base = self.base
        due_in = ~np.isnan(self.due)  # the ops that fell due in the window
        n = int(due_in.sum())
        win = [t for t in self.ticks if t.in_window and t.start >= t0]
        ok = self.ok[base:base + self.due.size]
        sent = ~np.isnan(self.sent)
        g = base + np.arange(self.due.size)
        # each op's tick is the first that started after it was sent; a tick
        # that did not converge fails the ops it carried
        bounds = np.array([t.boundary for t in self.ticks], np.int64)
        ends = np.array([t.end for t in self.ticks])
        conv = np.array([t.converged for t in self.ticks])
        k = np.searchsorted(bounds, g, side="right")
        has = k < bounds.size
        kk = np.minimum(k, bounds.size - 1)
        settled = due_in & sent & ok & has & conv[kk]
        settle_t = np.where(settled, ends[kk], np.nan)
        due_abs = t0 + self.due
        ack = (self.acked - due_abs)[due_in & sent & ok]
        settle = (settle_t - due_abs)[settled]
        self.ack_s, self.settle_s = ack, settle
        carried = [t for t in win if not t.converged]
        failed = n - int(settled.sum())
        window_s = t1 - t0
        self.attempted, self.failed = n, failed
        self.window_s = window_s
        log(f"window: {window_s:.3f} s, {len(win)} ticks ({len(carried)} not converged), "
            f"{n} ops due, {int(sent.sum())} sent, {int((sent & ok).sum())} acked, "
            f"{int(settled.sum())} settled")
        late = np.nanmax(self.sent - due_abs) if sent.any() else 0.0
        log(f"generator: latest send {late * 1e3:.3f} ms after its due time")
        return {
            "ack_p95_ms": float(np.percentile(ack, 95) * 1e3) if ack.size else None,
            "settle_p95_ms": float(np.percentile(settle, 95) * 1e3) if settle.size else None,
            "tick_ms": float(sum(t.end - t.start for t in win) / len(win) * 1e3),
            "bids_per_s": float(settled.sum() / window_s),
        }

    def submit_seconds(self) -> np.ndarray:
        s = ~np.isnan(self.sent)
        return (self.acked - self.sent)[s]

    # -- the check ------------------------------------------------------------

    def snapshot(self) -> dict:
        """The device book the last tick settled, read back."""
        prob = self.svc.book.device_problem()
        b, k = self.svc.book.num_bundles, self.svc.book.k_bound
        return {
            "idx": np.asarray(prob.idx).reshape(-1, b, k),
            "val": np.asarray(prob.val).reshape(-1, b, k),
            "mask": np.asarray(prob.bundle_mask),
            "pi": np.asarray(prob.pi),
        }

    def restart(self) -> tuple[dict, int, float]:
        """Drop the service and stand it up again from its WAL and checkpoints."""
        self.svc = None
        gc.collect()
        t = clock()
        svc = self.MarketService.from_economy(self.eco, config=self.svc_cfg)
        dt = clock() - t
        bk = svc.book
        b, k = bk.num_bundles, bk.k_bound
        book = {"idx": bk.idx.reshape(-1, b, k), "val": bk.val.reshape(-1, b, k),
                "mask": bk.mask, "pi": bk.pi}
        return book, svc.pending, dt


# -- comparison ---------------------------------------------------------------


def _row_digests(idx, val, mask, pi, chunk: int = 1 << 17) -> np.ndarray:
    """Two 64-bit digests per row of its exact bits, as one (n,) void array:
    random-weight sums of the row's 32-bit words modulo 2^64."""
    n = idx.shape[0]
    parts = (idx.reshape(n, -1).view(np.uint32), val.reshape(n, -1).view(np.uint32),
             mask.reshape(n, -1).astype(np.uint32), pi.reshape(n, -1).view(np.uint32))
    width = sum(p.shape[1] for p in parts)
    w = np.random.default_rng(0x5EED).integers(1, 2**63, size=(width, 2), dtype=np.uint64)
    h = np.empty((n, 2), np.uint64)
    for lo in range(0, n, chunk):
        words = np.concatenate([p[lo:lo + chunk] for p in parts], axis=1).astype(np.uint64)
        with np.errstate(over="ignore"):
            for i in range(2):
                h[lo:lo + chunk, i] = (words * w[:, i]).sum(axis=1, dtype=np.uint64)
    return h.view(np.dtype((np.void, 16))).reshape(-1)


def book_mismatch(got: dict, ref: reference.Book) -> int:
    """Rows in which a program book differs from the reference's.

    Live rows must match byte for byte, as a multiset: which slot holds a
    bid is the program's to choose.  Slots holding no bid must be all zero."""
    idx, val, mask, pi = got["idx"], got["val"], got["mask"], got["pi"]
    live = mask.any(axis=1)
    a = _row_digests(idx[live], val[live], mask[live], pi[live])
    b = _row_digests(*ref.live_rows())
    ua, ca = np.unique(a, return_counts=True)
    ub, cb = np.unique(b, return_counts=True)
    _, ia, ib = np.intersect1d(ua, ub, return_indices=True)
    matched = int(np.minimum(ca[ia], cb[ib]).sum())
    dead = ~live
    stale = (np.any(idx[dead] != 0, axis=(1, 2)) | np.any(val[dead] != 0, axis=(1, 2))
             | np.any(pi[dead] != 0, axis=1))
    return (a.size - matched) + (b.size - matched) + int(stale.sum())


def reference_book(cell: Cell) -> tuple[reference.Book, float]:
    """The reference's book, and how far the service's quoted reserve lies
    from the reference's own (relative, worst pool).

    The operators sell at the quoted reserve, and every clock starts there.
    Pools that nobody over-demands stay at that price, where the operator's
    surplus is zero up to rounding: whether it sells then turns on the last
    bit of the reserve.  So the reference settles at the quote's exact bits,
    and holds the quote itself to its own reserve by ``reserve_gap``."""
    eco = cell.eco
    ref_reserve = reference.exp_reserve(eco.capacity, eco.usage, eco.base_cost_rt,
                                        cell.config["reserve"])
    quote = np.asarray(cell.quote, np.float64)
    gap = float(np.max(np.abs(quote - ref_reserve) / ref_reserve))
    return reference.Book(cell.idx, cell.val, cell.mask, cell.pi, eco.capacity, eco.usage,
                          quote), gap


def check_ticks(cell: Cell, control: set = frozenset()) -> dict:
    """Replay the acknowledged ops into the reference book tick by tick and,
    at every converged tick, compare the program's committed prices and
    allocation with the reference settle of the same book; at the ticks in
    ``control``, also read the bfloat16 control."""
    import jax.numpy as jnp

    book, reserve_gap = reference_book(cell)
    reserve = np.asarray(cell.quote, np.float64)
    eco = cell.eco
    base_cost = np.tile(np.asarray(eco.base_cost_rt, np.float64), eco.C)
    clk = cell.config["clock"]
    warm = bool(cell.config["service"].get("warm_start", True))
    out = {"reserve_gap": reserve_gap, "price_gap": 0.0, "excess": 0.0, "alloc_gap": 0.0,
           "poll_off": 0}
    ctl = {"reserve_gap": 0.0, "price_gap": 0.0, "excess": 0.0, "alloc_gap": 0.0}
    if control:  # the reserve held in bfloat16: the least error a bfloat16 quote has
        ref64 = reference.exp_reserve(eco.capacity, eco.usage, eco.base_cost_rt,
                                      cell.config["reserve"])
        bf16 = np.asarray(jnp.asarray(ref64, jnp.bfloat16), np.float64)
        ctl["reserve_gap"] = float(np.max(np.abs(bf16 - ref64) / ref64))
    served = None  # what poll_prices should serve: the last committed prices
    prev = 0
    t_ref = clock()
    for j, t in enumerate(cell.ticks):
        sel = np.arange(prev, t.boundary)
        book.apply(traffic.Ops(cell.ops.agent[sel][cell.ok[sel]], cell.ops.kind[sel][cell.ok[sel]],
                               cell.ops.scale[sel][cell.ok[sel]]))
        prev = t.boundary
        # poll_prices serves the last converged tick's prices, bit for bit
        exp_before = served
        if t.converged:
            served = t.prices.astype(np.float32)
        for (got, _), want in ((t.polled_before, exp_before), (t.polled_after, served)):
            if want is not None and not np.array_equal(np.asarray(got, np.float32), want):
                out["poll_off"] += 1
        if not t.converged:
            continue
        prev_p = np.asarray(t.polled_before[0], np.float64)
        start = np.maximum(prev_p, reserve) if warm and t.polled_before[1] >= 0 else reserve
        p_ref, _, _ = reference.settle(book, base_cost, start, clk)
        s_ref = book.supply_scale().astype(np.float64)
        bound = reference.clock_step_bound(clk, p_ref, base_cost)
        z, psi = reference.outcome(book, t.prices)
        gap = float(np.max(np.abs(t.prices - p_ref) / bound))
        exc = float(np.max(np.maximum(z, 0.0) / s_ref))
        alloc = float(np.max(np.abs(t.psi - psi)))
        out["price_gap"] = max(out["price_gap"], gap)
        out["excess"] = max(out["excess"], exc)
        out["alloc_gap"] = max(out["alloc_gap"], alloc)
        if j in control:
            p_c, _, _ = reference.settle(book, base_cost, start, clk, dtype=jnp.bfloat16)
            _, psi_c = reference.outcome(book, p_c, dtype=jnp.bfloat16)
            z_c, psi_r = reference.outcome(book, p_c)
            ctl["price_gap"] = max(ctl["price_gap"], float(np.max(np.abs(p_c - p_ref) / bound)))
            ctl["excess"] = max(ctl["excess"], float(np.max(np.maximum(z_c, 0.0) / s_ref)))
            ctl["alloc_gap"] = max(ctl["alloc_gap"], float(np.max(np.abs(psi_c - psi_r))))
    log(f"reference: {len(cell.ticks)} ticks settled and compared in {clock() - t_ref:.2f} s")
    return {"checks": out, "control": ctl, "book": book}


def control_ticks(cell: Cell, seed: int, n: int = CONTROL_TICKS) -> set:
    """The ticks at which the control settles: ``n`` window ticks drawn from
    the seed, always holding the window's last."""
    win = [j for j, t in enumerate(cell.ticks) if t.in_window]
    if n >= len(win):
        return set(win)
    rng = np.random.default_rng(np.random.SeedSequence([seed, 3]))
    return set(rng.choice(win[:-1], size=n - 1, replace=False).tolist()) | {win[-1]}


# -- one run ------------------------------------------------------------------


def run(config: dict, mix: dict, limits: dict, e2e_names: dict, readers: dict, seed: int,
        seconds: float, trace: bool, peaks: dict | None, t_start: float,
        control: bool = False) -> dict:
    """One run of a cell; returns the result line's fields.  With ``control``
    the bfloat16 control takes the program's place in the verdict
    (``correct``, ``checks``) and the program's readings move to
    ``program_checks``.  ``readers`` maps each per-layer metric of the
    cell to ``(unit, read)``; ``e2e_names`` maps its end-to-end metrics to
    their units."""
    import jax

    configure_cache()
    compiles = Compiles.get()
    first_event = len(compiles.events)
    span = (lambda name: jax.profiler.TraceAnnotation(name)) if trace else (
        lambda name: contextlib.nullcontext())
    cell = Cell(config, mix, seed, seconds)
    cell.schedule()
    cell.warm_up()
    mark = len(compiles.events)
    n_setup, s_setup = compiles.since(first_event)
    log(f"set-up: {n_setup} compiles or cache loads, {s_setup:.2f} s")
    trace_dir = os.path.join(WORK, "trace")
    if trace:
        jax.profiler.start_trace(trace_dir)
    setup_s = clock() - t_start
    with span("bench.window"):
        t0, t1 = cell.drive(span)
    if trace:
        jax.profiler.stop_trace()
    n_comp, s_comp = compiles.since(mark)
    log(f"compiles inside the window: {n_comp} ({s_comp:.3f} s)")
    e2e = cell.end_to_end(t0, t1)
    e2e["setup_s"] = setup_s
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": jax.device_count(),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    result = {"attempted": cell.attempted, "failed": cell.failed, "device": device}

    if trace:
        tr = tracemod.load(trace_dir)
        lo, hi = tr.span("bench.window")
        device["busy_s"] = tracemod.busy_ns(tr, lo, hi) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        ctx = RunContext(cell, tr, (lo, hi), peaks)
        metrics = {}
        for name, (unit, reader) in readers.items():
            v = reader(ctx)
            if v is not None:
                metrics[name] = {"value": float(v), "unit": unit}
        result["metrics"] = metrics
        result["breakdown"] = {"device_ops": tracemod.top_ops(tr, lo, hi),
                               "idle_gaps": tracemod.idle_gaps(tr, lo, hi)}
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        result["metrics"] = {k: {"value": e2e[k], "unit": unit} for k, unit in e2e_names.items()
                             if e2e.get(k) is not None}

    # -- correctness, after the window and the memory reading --------------
    t = clock()
    got = cell.snapshot()
    restored, pending, restart_s = cell.restart()
    log(f"restart from WAL and checkpoints: {restart_s:.2f} s, {pending} pending")
    rep = check_ticks(cell, control_ticks(cell, seed) if control else set())
    checks = dict(rep["checks"])
    checks["book_rows_off"] = book_mismatch(got, rep["book"])
    checks["durable_missing"] = book_mismatch(restored, rep["book"]) + int(pending)
    log(f"check took {clock() - t:.2f} s")
    shutil.rmtree(WORK, ignore_errors=True)
    verdict = {k: {"value": v, "limit": limits[k]} for k, v in checks.items()}
    if control:
        # the bfloat16 reference in the program's place: its readings decide,
        # and the program's own are kept beside them
        result["program_checks"] = verdict
        verdict = {k: {"value": v, "limit": limits[k]} for k, v in rep["control"].items()}
    result["correct"] = all(v["value"] <= v["limit"] for v in verdict.values())
    result["checks"] = verdict
    return result


@dataclasses.dataclass
class RunContext:
    """What a per-layer metric reader sees of a traced run."""

    cell: Cell
    trace: tracemod.Trace
    window: tuple  # (lo, hi) ns on the trace's clock
    peaks: dict | None

    @property
    def ticks(self) -> list:
        return [t for t in self.cell.ticks if t.in_window]

    @property
    def tick_spans(self) -> list:
        lo, hi = self.window
        return [(s, e) for s, e in self.trace.spans_named("bench.tick") if lo <= s < hi]

    @property
    def submit_s(self) -> np.ndarray:
        return self.cell.submit_seconds()

    @property
    def ack_s(self) -> np.ndarray:
        """Each acknowledged bid's due → ack, seconds."""
        return self.cell.ack_s

    @property
    def settle_s(self) -> np.ndarray:
        """Each settled bid's due → commit of its tick, seconds."""
        return self.cell.settle_s
