"""Benchmark harness — one entry per paper table/figure, plus the settlement
scaling claim and the dry-run roofline summary.

Prints ``name,us_per_call,derived`` CSV rows (one per benchmark), where
``derived`` is the benchmark's headline number (see each function's doc).

    PYTHONPATH=src python -m benchmarks.run            # all
    PYTHONPATH=src python -m benchmarks.run fig6 table1
    PYTHONPATH=src python -m benchmarks.run --json bid_eval_sparse  # + BENCH_settlement.json

``--json`` additionally writes ``BENCH_settlement.json`` (one record per
benchmark: name, us_per_call, derived) so the perf trajectory is tracked
across PRs.
"""
from __future__ import annotations

import glob
import json
import os
import subprocess
import sys
import time

import numpy as np


def _timeit(fn, n=5, warmup=2):
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n * 1e6  # us


def fig2_weighting():
    """Paper Fig. 2 — utilization-weighted pricing curves.
    derived: φ(0.99)/φ(0.80) for the default exp curve (congestion spread)."""
    import jax.numpy as jnp
    from repro.core import CURVE_FAMILIES

    psi = jnp.linspace(0.0, 1.0, 11)
    rows = {}
    for name, phi in CURVE_FAMILIES.items():
        rows[name] = np.asarray(phi(psi)).round(3).tolist()
    us = _timeit(lambda: np.asarray(CURVE_FAMILIES["exp"](psi)))
    phi = CURVE_FAMILIES["exp"]
    spread = float(phi(np.float32(0.99)) / phi(np.float32(0.80)))
    print(f"# fig2 curves at psi=0..1 step .1: {json.dumps(rows)}", file=sys.stderr)
    return us, round(spread, 3)


def _economy_stats(epochs=6, seed=3):
    from repro.core.economy import make_fleet_economy

    eco = make_fleet_economy(seed=seed)
    return eco, [eco.run_epoch() for _ in range(epochs)]


def table1_premiums():
    """Paper Table I — bid premium γ statistics over successive auctions.
    derived: median γ of the final auction (paper: 0.0009–0.0092 once
    bidders learn; wild early)."""
    t0 = time.perf_counter()
    _, stats = _economy_stats()
    us = (time.perf_counter() - t0) * 1e6 / len(stats)
    print("# table1: auction, gamma_median, gamma_mean, pct_settled", file=sys.stderr)
    for s in stats:
        print(
            f"#   {s.epoch}, {s.gamma_median:.4f}, {s.gamma_mean:.4f}, {s.pct_settled:.1f}%",
            file=sys.stderr,
        )
    return us, round(stats[-1].gamma_median, 4)


def fig6_price_change():
    """Paper Fig. 6 — settled price as a ratio over the former fixed price.
    derived: max/min ratio across pools after the first auction (price
    dispersion the market discovers; 1.0 would mean fixed prices were right)."""
    t0 = time.perf_counter()
    _, stats = _economy_stats(epochs=1)
    us = (time.perf_counter() - t0) * 1e6
    r = stats[0].price_ratio
    print(
        f"# fig6: ratio min {r.min():.3f} median {np.median(r):.3f} max {r.max():.3f}",
        file=sys.stderr,
    )
    return us, round(float(r.max() / max(r.min(), 1e-9)), 2)


def fig7_utilization():
    """Paper Fig. 7 — utilization percentile of settled bids vs offers.
    derived: median(sell %ile) − median(buy %ile); positive = buys flow to
    cold pools, sells come from hot ones (the paper's headline behavior)."""
    t0 = time.perf_counter()
    _, stats = _economy_stats(epochs=4)
    us = (time.perf_counter() - t0) * 1e6 / 4
    buys = np.concatenate([s.buy_util_percentiles for s in stats])
    sells = np.concatenate([s.sell_util_percentiles for s in stats])
    print(
        f"# fig7: buy %ile quartiles {np.percentile(buys, [25,50,75]).round(1).tolist()} "
        f"sell %ile quartiles {np.percentile(sells, [25,50,75]).round(1).tolist()}",
        file=sys.stderr,
    )
    return us, round(float(np.median(sells) - np.median(buys)), 1)


def auction_scaling():
    """Paper §III.C.4 — '100 bidders × 100 resources took a few minutes in
    non-optimized Python; optimized code ≥1 order of magnitude faster.'
    Settlement runs on the sparse O(nnz) path (each bid touches 2 pools).
    derived: speedup of our settlement vs a 120 s few-minutes baseline."""
    import jax.numpy as jnp
    from repro.core import ClockConfig, clock_auction, pack_bids_sparse

    rng = np.random.default_rng(0)

    def make(u, r, b=3):
        bl, pis = [], []
        for _ in range(u):
            alts = []
            for _ in range(b):
                q = np.zeros(r, np.float32)
                q[rng.integers(0, r, size=2)] = rng.uniform(0.5, 4, size=2)
                alts.append(q)
            bl.append(alts)
            pis.append(float(rng.uniform(1, 20)))
        # operator supply
        for i in range(r):
            q = np.zeros(r, np.float32)
            q[i] = -float(rng.uniform(20, 50))
            bl.append([q])
            pis.append(float(-rng.uniform(0.5, 1) * -q[i]))
        return pack_bids_sparse(bl, pis, base_cost=np.ones(r, np.float32))

    rows = []
    # bigger markets use coarser clock ticks (tick size is an operator knob —
    # the paper runs weekly auctions); the largest case is round-capped on
    # this 1-core CPU container and reported as rounds/s.
    for (u, r, cap) in [(100, 100, 3000), (1_000, 200, 3000), (10_000, 500, 3000),
                        (100_000, 1000, 150)]:
        prob = make(u, r)
        p0 = jnp.full((r,), 0.5)
        cfgc = ClockConfig(max_rounds=cap, alpha=0.6, delta=0.25)
        run = lambda: clock_auction(prob, p0, cfgc).prices.block_until_ready()
        run()  # compile
        t0 = time.perf_counter()
        res = clock_auction(prob, p0, cfgc)
        res.prices.block_until_ready()
        dt = time.perf_counter() - t0
        rows.append((u, r, dt, int(res.rounds), bool(res.converged)))
    for u, r, dt, rounds, conv in rows:
        print(
            f"#   {u}x{r}: {dt*1e3:.1f} ms, {rounds} rounds ({rounds/dt:.0f}/s), "
            f"converged={conv}",
            file=sys.stderr,
        )
    base = rows[0][2]
    return base * 1e6, round(120.0 / base, 0)


_SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%d"
import time
import jax, jax.numpy as jnp
from repro.core import ClockConfig, random_market, sharded_clock_auction, users_mesh
from repro.kernels import ops

u, r = 100_000, 1_000
prob = random_market(u, r, seed=0)
p0 = jnp.full((r,), 0.1)
cfg = ClockConfig(max_rounds=150, alpha=0.6, delta=0.25)
mesh = users_mesh()
# the planet-scale O(nnz) scatter path, one z partial per shard
demand = ops.settlement_demand_fn(backend="jnp", exact=False)
run = lambda: sharded_clock_auction(prob, p0, cfg, demand_fn=demand, mesh=mesh)
run().prices.block_until_ready()  # compile
t0 = time.perf_counter()
res = run()
res.prices.block_until_ready()
dt = time.perf_counter() - t0
print(f"SHARDED {jax.device_count()} {u} {r} {dt:.6f} {int(res.rounds)} {bool(res.converged)}")
"""


def auction_scaling_sharded():
    """Multi-device settlement (ROADMAP: 'shard the clock over users'): the
    100k×1000 sparse market settled by sharded_clock_auction on 8 virtual
    CPU devices (subprocess, --xla_force_host_platform_device_count=8; the
    same program runs on real multi-host meshes).  Wall time is apples-to-
    apples with auction_scaling's round-capped largest case.  The child is
    held to the CPU: this process may already own an accelerator, and a
    chip serves one process at a time.
    derived: clock rounds/s on the 8-way sharded path (virtual CPU devices)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.setdefault("PYTHONPATH", "src")
    out = subprocess.run(
        [sys.executable, "-c", _SHARDED_SCRIPT % 8],
        capture_output=True, text=True, env=env, timeout=1200,
    )
    line = next(
        (l for l in out.stdout.splitlines() if l.startswith("SHARDED ")), None
    )
    if line is None:
        raise RuntimeError(f"sharded benchmark failed:\n{out.stdout}\n{out.stderr}")
    _, ndev, u, r, dt, rounds, conv = line.split()
    dt, rounds = float(dt), int(rounds)
    print(
        f"#   sharded {u}x{r} on {ndev} virtual CPU devices: {dt*1e3:.1f} ms, "
        f"{rounds} rounds "
        f"({rounds/dt:.0f}/s), converged={conv}",
        file=sys.stderr,
    )
    return dt * 1e6, round(rounds / dt, 0)


def economy_epoch():
    """AgentPopulation epoch throughput (ROADMAP: 'millions of users'): one
    full auction epoch — vectorized bid-book pack + sparse settle, 1 device —
    at 10k / 100k / 1M agents, against the legacy per-agent loop (pack +
    per-agent apply) at the sizes where the loop is still runnable.  Every
    size must report converged=True (asserted): the adaptive clock schedule
    replaced the max_rounds=40 cap the fixed coarse clock used to hit at 1M.
    Override sizes with ECONOMY_EPOCH_AGENTS=10000,100000 (comma-separated).
    us_per_call: vectorized epoch wall at the last (largest) size run.
    derived: loop/vectorized epoch speedup at the largest loop-compared
    size (null when every size is beyond the loop baseline's cap)."""
    import time as _time

    from repro.core import fleet_economy
    from repro.core.auction import ClockConfig

    sizes = [10_000, 100_000, 1_000_000]
    env_sizes = os.environ.get("ECONOMY_EPOCH_AGENTS")
    if env_sizes:
        sizes = [int(s) for s in env_sizes.split(",") if s]
    # coarse ticks with the adaptive schedule: the fixed coarse clock used to
    # hit max_rounds=40 unconverged at 1M agents; the accelerating step +
    # decaying cap clears the same book in ~34 rounds, so every size now
    # settles to an actual equilibrium (converged=True) instead of a cap
    cfg = ClockConfig(
        max_rounds=2000, alpha=0.6, delta=0.25, alpha_growth=1.6, delta_decay=0.6
    )
    loop_max = 100_000  # beyond this the per-agent loop is pointless to wait on

    fleet_economy(512, seed=0, clock=cfg).run_epoch()  # warm jax/numpy init
    # derived stays None (JSON null, not NaN — NaN is not strict JSON) when
    # no size is small enough for the loop baseline to run
    speedup = None
    us_vec_largest = float("nan")
    for n in sizes:
        eco = fleet_economy(n, seed=0, clock=cfg)
        t0 = _time.perf_counter()
        book = eco.pack_bid_book()
        t_pack = _time.perf_counter() - t0
        # fresh economy so the epoch draws the same book (jit warm from here on)
        eco = fleet_economy(n, seed=0, clock=cfg)
        eco.run_epoch()  # compile
        best_vec = np.inf
        for _ in range(2):
            eco_v = fleet_economy(n, seed=0, clock=cfg)
            t0 = _time.perf_counter()
            s_v = eco_v.run_epoch()
            best_vec = min(best_vec, _time.perf_counter() - t0)
        line = (f"#   {n} agents: pack {t_pack*1e3:.0f} ms, epoch "
                f"{best_vec*1e3:.0f} ms ({int(s_v.rounds)} rounds, "
                f"converged={bool(s_v.converged)}, U={book.num_rows})")
        if n <= loop_max:
            eco_l = fleet_economy(n, seed=0, clock=cfg, packer="loop")
            t0 = _time.perf_counter()
            s_l = eco_l.run_epoch()
            t_loop = _time.perf_counter() - t0
            assert (np.asarray(s_l.prices) == np.asarray(s_v.prices)).all(), (
                "loop and vectorized epochs diverged"
            )
            line += f", legacy loop {t_loop*1e3:.0f} ms ({t_loop/best_vec:.1f}x)"
            speedup = round(t_loop / best_vec, 1)
        us_vec_largest = best_vec * 1e6  # last (largest) size wins
        print(line, file=sys.stderr)
        assert bool(s_v.converged), (
            f"economy_epoch at {n} agents hit max_rounds — the adaptive "
            "clock is supposed to converge every size"
        )
    return us_vec_largest, speedup


def economy_epoch_policy():
    """Adaptive-bidder epoch overhead (ISSUE 5 tentpole): one 100k-agent
    epoch with the policy subsystem active — a Static / PriceChasing /
    BudgetSmoothing mix over the same fleet — vs the policy-less epoch.
    Epoch 0 is burned first so the measured epoch has real policy inputs
    (previous prices, fill rates) and PriceChasing actually acts.

    Whole-epoch walls are reported for context but make a poor overhead
    metric: the policy book settles in a different number of clock rounds,
    so the epoch ratio measures the changed *workload* as much as the
    subsystem.  The overhead claim is therefore pinned on the bid-book
    *pack phase* (policy observation + act() + overlay fold + pack — the
    only phase the subsystem adds work to), measured on each economy's
    live post-epoch-0 state.  Override the size with
    ECONOMY_EPOCH_POLICY_AGENTS.
    us_per_call: policy epoch wall.  derived: policy/plain pack-phase
    overhead ratio (must stay < 2x, asserted — as must the epoch ratio)."""
    import time as _time

    from repro.core import (
        BudgetSmoothingPolicy,
        PriceChasingPolicy,
        StaticPolicy,
        fleet_economy,
    )
    from repro.core.auction import ClockConfig

    n = int(os.environ.get("ECONOMY_EPOCH_POLICY_AGENTS", 100_000))
    cfg = ClockConfig(
        max_rounds=2000, alpha=0.6, delta=0.25, alpha_growth=1.6, delta_decay=0.6
    )
    mix = [StaticPolicy(), PriceChasingPolicy(), BudgetSmoothingPolicy()]

    def build(with_policies):
        kw = dict(policies=mix, policy=np.arange(n) % 3) if with_policies else {}
        return fleet_economy(n, seed=0, clock=cfg, **kw)

    epoch_walls, pack_walls = {}, {}
    for with_policies in (False, True):
        eco = build(with_policies)
        eco.run_epoch()  # epoch 0: warm jit, generate prices/fills to react to
        best = np.inf
        for _ in range(2):
            t0 = _time.perf_counter()
            s = eco.run_epoch()
            best = min(best, _time.perf_counter() - t0)
        epoch_walls[with_policies] = best
        # pack phase on the live state (restoring RNG so packing is repeatable
        # and leaves the economy's stream untouched)
        best_pack = np.inf
        for _ in range(6):
            st = eco.rng.bit_generator.state
            t0 = _time.perf_counter()
            eco.pack_bid_book()
            best_pack = min(best_pack, _time.perf_counter() - t0)
            eco.rng.bit_generator.state = st
        pack_walls[with_policies] = best_pack
        print(
            f"#   {n} agents, policies={'on' if with_policies else 'off'}: "
            f"epoch {best*1e3:.0f} ms ({int(s.rounds)} rounds, "
            f"converged={bool(s.converged)}, migrations={int(s.migrations)}), "
            f"pack {best_pack*1e3:.0f} ms",
            file=sys.stderr,
        )
    epoch_ratio = epoch_walls[True] / epoch_walls[False]
    pack_ratio = pack_walls[True] / pack_walls[False]
    print(
        f"#   overhead: pack {pack_ratio:.2f}x, whole epoch {epoch_ratio:.2f}x "
        "(epoch ratio includes the changed settlement workload)",
        file=sys.stderr,
    )
    # acceptance bound: the policy epoch must cost < 2x the policy-less
    # epoch.  The pack-phase ratio is the sharper subsystem-cost signal
    # (observation + act + overlay fold land entirely in the pack), but its
    # ~35 ms denominator makes it noise-sensitive on a loaded container, so
    # it gets a tripwire bound rather than the headline one.
    assert epoch_ratio < 2.0, (
        f"policy epoch wall {epoch_ratio:.2f}x exceeds the 2x budget"
    )
    assert pack_ratio < 3.0, (
        f"policy pack-phase overhead {pack_ratio:.2f}x exceeds the tripwire"
    )
    return epoch_walls[True] * 1e6, round(pack_ratio, 2)


def economy_epoch_warm():
    """Warm-started repeated auctions (ROADMAP: 'warm-start prices from the
    previous epoch'): a 4-epoch run under the default fine-step clock, cold
    (reserve-curve restart, the paper's baseline) vs warm
    (Economy(warm_start=True): each clock seeded with max(p_prev, reserve)).
    Override the fleet size with ECONOMY_EPOCH_WARM_AGENTS.
    us_per_call: mean warm epoch wall.  derived: cold/warm total clock
    rounds — the mechanism-cost saving of carrying price memory."""
    import time as _time

    from repro.core import fleet_economy

    n = int(os.environ.get("ECONOMY_EPOCH_WARM_AGENTS", 20_000))
    epochs = 4
    totals, walls = {}, {}
    for warm in (False, True):
        eco = fleet_economy(n, seed=0, warm_start=warm)
        t0 = _time.perf_counter()
        stats = [eco.run_epoch() for _ in range(epochs)]
        walls[warm] = _time.perf_counter() - t0
        totals[warm] = sum(s.rounds for s in stats)
        assert all(s.converged for s in stats)
        print(
            f"#   {n} agents, {'warm' if warm else 'cold'}: rounds "
            f"{[s.rounds for s in stats]} (total {totals[warm]}), "
            f"wall {walls[warm]:.1f} s",
            file=sys.stderr,
        )
    return walls[True] / epochs * 1e6, round(totals[False] / totals[True], 1)


def economy_epoch_faulty():
    """Fault-tolerant epoch overhead (ISSUE 6 tentpole): a 4-epoch horizon
    with the full failure-injection stack active — a mid-horizon region
    fault, bid dropout, flaky sellers, failing pools, clock retries, and
    the proportional-rationing fallback — vs the identical fault-free
    horizon.  The fault path adds clawback scans, reputation-weighted
    reserves, and the reliability EMA on top of each epoch; the bound here
    keeps that machinery from creeping into the epoch hot path.  Override
    the fleet size with ECONOMY_EPOCH_FAULTY_AGENTS.
    us_per_call: mean faulty epoch wall.  derived: faulty/plain epoch wall
    ratio (must stay < 2x, asserted)."""
    import time as _time

    from repro.core import fleet_economy
    from repro.core.faults import FaultModel, RegionFault

    n = int(os.environ.get("ECONOMY_EPOCH_FAULTY_AGENTS", 20_000))
    epochs = 4
    fm = FaultModel(
        seed=7,
        region_faults=(RegionFault(cluster=1, start=1, end=3, scale=0.25),),
        bid_dropout=0.05,
        seller_fail=0.1,
        pool_fail=0.05,
    )
    walls = {}
    for faulty in (False, True):
        kw = (
            dict(faults=fm, clock_retries=2, ration_fallback=True)
            if faulty
            else {}
        )
        eco = fleet_economy(n, seed=0, **kw)
        eco.run_epoch()  # warm jit on this economy's book shapes
        eco = fleet_economy(n, seed=0, **kw)
        t0 = _time.perf_counter()
        stats = [eco.run_epoch() for _ in range(epochs)]
        walls[faulty] = _time.perf_counter() - t0
        degraded = sum(s.degraded for s in stats)
        evictions = sum(s.evictions for s in stats)
        print(
            f"#   {n} agents, {'faulty' if faulty else 'plain'}: wall "
            f"{walls[faulty]:.1f} s, rounds {[s.rounds for s in stats]}, "
            f"degraded={degraded}, evictions={evictions}",
            file=sys.stderr,
        )
        if faulty:
            assert degraded > 0, "fault schedule never degraded an epoch"
    ratio = walls[True] / walls[False]
    print(f"#   fault-path overhead: {ratio:.2f}x", file=sys.stderr)
    assert ratio < 2.0, (
        f"faulty epoch wall {ratio:.2f}x exceeds the 2x budget"
    )
    return walls[True] / epochs * 1e6, round(ratio, 2)


def economy_epoch_fused():
    """One fused epoch program (ISSUE 7 tentpole): the whole epoch — pack,
    clock, settle, verify, surplus, apply — as a single donated-buffer
    jitted program over device-resident market state (Economy(fused=True)),
    vs the staged path (host pack → jitted settle → host apply) on the
    identical fleet, plus the pipelined horizon (pipeline=True: epoch t+1's
    device program overlaps epoch t's host stats assembly).  Per-phase
    breakdown: staged reports its pack phase (the host bid-book assembly
    fusion moves on device); fused reports prepare (host faults/reserve/
    RNG) / dispatch (device program wall) / finalize (adopt + stats).
    Prices must match the staged path every epoch (asserted): bitwise
    inside the U_cap ≤ 128 parity gate, float-close beyond it; the full
    EpochStats bit-parity suite is tests/test_fused_epoch.py.
    Override the fleet size with ECONOMY_EPOCH_FUSED_AGENTS.
    us_per_call: fused epoch wall.  derived: staged/fused epoch speedup
    (the measured pipelining overlap is printed alongside)."""
    import time as _time

    import jax

    from repro.core import fleet_economy
    from repro.core.auction import ClockConfig

    n = int(os.environ.get("ECONOMY_EPOCH_FUSED_AGENTS", 100_000))
    epochs = 4
    cfg = ClockConfig(
        max_rounds=2000, alpha=0.6, delta=0.25, alpha_growth=1.6, delta_decay=0.6
    )

    def walls(eco):
        """Epoch walls 1..epochs on a warm program (epoch 0 burns the jit)."""
        eco.run_epoch()
        out = []
        for _ in range(epochs):
            t0 = _time.perf_counter()
            s = eco.run_epoch()
            out.append((_time.perf_counter() - t0, s))
            assert bool(s.converged)
        return out

    eco_s = fleet_economy(n, seed=0, clock=cfg)
    staged = walls(eco_s)
    # staged pack phase on the live state (RNG restored so the stream and
    # the book the next epoch would draw are untouched)
    st = eco_s.rng.bit_generator.state
    t0 = _time.perf_counter()
    eco_s.pack_bid_book()
    t_pack = _time.perf_counter() - t0
    eco_s.rng.bit_generator.state = st

    eco_f = fleet_economy(n, seed=0, clock=cfg, fused=True)
    fused = walls(eco_f)
    # inside the documented bit-parity gate (U_cap = R + 2N ≤ 128) prices
    # must match the staged path bitwise; beyond it XLA's shape-dependent
    # reduce order makes the clock trajectory float-close only (the exact
    # contract lives in repro.core.fused's docstring and the parity suite)
    exact = eco_f.R + 2 * len(eco_f.pop) <= 128
    for (_, s_s), (_, s_f) in zip(staged, fused):
        p_s, p_f = np.asarray(s_s.prices), np.asarray(s_f.prices)
        if exact:
            assert (p_s == p_f).all(), "fused and staged epochs diverged"
        else:
            np.testing.assert_allclose(p_f, p_s, rtol=1e-3, atol=1e-6,
                                       err_msg="fused and staged diverged")
    # per-phase breakdown: one more binding epoch, phases timed by hand
    # (the same prepare → dispatch → adopt+finalize run_epoch performs)
    t0 = _time.perf_counter()
    prep = eco_f._fused_prepare(False)
    t_prep = _time.perf_counter() - t0
    t0 = _time.perf_counter()
    out = eco_f._fused_dispatch(prep, False)
    jax.block_until_ready(out)
    t_disp = _time.perf_counter() - t0
    t0 = _time.perf_counter()
    eco_f._fused_adopt(prep, out)
    eco_f._fused_finalize(prep, out, False)
    t_fin = _time.perf_counter() - t0

    wall_s = min(w for w, _ in staged)
    wall_f = min(w for w, _ in fused)
    print(
        f"#   {n} agents, staged: epoch {wall_s*1e3:.0f} ms best "
        f"(pack phase {t_pack*1e3:.0f} ms), rounds "
        f"{[int(s.rounds) for _, s in staged]}",
        file=sys.stderr,
    )
    print(
        f"#   {n} agents, fused:  epoch {wall_f*1e3:.0f} ms best "
        f"(prepare {t_prep*1e3:.0f} ms, dispatch {t_disp*1e3:.0f} ms, "
        f"finalize {t_fin*1e3:.0f} ms)",
        file=sys.stderr,
    )

    # pipelined horizon vs the same fused epochs run back-to-back: the
    # saving is the host finalize work hidden behind the next dispatch
    eco_q = fleet_economy(n, seed=0, clock=cfg, fused=True)
    eco_q.run_horizon(1)  # burn the jit
    t0 = _time.perf_counter()
    eco_q.run_horizon(epochs)
    wall_seq = _time.perf_counter() - t0
    eco_p = fleet_economy(n, seed=0, clock=cfg, fused=True, pipeline=True)
    eco_p.run_horizon(1)
    t0 = _time.perf_counter()
    eco_p.run_horizon(epochs)
    wall_pipe = _time.perf_counter() - t0
    overlap = wall_seq - wall_pipe
    print(
        f"#   pipelined horizon ({epochs} epochs): {wall_pipe*1e3:.0f} ms vs "
        f"{wall_seq*1e3:.0f} ms sequential — overlap {overlap*1e3:.0f} ms "
        f"({overlap / wall_seq * 100:.0f}% of the sequential wall)",
        file=sys.stderr,
    )
    return wall_f * 1e6, round(wall_s / wall_f, 2)


def bid_eval_round():
    """Settlement hot loop: one proxy-evaluation round at 100k bids × 1k
    pools (jnp path on CPU; the Pallas kernel is the TPU-fused twin).
    derived: bids/s."""
    import jax.numpy as jnp
    from repro.kernels import ops

    rng = np.random.default_rng(1)
    U, B, R = 100_000, 4, 1_000
    bundles = jnp.asarray(rng.normal(size=(U, B, R)).astype(np.float32))
    mask = jnp.asarray(rng.random((U, B)) < 0.9)
    pi = jnp.asarray(rng.normal(size=(U,)).astype(np.float32) * 5)
    prices = jnp.asarray(np.abs(rng.normal(size=(R,))).astype(np.float32))
    import jax

    f = jax.jit(lambda *a: ops.bid_eval(*a, backend="jnp")[0])
    f(bundles, mask, pi, prices).block_until_ready()
    us = _timeit(lambda: f(bundles, mask, pi, prices).block_until_ready(), n=3, warmup=1)
    return us, round(U / (us / 1e6), 0)


def bid_eval_sparse():
    """Settlement hot loop on the sparse O(nnz) path: same 100k bids × 1k
    pools as bid_eval_round, K=8 nonzeros per bundle, jnp backend on CPU.
    Also times the dense path on the equivalent densified problem.
    derived: dense/sparse speedup (us_per_call ratio)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops

    rng = np.random.default_rng(1)
    U, B, R, K = 100_000, 4, 1_000, 8
    idx_np = np.sort(rng.integers(0, R, size=(U, B, K)), axis=-1).astype(np.int32)
    val_np = rng.normal(size=(U, B, K)).astype(np.float32)
    mask = jnp.asarray(rng.random((U, B)) < 0.9)
    pi = jnp.asarray(rng.normal(size=(U,)).astype(np.float32) * 5)
    prices = jnp.asarray(np.abs(rng.normal(size=(R,))).astype(np.float32))

    idx, val = jnp.asarray(idx_np), jnp.asarray(val_np)
    f_sp = jax.jit(
        lambda i, v, m, p, pr: ops.sparse_bid_eval(i, v, m, p, pr, R, backend="jnp")[0]
    )
    f_sp(idx, val, mask, pi, prices).block_until_ready()
    us_sp = _timeit(
        lambda: f_sp(idx, val, mask, pi, prices).block_until_ready(), n=5, warmup=1
    )

    # densify the same bid book (duplicate indices sum) and time the dense path
    dense_np = np.zeros((U, B, R), np.float32)
    uu = np.repeat(np.arange(U), B * K)
    bb = np.tile(np.repeat(np.arange(B), K), U)
    np.add.at(dense_np, (uu, bb, idx_np.reshape(-1)), val_np.reshape(-1))
    bundles = jnp.asarray(dense_np)
    del dense_np
    f_d = jax.jit(lambda b, m, p, pr: ops.bid_eval(b, m, p, pr, backend="jnp")[0])
    f_d(bundles, mask, pi, prices).block_until_ready()
    us_d = _timeit(
        lambda: f_d(bundles, mask, pi, prices).block_until_ready(), n=3, warmup=1
    )
    print(
        f"# bid_eval_sparse: sparse {us_sp:.0f} us/round, dense {us_d:.0f} us/round, "
        f"{U / (us_sp / 1e6):.0f} bids/s sparse",
        file=sys.stderr,
    )
    return us_sp, round(us_d / us_sp, 1)


def bid_eval_csr():
    """Variable-K settlement hot loop: the same 100k bids × 1k pools with a
    *skewed* bundle-size profile (K ∈ {1..16}, geometric with mean ≈ 4) —
    the book shape K_max padding is worst at.  Times one CSR proxy round
    (csr_proxy_demand with the scatter-free CSRDemandAux layouts, jnp on
    CPU) against the K_max=16 padded path on the identical book.
    derived: padded/CSR speedup (us_per_call ratio)."""
    import jax
    import jax.numpy as jnp
    from repro.core import csr_demand_aux, csr_proxy_demand, csr_problem_from_arrays
    from repro.kernels import ops

    rng = np.random.default_rng(1)
    U, B, R = 100_000, 4, 1_000
    counts = np.minimum(rng.geometric(0.25, size=(U, B)), 16).astype(np.int64)
    K = int(counts.max())
    idx_np = np.zeros((U, B, K), np.int32)
    val_np = np.zeros((U, B, K), np.float32)
    for k in range(K):
        live = counts > k
        idx_np[..., k] = np.where(live, rng.integers(0, R, size=(U, B)), 0)
        val_np[..., k] = np.where(live, rng.normal(size=(U, B)), 0.0)
    mask_np = rng.random((U, B)) < 0.9
    pi_np = (rng.normal(size=(U,)) * 5).astype(np.float32)
    prices = jnp.asarray(np.abs(rng.normal(size=(R,))).astype(np.float32))

    # flat CSR streams of the same book (bundle-major, same k order)
    offsets = np.zeros(U * B + 1, np.int64)
    offsets[1:] = np.cumsum(counts.reshape(-1))
    nnz = int(offsets[-1])
    flat_idx = np.zeros(nnz, np.int32)
    flat_val = np.zeros(nnz, np.float32)
    starts = offsets[:-1].reshape(U, B)
    for k in range(K):
        live = counts > k
        pos = (starts + k)[live]
        flat_idx[pos] = idx_np[..., k][live]
        flat_val[pos] = val_np[..., k][live]
    prob = csr_problem_from_arrays(
        flat_idx, flat_val, offsets, mask_np, pi_np,
        base_cost=np.ones(R, np.float32),
    )
    aux = csr_demand_aux(prob)
    f_csr = jax.jit(csr_proxy_demand)
    f_csr(prob, prices, aux)[0].block_until_ready()
    us_csr = _timeit(
        lambda: f_csr(prob, prices, aux)[0].block_until_ready(), n=5, warmup=1
    )

    idx, val = jnp.asarray(idx_np), jnp.asarray(val_np)
    mask, pi = jnp.asarray(mask_np), jnp.asarray(pi_np)
    f_pad = jax.jit(
        lambda i, v, m, p, pr: ops.sparse_bid_eval(i, v, m, p, pr, R, backend="jnp")[0]
    )
    f_pad(idx, val, mask, pi, prices).block_until_ready()
    us_pad = _timeit(
        lambda: f_pad(idx, val, mask, pi, prices).block_until_ready(), n=5, warmup=1
    )
    print(
        f"# bid_eval_csr: nnz {nnz} (vs {U * B * K} padded slots), csr "
        f"{us_csr:.0f} us/round, padded {us_pad:.0f} us/round",
        file=sys.stderr,
    )
    return us_csr, round(us_pad / us_csr, 1)


def roofline_summary():
    """§Roofline — aggregate the dry-run matrix artifacts.
    derived: count of single-pod cells whose compile succeeded."""
    t0 = time.perf_counter()
    files = sorted(glob.glob(os.path.join("experiments", "dryrun", "*__16x16.json")))
    n_ok = 0
    print(
        "# roofline: arch, shape, bottleneck, t_comp, t_mem, t_coll, useful, "
        "peak_frac",
        file=sys.stderr,
    )
    for path in files:
        rec = json.load(open(path))
        if rec.get("status") != "ok" or not rec.get("roofline"):
            continue
        n_ok += 1
        r = rec["roofline"]
        print(
            f"#   {r['arch']}, {r['shape']}, {r['bottleneck']}, "
            f"{r['t_compute']:.3f}s, {r['t_memory']:.3f}s, {r['t_collective']:.3f}s, "
            f"{r['useful_ratio']:.2f}, {r['peak_fraction']:.4f}",
            file=sys.stderr,
        )
    return (time.perf_counter() - t0) * 1e6, n_ok


BENCHES = {
    "fig2_weighting": fig2_weighting,
    "table1_premiums": table1_premiums,
    "fig6_price_change": fig6_price_change,
    "fig7_utilization": fig7_utilization,
    "auction_scaling": auction_scaling,
    "auction_scaling_sharded": auction_scaling_sharded,
    "economy_epoch": economy_epoch,
    "economy_epoch_policy": economy_epoch_policy,
    "economy_epoch_warm": economy_epoch_warm,
    "economy_epoch_faulty": economy_epoch_faulty,
    "economy_epoch_fused": economy_epoch_fused,
    "bid_eval_round": bid_eval_round,
    "bid_eval_sparse": bid_eval_sparse,
    "bid_eval_csr": bid_eval_csr,
    "roofline_summary": roofline_summary,
}

JSON_PATH = "BENCH_settlement.json"


def _git_sha() -> str:
    """Short HEAD sha, with a ``-dirty`` suffix when the tree has uncommitted
    changes — a trajectory record must not claim a commit it didn't run."""
    try:
        return subprocess.run(
            ["git", "describe", "--always", "--dirty"],
            capture_output=True, text=True, check=True,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        ).stdout.strip()
    except Exception:
        return "unknown"


def _load_records(path: str) -> list:
    """Existing trajectory records, or [] when absent/corrupt (never raise —
    a broken file must not block recording fresh numbers).

    Every record is stamped: pre-PR-2 records predate the git_sha field and
    pre-PR-9 records predate workload/host, so missing keys are normalized on
    load — downstream consumers (the CI regression guard, perf-trajectory
    plots) can rely on the keys existing unconditionally.
    """
    try:
        with open(path) as f:
            prev = json.load(f)
        if not isinstance(prev, list):
            return []
        for rec in prev:
            if isinstance(rec, dict):
                rec.setdefault("git_sha", "unknown")
                rec.setdefault("workload", {})
                rec.setdefault("host", "unknown")
        return prev
    except (OSError, ValueError):
        return []


# env knobs that reshape a benchmark's workload — any of these being set means
# the numbers are not comparable to a run without them, so they go in the
# record's identity stamp
_WORKLOAD_ENV_PREFIXES = ("ECONOMY_EPOCH_",)


def _workload() -> dict:
    return {
        k: v
        for k, v in sorted(os.environ.items())
        if k.startswith(_WORKLOAD_ENV_PREFIXES)
    }


def _host_tag() -> str:
    """Where this run happened, for like-with-like trend comparison.

    BENCH_HOST_TAG overrides; GitHub-hosted CI runners are one stable pool
    ("github-ci"); otherwise the machine's hostname."""
    tag = os.environ.get("BENCH_HOST_TAG")
    if tag:
        return tag
    if os.environ.get("GITHUB_ACTIONS") == "true":
        return "github-ci"
    import platform

    return platform.node() or "unknown"


def main() -> None:
    from repro import compile_cache

    compile_cache.configure()
    args = sys.argv[1:]
    write_json = "--json" in args
    want = [a for a in args if not a.startswith("--")] or list(BENCHES)
    sha = _git_sha()
    records = []
    print("name,us_per_call,derived")
    for name in want:
        # exact name wins; prefix match is a convenience for unambiguous stems
        key = name if name in BENCHES else next(
            (k for k in BENCHES if k.startswith(name)), None
        )
        if key is None:
            print(f"# unknown benchmark {name}", file=sys.stderr)
            continue
        us, derived = BENCHES[key]()
        print(f"{key},{us:.1f},{derived}")
        records.append({
            "name": key, "us_per_call": round(us, 1), "derived": derived,
            "git_sha": sha, "workload": _workload(), "host": _host_tag(),
        })
    if write_json:
        # append, never clobber: the file is the cross-PR perf trajectory
        prev = _load_records(JSON_PATH)
        with open(JSON_PATH, "w") as f:
            json.dump(prev + records, f, indent=1)
        print(
            f"# wrote {JSON_PATH} (+{len(records)} records @ {sha}, "
            f"{len(prev)} kept)",
            file=sys.stderr,
        )


if __name__ == "__main__":
    main()
