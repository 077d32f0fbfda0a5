#!/usr/bin/env python3
"""Bring-up smoke run of the market on a TPU: the service and the economy
through their normal entry points, at deployment size, checked for results.

    python chip_smoke.py            # one chip: phases 1-4 below
    python chip_smoke.py --chips 4  # four chips: the sharded epoch only

One process drives every phase, so one process owns the chip.  Phases:

1. service — ``MarketService.from_economy(fleet_economy(100_000, 6))`` with
   its WAL and checkpoints in ``<checkout>/.chip_smoke``; ~1% bid churn
   plus withdrawals per tick through ``submit``/``withdraw``; 3 binding
   ticks, each converged and SYSTEM-feasible; ``book.parity_check()``; and
   the same book settled once more on the chip and on the host CPU.
2. economy — one staged epoch of ``fleet_economy(1_000_000)``, the
   adaptive clock of the ``economy_epoch`` benchmark.
3. fused — ``fleet_economy(100_000)`` for 2 epochs staged, fused, and
   fused with the compiled Pallas kernel in the price loop.
4. kernels — ``ops.sparse_bid_eval`` on the phase-1 book and
   ``ops.sparse_bid_eval_csr`` on the economy's packed CSR book, compiled,
   against ``kernels/ref.py`` on the chip.

``--chips 4`` runs phase 2's epoch twice — auto-sharded over the four chips
and on a one-device settle mesh — and requires EpochStats, prices and
allocations to be bit-identical.

Data is made from ``--seed``.  The script exits nonzero, printing no result,
when JAX finds no TPU, when the package beside it is missing, or when any
phase fails.  The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# the CPU reference settle of phase 1 needs the host backend beside the TPU
_platforms = os.environ.get("JAX_PLATFORMS", "")
if "tpu" in _platforms and "cpu" not in _platforms:
    os.environ["JAX_PLATFORMS"] = _platforms + ",cpu"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import compile_cache  # noqa: E402
from repro.core import fleet_economy  # noqa: E402
from repro.core.auction import ClockConfig, users_mesh  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.serve import ServiceConfig  # noqa: E402
from repro.serve.market import BidDelta, MarketService  # noqa: E402

SERVICE_AGENTS = 100_000
SERVICE_CLUSTERS = 6
SERVICE_TICKS = 3
CHURN = 0.01  # fraction of agents re-pricing their bid per tick
WITHDRAW = 0.002  # fraction of agents withdrawing per tick
ECONOMY_AGENTS = 1_000_000
FUSED_AGENTS = 100_000
FUSED_EPOCHS = 2
# the economy_epoch benchmark's adaptive clock
EPOCH_CLOCK = ClockConfig(
    max_rounds=2000, alpha=0.6, delta=0.25, alpha_growth=1.6, delta_decay=0.6
)
# kernel z vs the reference: the kernel folds each pool's demand in another
# order than the reference scatter, so z agrees to f32 rounding of sums over
# up to 10^5 terms — bounded here at 1e-4 of the pool's book volume (the
# supply scale the clock divides z by, so the price step moves < 1e-4·α·c)
Z_RTOL_OF_VOLUME = 1e-4


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def clock_move_bound(cfg: ClockConfig, prices, base_cost) -> np.ndarray:
    """Largest price move one clock round may make, per pool.

    Eq. (3) caps each round's step at ``delta·max(p, ε·c)``.  Two backends
    that fold demand in different orders see z differ by rounding, so at a
    round where some z_r sits at the tolerance one clock may step and the
    other stop: their settled prices then differ by at most one round's
    move.  Bit equality across backends is not expected."""
    p = np.asarray(prices, np.float64)
    c = np.asarray(base_cost, np.float64)
    return cfg.delta * np.maximum(p, cfg.price_floor_frac * c)


def check_within(name, got, want, bound) -> None:
    diff = np.abs(np.asarray(got, np.float64) - np.asarray(want, np.float64))
    rel = float(np.max(diff / np.maximum(np.abs(np.asarray(want, np.float64)), 1e-30)))
    log(
        f"{name}: max |Δp| {diff.max():.6g} (max rel {rel:.3g}), "
        f"max |Δp|/bound {float(np.max(diff / bound)):.3g}"
    )
    if not np.all(diff <= bound):
        bad = np.flatnonzero(diff > bound)
        raise AssertionError(
            f"{name}: pools {bad.tolist()} move by {diff[bad].tolist()} > "
            f"bound {bound[bad].tolist()}"
        )


def require(cond, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def _churn_tick(svc, rng, keys, idx_rows, val_rows, mask_rows, pi_rows, live, withdrawn):
    n_delta = int(CHURN * SERVICE_AGENTS)
    pick = rng.choice(live, size=min(n_delta, live.size), replace=False)
    scale = rng.uniform(0.9, 1.1, size=pick.size).astype(np.float32)
    accepted = 0
    for j, i in enumerate(pick):
        withdrawn.discard(keys[i])  # a re-submission revives a withdrawn key
        bundles = [(idx_rows[i, b], val_rows[i, b]) for b in np.flatnonzero(mask_rows[i])]
        accepted += svc.submit(BidDelta(keys[i], bundles, pi_rows[i][mask_rows[i]] * scale[j]))
    out = 0
    for i in rng.choice(live, size=int(WITHDRAW * SERVICE_AGENTS), replace=False):
        if keys[i] not in withdrawn and svc.withdraw(keys[i]):
            withdrawn.add(keys[i])
            out += 1
    return accepted, out


def phase_service(seed: int) -> dict:
    """Phase 1.  Returns the service, its economy and the last prices."""
    workdir = os.path.join(ROOT, ".chip_smoke")
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    cfg = ServiceConfig(
        wal_path=os.path.join(workdir, "market.wal"),
        checkpoint_dir=os.path.join(workdir, "ckpt"),
    )
    t0 = time.perf_counter()
    eco = fleet_economy(SERVICE_AGENTS, SERVICE_CLUSTERS, seed=seed)
    svc = MarketService.from_economy(eco, config=cfg)
    book = svc.book
    log(
        f"service: {book.num_rows} rows in {book.rows_cap} slots × "
        f"{book.num_bundles} bundles × {book.k_bound} pools, nnz cap "
        f"{book.nnz_cap}, R = {book.num_resources}; bridged in "
        f"{time.perf_counter() - t0:.2f} s"
    )
    rng = np.random.default_rng(seed)
    keys, idx_rows, val_rows, mask_rows, pi_rows = eco.export_bid_rows()
    live = np.flatnonzero(mask_rows.any(axis=1))
    withdrawn: set = set()
    stats = None
    for t in range(SERVICE_TICKS):
        sub, out = _churn_tick(
            svc, rng, keys, idx_rows, val_rows, mask_rows, pi_rows, live, withdrawn
        )
        t0 = time.perf_counter()
        stats = svc.tick()
        dt = time.perf_counter() - t0
        log(
            f"tick {t}: {sub} bids in, {out} withdrawn, {stats.rounds} rounds, "
            f"converged={stats.converged}, system_ok={stats.system_ok}, "
            f"health={stats.health}, pct_settled={stats.pct_settled:.2f}%, "
            f"wall {dt:.3f} s"
        )
        require(stats.converged, f"tick {t} did not converge")
        require(stats.system_ok, f"tick {t} is not SYSTEM-feasible")
    book.parity_check()
    log("incremental book bit-identical to the full repack")

    # the same book, settled from the reserve curve on the chip and on the CPU
    problem = book.device_problem()
    start = np.asarray(svc.reserve, np.float32)
    res_tpu, _, _ = svc._settle(problem, jnp.asarray(start), None)
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        res_cpu, _, _ = svc._settle(
            jax.device_put(problem, cpu), jax.device_put(start, cpu), None
        )
    p_tpu, p_cpu = np.asarray(res_tpu.prices), np.asarray(res_cpu.prices)
    log(
        f"settle: chip {int(res_tpu.rounds)} rounds converged="
        f"{bool(res_tpu.converged)}, cpu {int(res_cpu.rounds)} rounds "
        f"converged={bool(res_cpu.converged)}, prices bit-equal "
        f"{np.array_equal(p_tpu, p_cpu)}"
    )
    require(bool(res_tpu.converged) and bool(res_cpu.converged), "settle did not converge")
    bound = clock_move_bound(
        svc.clock, np.maximum(p_tpu, p_cpu), book.base_cost
    )
    check_within("chip vs cpu settle", p_tpu, p_cpu, bound)
    return {"svc": svc, "eco": eco, "prices": stats.prices}


def phase_economy(seed: int) -> None:
    t0 = time.perf_counter()
    eco = fleet_economy(ECONOMY_AGENTS, seed=seed, clock=EPOCH_CLOCK)
    t1 = time.perf_counter()
    s = eco.run_epoch()
    log(
        f"economy: {ECONOMY_AGENTS} agents built in {t1 - t0:.2f} s; staged "
        f"epoch {time.perf_counter() - t1:.2f} s, {s.rounds} rounds, "
        f"converged={s.converged}, system_ok={s.system_ok}, "
        f"pct_settled={s.pct_settled:.2f}%"
    )
    require(s.converged, "1M-agent epoch did not converge")
    require(s.system_ok, "1M-agent epoch is not SYSTEM-feasible")


def phase_fused(seed: int) -> None:
    runs = {}
    for name, kw in (
        ("staged", {}),
        ("fused", {"fused": True}),
        ("fused+pallas", {"fused": True, "fused_backend": "pallas"}),
    ):
        eco = fleet_economy(FUSED_AGENTS, seed=seed, clock=EPOCH_CLOCK, **kw)
        t0 = time.perf_counter()
        runs[name] = [eco.run_epoch() for _ in range(FUSED_EPOCHS)]
        log(
            f"{name}: {FUSED_EPOCHS} epochs in {time.perf_counter() - t0:.2f} s, "
            f"rounds {[s.rounds for s in runs[name]]}"
        )
        for e, s in enumerate(runs[name]):
            require(s.converged and s.system_ok, f"{name} epoch {e}: not converged/feasible")
    # float-close to the staged path: beyond the 128-row parity gate the
    # fused program folds demand in another order (and the kernel in its
    # own), so prices may part by one clock round's move — the bound above
    base_cost = np.tile(eco.base_cost_rt, eco.C)
    for name in ("fused", "fused+pallas"):
        for e, (a, b) in enumerate(zip(runs["staged"], runs[name])):
            bound = clock_move_bound(EPOCH_CLOCK, np.maximum(a.prices, b.prices), base_cost)
            check_within(f"{name} vs staged epoch {e}", b.prices, a.prices, bound)
    # the in-loop z the pallas run used is the compiled Mosaic kernel
    n, c, k = FUSED_AGENTS, eco.C, eco.T
    u_cap, r = eco.R + 2 * n, eco.R
    z_fn = ops.fused_epoch_z_fn("pallas", r)
    text = (
        jax.jit(z_fn)
        .lower(
            jax.ShapeDtypeStruct((u_cap, c, k), jnp.int32),
            jax.ShapeDtypeStruct((u_cap, c, k), jnp.float32),
            jax.ShapeDtypeStruct((u_cap, c), jnp.bool_),
            jax.ShapeDtypeStruct((u_cap, c), jnp.float32),
            jax.ShapeDtypeStruct((r,), jnp.float32),
        )
        .compile()
        .as_text()
    )
    require("tpu_custom_call" in text, "fused pallas z is not a compiled Mosaic kernel")
    log("fused+pallas in-loop z compiles to a Mosaic custom call")


def _check_kernel(name, got, want, volume) -> None:
    (zk, ck), (zr, cr) = got, want
    ck, cr = np.asarray(ck), np.asarray(cr)
    zk, zr = np.asarray(zk, np.float64), np.asarray(zr, np.float64)
    err = np.abs(zk - zr)
    tol = Z_RTOL_OF_VOLUME * np.asarray(volume, np.float64)
    log(
        f"{name}: {int((cr >= 0).sum())}/{cr.size} active, chosen equal "
        f"{np.array_equal(ck, cr)}, max |Δz| {err.max():.6g}, "
        f"max |Δz|/tol {float(np.max(err / tol)):.3g}"
    )
    require(np.array_equal(ck, cr), f"{name}: chosen differs at {np.flatnonzero(ck != cr)[:10]}")
    require(np.all(err <= tol), f"{name}: z differs beyond 1e-4 of pool volume")


def phase_kernels(svc, eco, prices) -> None:
    book = svc.book
    u, b, k, r = book.rows_cap, book.num_bundles, book.k_bound, book.num_resources
    args = (
        jnp.asarray(book.idx.reshape(u, b, k)),
        jnp.asarray(book.val.reshape(u, b, k)),
        jnp.asarray(book.mask),
        jnp.asarray(book.pi),
        jnp.asarray(prices, jnp.float32),
    )
    _check_kernel(
        f"sparse_bid_eval ({u}×{b}×{k}, R={r})",
        ops.sparse_bid_eval(*args, r, backend=None),
        ops.sparse_bid_eval(*args, r, backend="jnp"),
        book.supply_scale(),
    )
    csr = eco.pack_bid_book().problem
    nnz = int(csr.idx.shape[0])
    require(nnz <= ops.CSR_MAX_NNZ, f"packed book nnz {nnz} over the CSR cap")
    cargs = (
        csr.idx, csr.val, csr.rows, csr.offsets, csr.bundle_mask, csr.pi,
        jnp.asarray(prices, jnp.float32), csr.num_resources, csr.k_bound,
    )
    _check_kernel(
        f"sparse_bid_eval_csr (nnz {nnz}, {csr.bundle_mask.shape[0]} rows, "
        f"k_bound {csr.k_bound})",
        ops.sparse_bid_eval_csr(*cargs, backend=None),
        ops.sparse_bid_eval_csr(*cargs, backend="jnp"),
        csr.supply_scale,
    )


def _stats_diff(a, b) -> list[str]:
    """EpochStats fields whose bytes differ."""
    out = []
    for f in dataclasses.fields(a):
        x, y = np.asarray(getattr(a, f.name)), np.asarray(getattr(b, f.name))
        if x.shape != y.shape or x.tobytes() != y.tobytes():
            out.append(f.name)
    return out


def phase_sharded(seed: int) -> None:
    """The 1M-agent epoch auto-sharded over every chip vs one device."""
    ndev = jax.device_count()
    sharded = fleet_economy(ECONOMY_AGENTS, seed=seed, clock=EPOCH_CLOCK)
    require(
        sharded.settle_blocks % ndev == 0,
        f"{ndev} devices do not divide settle_blocks={sharded.settle_blocks}",
    )
    single = fleet_economy(
        ECONOMY_AGENTS, seed=seed, clock=EPOCH_CLOCK, settle_mesh=users_mesh(1)
    )
    t0 = time.perf_counter()
    sa = sharded.run_epoch()
    t1 = time.perf_counter()
    sb = single.run_epoch()
    t2 = time.perf_counter()
    log(
        f"sharded over {ndev}: {t1 - t0:.2f} s, {sa.rounds} rounds; one device: "
        f"{t2 - t1:.2f} s, {sb.rounds} rounds"
    )
    diff = _stats_diff(sa, sb)
    alloc = {
        "placed": (sharded.pop.placed, single.pop.placed),
        "home": (sharded.pop.home, single.pop.home),
        "usage": (sharded.usage, single.usage),
    }
    diff += [k for k, (x, y) in alloc.items() if x.tobytes() != y.tobytes()]
    if diff:
        dp = np.abs(np.asarray(sa.prices, np.float64) - np.asarray(sb.prices, np.float64))
        raise AssertionError(
            f"sharded vs one-device epoch differ in {diff}; max |Δp| {dp.max():.6g}"
        )
    log("sharded and one-device epochs bit-identical: EpochStats, prices, allocations")


def run_phase(name, fn, *args):
    t0 = time.perf_counter()
    log(f"phase {name} ...")
    try:
        out = fn(*args)
    except Exception:
        traceback.print_exc()
        log(f"phase {name} FAILED after {time.perf_counter() - t0:.1f} s")
        return False, None
    log(f"phase {name} ok in {time.perf_counter() - t0:.1f} s")
    return True, out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"no TPU: JAX found {devices[0].platform!r}; nothing was run")
        return 2
    if len(devices) != args.chips:
        log(f"--chips {args.chips} needs {args.chips} TPU chips, JAX found {len(devices)}")
        return 2
    log(f"cache: {compile_cache.configure()}")
    log(f"device: {devices[0].device_kind} × {len(devices)}, jax {jax.__version__}")

    if args.chips == 4:
        results = [run_phase("sharded", phase_sharded, args.seed)[0]]
    else:
        ok1, svc = run_phase("service", phase_service, args.seed)
        results = [ok1]
        results.append(run_phase("economy", phase_economy, args.seed)[0])
        results.append(run_phase("fused", phase_fused, args.seed)[0])
        if ok1:
            results.append(
                run_phase("kernels", phase_kernels, svc["svc"], svc["eco"], svc["prices"])[0]
            )
        else:
            log("phase kernels skipped: it runs on the phase-1 book")
            results.append(False)
    if not all(results):
        return 1
    print(
        json.dumps(
            {
                "ok": True,
                "device": {
                    "platform": devices[0].platform,
                    "kind": devices[0].device_kind,
                    "count": len(devices),
                },
            }
        ),
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
