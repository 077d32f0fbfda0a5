"""MarketBook's account encoding: the slot-indexed mirror against the loop.

``export_state`` and ``export_dirty_state`` encode the raw accounts behind
the live slots by gathers over a slot-indexed mirror of the submissions.
What they spell is the on-disk checkpoint format, so it is pinned three
ways: array for array against the per-account loop that used to produce it
(kept below as the oracle), by the sha256 of one full and one delta record,
and by round trips through ``from_state`` and ``apply_dirty_state``.  The
mirror is written from the submissions, never from the slot arrays, so a
restored book whose slot arrays are corrupt still fails ``parity_check``.
"""
import hashlib
import json
import os

import numpy as np
import pytest

from repro.checkpoint.store import CheckpointStore
from repro.core.types import MarketBook

R, B, K = 6, 3, 4
BASE = np.linspace(1.0, 2.0, R).astype(np.float32)


def _loop_encode(book, live_slots):
    """The per-account encoder the mirror replaced, as it was: the oracle."""
    keys, slots, kinds = [], [], []
    raw_counts, raw_nnz, raw_idx, raw_val, raw_pi = [], [], [], [], []
    packed_idx, packed_val, packed_mask, packed_pi = [], [], [], []
    b_cap, k_cap = book.num_bundles, book.k_bound
    for s in live_slots:
        key = book._slot_key[s]
        json.dumps(key)
        acct = book._accounts[key]
        keys.append(key)
        slots.append(s)
        if len(acct) == 2:
            bundles, pi = acct
            kinds.append(0)
            raw_counts.append(len(bundles))
            pi_arr = np.broadcast_to(np.asarray(pi, np.float32), (len(bundles),))
            raw_pi.append(np.asarray(pi_arr, np.float32))
            for ii, vv in bundles:
                ii = np.asarray(ii, np.int32).reshape(-1)
                raw_nnz.append(ii.shape[0])
                raw_idx.append(ii)
                raw_val.append(np.asarray(vv, np.float32).reshape(-1))
        else:
            kinds.append(1)
            packed_idx.append(np.asarray(acct[0], np.int32))
            packed_val.append(np.asarray(acct[1], np.float32))
            packed_mask.append(np.asarray(acct[2], bool))
            packed_pi.append(np.asarray(acct[3], np.float32))

    def _cat(chunks, dtype):
        return np.concatenate(chunks).astype(dtype, copy=False) if chunks else np.zeros(0, dtype)

    def _stack(chunks, dtype, shape):
        return (np.stack(chunks).astype(dtype, copy=False) if chunks
                else np.zeros((0, *shape), dtype))

    return keys, {
        "slots": np.asarray(slots, np.int64),
        "kinds": np.asarray(kinds, np.int8),
        "raw_counts": np.asarray(raw_counts, np.int32),
        "raw_nnz": np.asarray(raw_nnz, np.int32),
        "raw_idx": _cat(raw_idx, np.int32),
        "raw_val": _cat(raw_val, np.float32),
        "raw_pi": _cat(raw_pi, np.float32),
        "packed_idx": _stack(packed_idx, np.int32, (b_cap, k_cap)),
        "packed_val": _stack(packed_val, np.float32, (b_cap, k_cap)),
        "packed_mask": _stack(packed_mask, bool, (b_cap,)),
        "packed_pi": _stack(packed_pi, np.float32, (b_cap,)),
    }


def _bundle(idx, val):
    return np.array(idx, np.int32), np.array(val, np.float32)


def _raw(*bundles, pi):
    return tuple(_bundle(*q) for q in bundles), np.asarray(pi, np.float32)


def _upsert_raw(book, keys, raws):
    """The service's drain: pre-packed rows plus the raw submissions."""
    rows = [book._pack_row(*r) for r in raws]
    book.upsert_rows(keys, *(np.stack([row[j] for row in rows]) for j in range(4)), raw=raws)


def _upsert_packed(book, keys, raws):
    """Economy sync: pre-packed rows alone."""
    rows = [book._pack_row(*r) for r in raws]
    book.upsert_rows(keys, *(np.stack([row[j] for row in rows]) for j in range(4)))


def _first_window(book):
    """Raw and packed accounts, scalar and per-bundle pi, an empty bundle,
    an int key, removes with LIFO slot reuse, kind changes, one doubling."""
    book.upsert("a", [([3, 1], [2.0, -1.0]), ([0], [1.5])], 4.0)
    book.upsert(7, [([2, 5, 4], [1.0, 1.0, 1.0]), ([], []), ([5], [0.5])], [3.0, 0.0, 1.0])
    _upsert_packed(book, ["p0", "p1"], [
        _raw(([4, 0], [1.0, 2.0]), ([1], [0.25]), pi=2.5),
        _raw(([5], [-3.0]), pi=-1.0),
    ])
    _upsert_raw(book, ["r0"], [_raw(([2, 2, 1, 0], [1.0, 0.5, 0.5, 0.75]), pi=[6.0])])
    assert book.rows_cap == 8
    book.remove("a")
    book.remove("p1")
    book.upsert("c", [([1], [1.0])], 2.0)  # takes p1's slot, then "d" takes a's
    _upsert_raw(book, ["d"], [_raw(([0, 3], [1.0, 1.0]), ([], []), pi=[1.0, 0.0])])
    assert book._key_slot["c"] == 3 and book._key_slot["d"] == 0
    _upsert_raw(book, ["p0"], [_raw(([5, 4, 3], [1.0, 1.0, 1.0]), pi=1.5)])  # packed -> raw
    _upsert_packed(book, ["r0"], [_raw(([3], [2.0]), ([2], [2.0]), pi=[4.0, 3.0])])  # raw -> packed


def _second_window(book):
    """Fresh keys past the next doubling, a re-add into a freed slot, a bid
    re-priced with fewer bundles, and tombstones."""
    book.remove(7)
    _upsert_raw(book, [f"e{j}" for j in range(6)], [
        _raw(*([([j % R], [1.0 + j])] * (1 + j % B)), pi=float(j)) for j in range(6)
    ])
    _upsert_packed(book, ["q0", "q1"], [
        _raw(([0, 1, 2, 3], [1.0, 1.0, 1.0, 1.0]), pi=4.0),
        _raw(([], []), ([1], [1.0]), pi=[0.0, 2.0]),
    ])
    assert book.rows_cap == 16
    book.remove("c")
    book.upsert("c", [([4], [1.0]), ([5], [1.0]), ([3], [1.0])], [1.0, 2.0, 3.0])
    _upsert_raw(book, ["d"], [_raw(([5], [1.0]), pi=0.5)])
    book.remove("e3")


def _book():
    book = MarketBook(BASE, B, K, rows_cap=4)
    _first_window(book)
    return book


def _assert_same(arrays, oracle):
    for name, want in oracle.items():
        got = arrays[name]
        assert got.dtype == want.dtype and got.shape == want.shape, name
        assert got.tobytes() == want.tobytes(), name


def _assert_full_matches_loop(book):
    live = [s for s in range(book._next_slot) if book._slot_key[s] is not None]
    keys, oracle = _loop_encode(book, live)
    arrays, meta = book.export_state()
    assert meta["keys"] == keys
    _assert_same(arrays, oracle)


def _assert_dirty_matches_loop(book):
    rows = sorted(book._ckpt_dirty)
    live = [s for s in rows if book._slot_key[s] is not None]
    keys, oracle = _loop_encode(book, live)
    arrays, meta = book.export_dirty_state(clear=False)
    assert meta["keys"] == keys
    assert meta["row_keys"] == [book._slot_key[s] for s in rows]
    _assert_same(arrays, oracle)


def test_full_and_dirty_exports_match_the_loop_encoder():
    book = _book()
    _assert_full_matches_loop(book)
    _assert_dirty_matches_loop(book)
    book.export_state(clear_dirty=True)
    _second_window(book)
    _assert_full_matches_loop(book)
    _assert_dirty_matches_loop(book)
    kinds = book.export_state()[0]["kinds"]
    assert 0 < np.count_nonzero(kinds == 0) < kinds.size  # both kinds present


def _churn(book, rng, steps):
    keys = [f"k{j}" for j in range(12)] + list(range(12))
    for _ in range(steps):
        op = rng.integers(4)
        batch = list(rng.choice(len(keys), size=int(rng.integers(1, 5)), replace=False))
        batch = [keys[j] for j in batch]
        if op == 0:
            for key in batch:
                book.remove(key)
            continue
        raws = []
        for _ in batch:
            nb = int(rng.integers(1, B + 1))
            bundles = []
            for _ in range(nb):
                n = int(rng.integers(0, K + 1))
                bundles.append((rng.integers(0, R, size=n).astype(np.int32),
                                rng.normal(size=n).astype(np.float32)))
            pi = rng.normal(size=nb) if rng.random() < 0.5 else rng.normal()
            raws.append((tuple(bundles), np.asarray(pi, np.float32)))
        if op == 1:
            _upsert_raw(book, batch, raws)
        elif op == 2:
            _upsert_packed(book, batch, raws)
        else:
            for key, (bundles, pi) in zip(batch, raws):
                book.upsert(key, bundles, pi)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_random_churn_matches_the_loop_encoder(seed):
    rng = np.random.default_rng(seed)
    book = MarketBook(BASE, B, K, rows_cap=2)
    for _ in range(4):
        _churn(book, rng, 12)
        _assert_full_matches_loop(book)
        _assert_dirty_matches_loop(book)
        if rng.random() < 0.5:
            book.export_dirty_state(clear=True)
    assert book.rows_cap >= 16
    book.parity_check()


def _record_sha(tmp_path, prefix, arrays, meta):
    store = CheckpointStore(str(tmp_path))
    store.write_record(prefix, 1, {f"book/{k}": v for k, v in arrays.items()},
                       metadata={"book": meta})
    path = store.record_path(prefix, 1)
    return [hashlib.sha256(open(os.path.join(path, f), "rb").read()).hexdigest()
            for f in ("arrays.npz", "manifest.json")]


# the records the per-account loop wrote for these two windows
FULL_SHA = ["32a256fbe55f4a4c5c7796f855efcac05bdeda53ec3ad79966f611c7443cc267",
            "de7cfe7e5ed2bf12f8e4a9b6af1ccf9f43125d4ea05cd936cbbbcc1e373482f6"]
DELTA_SHA = ["a23110cb3d17b064a97eefb0df18dad1ff562b402390bd4d0a14e85ee8d18285",
             "9e03366130334403b0ac88a2284f4fb698881e21fbd32779a25a5ad91e6f9909"]


def test_full_and_delta_record_bytes_are_pinned(tmp_path):
    book = _book()
    assert _record_sha(tmp_path, "full", *book.export_state(clear_dirty=True)) == FULL_SHA
    _second_window(book)
    assert _record_sha(tmp_path, "delta", *book.export_dirty_state()) == DELTA_SHA


def _copy(state):
    arrays, meta = state
    return {k: np.array(v, copy=True) for k, v in arrays.items()}, json.loads(json.dumps(meta))


def _assert_same_export(a, b):
    (arrays_a, meta_a), (arrays_b, meta_b) = a, b
    assert meta_a == meta_b and arrays_a.keys() == arrays_b.keys()
    _assert_same(arrays_a, arrays_b)


def test_restore_and_delta_replay_give_identical_encodings():
    book = _book()
    base = _copy(book.export_state(clear_dirty=True))
    restored = MarketBook.from_state(*base)
    _assert_same_export(restored.export_state(), base)
    restored.parity_check()

    _second_window(book)
    restored.apply_dirty_state(*_copy(book.export_dirty_state()))
    _assert_same_export(restored.export_state(), book.export_state())
    restored.parity_check()
    _churn(book, np.random.default_rng(5), 10)
    restored.apply_dirty_state(*_copy(book.export_dirty_state()))
    _assert_same_export(restored.export_state(), book.export_state())
    # the restored book keeps writing the mirror like the original
    for b in (book, restored):
        _churn(b, np.random.default_rng(6), 10)
    _assert_same_export(restored.export_state(), book.export_state())


@pytest.mark.parametrize("name", ["idx", "val", "mask", "pi"])
def test_corrupt_slot_arrays_fail_parity_after_restore(name):
    book = _book()
    book.parity_check()
    arrays, meta = _copy(book.export_state())
    s = book._key_slot["p0"]
    flat = arrays[name].reshape(-1)
    width = flat.size // book.rows_cap
    region = flat[s * width:(s + 1) * width]  # one slot's row, as on disk
    region[...] = ~region if name == "mask" else region + 1
    restored = MarketBook.from_state(arrays, meta)
    with pytest.raises(AssertionError, match="diverged from full repack"):
        restored.parity_check()


def test_non_json_keys_are_refused_by_both_exports():
    book = _book()
    book.upsert(b"bytes", [([0], [1.0])], 1.0)
    with pytest.raises(TypeError, match=r"book key b'bytes' is not JSON-serializable"):
        book.export_state()
    with pytest.raises(TypeError, match=r"book key b'bytes' is not JSON-serializable"):
        book.export_dirty_state()
