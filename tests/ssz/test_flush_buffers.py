"""The collector's launch buffers (ssz/device_htr.py): a level's pairs
gathered straight into its size class's buffer, kept across flushes and
given back to the pool, on the device road and the host road. Every root
and every retained level is checked against hashlib after each flush, so
a stale pad row, a buffer two flushes share or a fetched view written
into would show."""

from __future__ import annotations

import hashlib
import threading

import numpy as np
import pytest

from lodestar_tpu.ssz import device_htr as dh
from lodestar_tpu.ssz import tree as T
from lodestar_tpu.ssz.hash import hash_nodes_cpu

DEPTH = 7
ROWS = 1 << DEPTH


@pytest.fixture(params=["device", "cpu"])
def road(request, monkeypatch):
    """Mode on with every level on the device, or mode off; an empty
    pool, so that a first flush has to allocate."""
    prev = dh.configure_device_htr(mode="on" if request.param == "device" else "off")
    monkeypatch.setattr(dh, "DEVICE_MIN_FLUSH_PAIRS", 1)
    monkeypatch.setattr(dh, "_launch_buffers", {})
    yield request.param
    dh.configure_device_htr(mode=prev)


def sha_levels(leaves: np.ndarray) -> list[bytes]:
    """Every level of the tree over `leaves`, by hashlib alone."""
    out = [leaves.tobytes()]
    while len(out[-1]) > 32:
        below = out[-1]
        out.append(b"".join(hashlib.sha256(below[i : i + 64]).digest() for i in range(0, len(below), 64)))
    return out


def stack(seed: int, depth: int = DEPTH) -> list[np.ndarray]:
    leaves = np.random.default_rng(seed).integers(0, 256, size=(1 << depth, 32), dtype=np.uint8)
    levels = [leaves]
    while levels[-1].shape[0] > 1:
        levels.append(hash_nodes_cpu(levels[-1]).copy())
    return levels


def rewrite(levels: list[np.ndarray], dirty, value: int) -> None:
    for i in dirty:
        levels[0][int(i), :8] = np.frombuffer(value.to_bytes(8, "little"), dtype=np.uint8)


def assert_stack_is_hashlibs(levels: list[np.ndarray]) -> None:
    want = sha_levels(levels[0])
    assert [lv.tobytes() for lv in levels] == want


def flush(levels: list[np.ndarray], dirty) -> dict:
    coll = dh.DirtyCollector()
    coll.add_stack_job(levels, dirty)
    return coll.flush()


RNG = np.random.default_rng(39)
SORTED = np.sort(RNG.choice(ROWS, size=40, replace=False))
UNSORTED = np.concatenate([RNG.permutation(SORTED), SORTED[:9]])  # repeats too

DIRTY_FORMS = {
    "sorted array": SORTED,
    "unsorted array with repeats": UNSORTED,
    "set": set(int(i) for i in SORTED),
    "range": range(17, 90),
    "list": [int(i) for i in UNSORTED],
    "int32 array": SORTED.astype(np.int32),
    "empty": np.array([], dtype=np.int64),
}


@pytest.mark.parametrize("form", sorted(DIRTY_FORMS))
def test_every_form_of_a_dirty_set_gives_hashlibs_levels(road, form):
    dirty = DIRTY_FORMS[form]
    levels = stack(1)
    rewrite(levels, dirty, 0xC0FFEE)
    stats = flush(levels, dirty)
    assert_stack_is_hashlibs(levels)
    assert stats["dirty_chunks"] == len(set(int(i) for i in dirty))
    if form == "empty":
        assert stats["launches"] == 0 and stats["buffers_allocated"] == 0
    else:
        assert stats["backend"] == road
        assert stats["launches"] == (DEPTH if road == "device" else 0)


def test_dirty_leaves_outside_the_stack_are_refused(road):
    levels = stack(2)
    for bad in ([ROWS], [-1, 3]):
        with pytest.raises(IndexError):
            dh.DirtyCollector().add_stack_job(levels, bad)


@pytest.mark.parametrize("flushes", [2, 3])
def test_flushes_in_a_row_reuse_the_buffers_and_stay_exact(road, flushes):
    """Dirty sets of one size and different leaves: every flush after the
    first takes every level's buffers from the pool, and a pad row or a
    root left over from the flush before changes nothing."""
    levels = stack(3)
    rng = np.random.default_rng(7)
    allocated = []
    for k in range(flushes):
        dirty = rng.choice(ROWS, size=48, replace=False)
        rewrite(levels, dirty, 1000 + k)
        stats = flush(levels, dirty)
        assert_stack_is_hashlibs(levels)
        allocated.append(stats["buffers_allocated"])
    assert allocated[0] > 0
    assert allocated[1:] == [0] * (flushes - 1)


def test_a_repeat_of_the_same_shapes_allocates_nothing(road):
    levels = stack(4)
    first = flush(levels, range(ROWS))
    again = flush(levels, range(ROWS))
    # one pairs and one roots buffer for each class of a full stack: 64 pairs down to the floor of 8
    assert first["buffers_allocated"] == 2 * len({dh.pad_pow2_pairs(ROWS >> k) for k in range(1, DEPTH + 1)})
    assert again["buffers_allocated"] == 0
    assert_stack_is_hashlibs(levels)


def test_two_stack_jobs_and_a_node_job_share_each_levels_buffer(road):
    a, b = stack(5), stack(6, depth=DEPTH - 2)
    chunks = np.random.default_rng(8).integers(0, 256, size=(32, 32), dtype=np.uint8)
    node = T.subtree_from_chunks(chunks, 5)
    T.compute_root(node)
    for k in range(2):
        for i in (0, 5, 6, 31):
            node = T.set_node(node, (1 << 5) + i, T.leaf(bytes([i + k + 1]) * 32))
            chunks[i] = i + k + 1
        rewrite(a, range(0, ROWS, 3), 77 + k)
        rewrite(b, [1, 2, 30], 78 + k)
        coll = dh.DirtyCollector()
        coll.add_stack_job(a, range(0, ROWS, 3))
        coll.add_stack_job(b, [30, 2, 1, 2])
        coll.add_node_job(T.collect_unhashed(node))
        stats = coll.flush()
        assert stats["launches"] == (DEPTH if road == "device" else 0)
        assert_stack_is_hashlibs(a)
        assert_stack_is_hashlibs(b)
        assert node._root == sha_levels(chunks)[-1]
    assert stats["buffers_allocated"] == 0  # the same shapes the second time round


def test_two_threads_flushing_at_once_never_hold_one_buffer(road, monkeypatch):
    """Both threads take their first level's class at once: the one that
    finds it taken allocates its own, and no buffer is ever held twice."""
    held: set[int] = set()
    twice: list[int] = []
    lock = threading.Lock()
    meet = threading.Barrier(2, timeout=30)
    first = threading.local()
    firsts: list[bool] = []  # whether each thread allocated its first level's buffers
    take, give = dh._take_buffers, dh._give_back

    def taking(size):
        bufs, allocated = take(size)
        with lock:
            if id(bufs) in held:
                twice.append(id(bufs))
            held.add(id(bufs))
        if not getattr(first, "met", False):
            first.met = True
            firsts.append(allocated)
            meet.wait()  # both hold their first level's buffers here
        return bufs, allocated

    def giving(bufs):
        with lock:
            held.discard(id(bufs))
        give(bufs)

    flush(stack(9), range(ROWS))  # fill the pool: both threads want the same classes
    monkeypatch.setattr(dh, "_take_buffers", taking)
    monkeypatch.setattr(dh, "_give_back", giving)
    stacks = [stack(10), stack(11)]
    results: list = [None, None]

    def run(k):
        dirty = range(k, ROWS, 2)
        rewrite(stacks[k], dirty, 500 + k)
        results[k] = flush(stacks[k], dirty)

    threads = [threading.Thread(target=run, args=(k,)) for k in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert not twice
    assert all(r is not None for r in results)
    assert sorted(firsts) == [False, True]
    assert sum(r["buffers_allocated"] for r in results) >= 2
    for levels in stacks:
        assert_stack_is_hashlibs(levels)


@pytest.mark.parametrize("pairs", [1, 8, 12, 64, 100])
def test_hash_level_gives_what_it_gave(road, pairs):
    """`hash_level`, and `_device_level` without buffers: the level's
    roots as an array of the caller's, which a later level through the
    same pooled buffer leaves as it was, and the caller's rows untouched."""
    rng = np.random.default_rng(pairs)
    data = [rng.integers(0, 256, size=(2 * pairs, 32), dtype=np.uint8) for _ in range(2)]
    kept = [d.copy() for d in data]
    want = [hash_nodes_cpu(d) for d in data]
    got = [dh.hash_level(d) for d in data]
    if road == "device":
        got += [dh._device_level(d) for d in data]
    for k, roots in enumerate(got):
        assert np.array_equal(roots, want[k % 2])
    assert all(np.array_equal(d, k) for d, k in zip(data, kept))
