"""State hashTreeRoot through the dirty-subtree collector
(state_transition/htr.py): randomized mutation-sequence differential
fuzz across every fork's state type, the launch-count invariant on
slot-shaped mutation batches, the device-error → CPU fallback with
identical roots and a bumped fallback counter, and the real
process_slots hot path."""

from __future__ import annotations

import numpy as np
import pytest

from lodestar_tpu import params
from lodestar_tpu.ssz import device_htr as dh
from lodestar_tpu.state_transition import process_slots, state_hash_tree_root
from lodestar_tpu.state_transition.genesis import create_interop_genesis_state
from lodestar_tpu.state_transition.htr import StateRootTracker
from lodestar_tpu.types import ssz_types

FORKS = ("phase0", "altair", "bellatrix", "capella", "deneb")


@pytest.fixture(scope="module", autouse=True)
def minimal_preset():
    prev = params.active_preset()
    params.set_active_preset("minimal")
    yield params.active_preset()
    params.set_active_preset(prev)


@pytest.fixture
def device_on():
    """Force the device backend and drop the per-level size floor so
    minimal-preset state trees actually dispatch (production keeps the
    DEVICE_MIN_PAIRS asymmetry for sparse flushes)."""
    prev = dh.configure_device_htr(mode="on")
    prev_min = dh.DEVICE_MIN_FLUSH_PAIRS
    dh.DEVICE_MIN_FLUSH_PAIRS = 1
    yield
    dh.DEVICE_MIN_FLUSH_PAIRS = prev_min
    dh.configure_device_htr(mode=prev)


class _Counter:
    def __init__(self):
        self.n = 0.0

    def labels(self, *a):  # aggregate across legs; tests check the total
        return self

    def inc(self, amount=1):
        self.n += amount


class _Sink:
    def labels(self, *a):
        return self

    def inc(self, amount=1):
        pass

    def observe(self, v):
        pass


class FakeHtrMetrics:
    def __init__(self):
        self.flushes = _Sink()
        self.dirty_chunks = _Sink()
        self.launches = _Sink()
        self.seconds = _Sink()
        self.fallbacks = _Counter()


def _mk_validator(t, i):
    v = t.Validator.default()
    v.pubkey = bytes([i % 251, (i * 7) % 251]) * 24
    v.withdrawal_credentials = bytes([i % 13]) * 32
    v.effective_balance = 32_000_000_000
    v.activation_eligibility_epoch = i
    v.activation_epoch = i
    v.exit_epoch = 2**64 - 1
    v.withdrawable_epoch = 2**64 - 1
    return v


def _mk_state(p, fork: str, n: int = 12):
    t = ssz_types(p)
    state = getattr(t, fork).BeaconState.default()
    state.validators = [_mk_validator(t, i) for i in range(n)]
    state.balances = [32_000_000_000 + i for i in range(n)]
    state.slot = 100
    state.genesis_time = 1_600_000_000
    if fork != "phase0":
        state.previous_epoch_participation = [1] * n
        state.current_epoch_participation = [3] * n
        state.inactivity_scores = [0] * n
    return state


def _mutate(state, t, rng, fork: str) -> None:
    """One random state mutation drawn from the shapes the transition
    actually performs (whole-list rewrites, in-place element pokes,
    in-place validator field writes, appends, container swaps)."""
    n = len(state.validators)
    op = int(rng.integers(0, 10))
    if op == 0:
        state.slot = int(state.slot) + 1
    elif op == 1:
        state.balances[int(rng.integers(0, n))] = int(rng.integers(0, 2**40))
    elif op == 2:  # vectorized-epoch shape: whole list replaced
        state.balances = [int(x) for x in rng.integers(0, 2**40, size=n)]
    elif op == 3:  # in-place validator container mutation
        v = state.validators[int(rng.integers(0, n))]
        v.effective_balance = int(rng.integers(0, 2**40))
        v.slashed = bool(rng.integers(0, 2))
    elif op == 4:
        idx = int(rng.integers(0, len(state.randao_mixes)))
        state.randao_mixes[idx] = bytes(rng.integers(0, 256, size=32, dtype=np.uint8))
    elif op == 5:
        idx = int(rng.integers(0, len(state.state_roots)))
        state.state_roots[idx] = bytes(rng.integers(0, 256, size=32, dtype=np.uint8))
    elif op == 6:  # registry growth (deposit shape)
        state.validators.append(_mk_validator(t, int(rng.integers(0, 200))))
        state.balances.append(32_000_000_000)
        if fork != "phase0":
            state.previous_epoch_participation.append(0)
            state.current_epoch_participation.append(0)
            state.inactivity_scores.append(0)
    elif op == 7:
        cp = t.Checkpoint.default()
        cp.epoch = int(rng.integers(0, 1000))
        cp.root = bytes(rng.integers(0, 256, size=32, dtype=np.uint8))
        state.finalized_checkpoint = cp
    elif op == 8:
        ed = t.Eth1Data.default()
        ed.deposit_count = int(rng.integers(0, 1000))
        state.eth1_data_votes.append(ed)
    else:
        state.slashings[int(rng.integers(0, len(state.slashings)))] = int(
            rng.integers(0, 2**40)
        )
        if fork != "phase0":
            state.current_epoch_participation[int(rng.integers(0, n))] = int(
                rng.integers(0, 8)
            )


@pytest.mark.parametrize("fork", FORKS)
def test_differential_fuzz_across_forks(fork, minimal_preset, device_on, monkeypatch):
    """At every commit: device-flushed root == CPU-incremental root
    (device path force-erred) == from-scratch value-path root."""
    p = minimal_preset
    t = ssz_types(p)
    rng = np.random.default_rng(hash(fork) % 2**32)
    state_dev = _mk_state(p, fork)
    state_cpu = _mk_state(p, fork)

    real_device_level = dh._device_level

    def boom(data, steps=None):
        raise RuntimeError("injected: force the CPU incremental path")

    for round_ in range(5):
        for _ in range(int(rng.integers(1, 6))):
            seed = int(rng.integers(0, 2**31))
            _mutate(state_dev, t, np.random.default_rng(seed), fork)
            _mutate(state_cpu, t, np.random.default_rng(seed), fork)
        r_dev = state_hash_tree_root(state_dev)
        monkeypatch.setattr(dh, "_device_level", boom)
        try:
            r_cpu = state_hash_tree_root(state_cpu)
        finally:
            monkeypatch.setattr(dh, "_device_level", real_device_level)
        r_value = state_dev.type.hash_tree_root(state_dev)
        assert r_dev == r_cpu == r_value, (fork, round_)


def test_launch_count_invariant(minimal_preset, device_on):
    """A hash_tree_root flush after a slot's worth of mutations issues
    at most one hash_pairs dispatch per tree level (collector levels +
    the validator element-root levels when validators went dirty)."""
    p = minimal_preset
    state = _mk_state(p, "phase0")
    tracker = StateRootTracker(state.type)
    tracker.root(state)  # cold build
    # slot-shaped mutation batch: a few balances, one validator, one mix
    state.balances[2] = 7
    state.balances[9] = 8
    state.validators[1].effective_balance = 9
    state.randao_mixes[5] = b"\x42" * 32
    state.slot = 101
    before = dh.launch_count()
    root, stats = tracker.root(state)
    total_launches = dh.launch_count() - before
    # collector: <= one launch per level of the deepest dirty field
    assert 0 < stats["launches"] <= stats["levels"]
    # element re-rooting adds the validator subtree's own levels
    # (batch_container_roots through the same backend switch): 3 field
    # levels (8 fields) + 1 level for the two-chunk Bytes48 pubkey
    # column — still one dispatch per LEVEL of the overall state tree
    assert total_launches <= stats["levels"] + 4
    assert root == state.type.hash_tree_root(state)
    # an untouched state flushes nothing
    before = dh.launch_count()
    root2, stats2 = tracker.root(state)
    assert root2 == root
    assert stats2["launches"] == 0 and dh.launch_count() == before


def test_device_error_falls_back_with_identical_root(
    minimal_preset, device_on, monkeypatch
):
    p = minimal_preset
    m = FakeHtrMetrics()
    prev_metrics = dh._htr_metrics
    dh.configure_device_htr(metrics=m)
    try:
        state = _mk_state(p, "altair")
        expect = state.type.hash_tree_root(state)

        def boom(data, steps=None):
            raise RuntimeError("injected device fault")

        monkeypatch.setattr(dh, "_device_level", boom)
        got = state_hash_tree_root(state)
        assert got == expect
        assert m.fallbacks.n >= 1
    finally:
        dh._htr_metrics = prev_metrics


def test_tracker_error_degrades_to_value_path(minimal_preset, device_on, monkeypatch):
    """A tracker bug (not a device fault) serves the verified value
    path, drops the tracker, and counts the fallback."""
    p = minimal_preset
    m = FakeHtrMetrics()
    prev_metrics = dh._htr_metrics
    dh.configure_device_htr(metrics=m)
    try:
        state = _mk_state(p, "phase0")
        expect = state.type.hash_tree_root(state)
        from lodestar_tpu.state_transition import htr as htr_mod

        def boom(self, s):
            raise RuntimeError("injected tracker bug")

        monkeypatch.setattr(htr_mod.StateRootTracker, "root", boom)
        got = state_hash_tree_root(state)
        assert got == expect
        assert m.fallbacks.n == 1
        assert htr_mod._TRACKER_KEY not in state.__dict__
    finally:
        dh._htr_metrics = prev_metrics


def test_process_slots_hot_path_device_matches_cpu(minimal_preset, device_on):
    """The real hot path: epoch-boundary process_slots with the device
    collector produces a state whose root matches a pure-CPU replica."""
    p = minimal_preset
    genesis = create_interop_genesis_state(16, p=p)
    st_dev = genesis.copy()
    target = p.SLOTS_PER_EPOCH + 2  # crosses the epoch boundary
    process_slots(st_dev, target, p)
    st_cpu = genesis.copy()
    prev = dh.configure_device_htr(mode="off")
    try:
        process_slots(st_cpu, target, p)
        root_cpu = st_cpu.type.hash_tree_root(st_cpu)
    finally:
        dh.configure_device_htr(mode=prev)
    assert state_hash_tree_root(st_dev) == root_cpu
    assert [bytes(r) for r in st_dev.state_roots] == [bytes(r) for r in st_cpu.state_roots]


def test_tracker_survives_registry_growth_and_shrink(minimal_preset, device_on):
    """Length changes across the power-of-two boundary rebuild cleanly;
    a default (all-zero-serialization) validator appended at a padding
    row is still detected (the forced-dirty window)."""
    p = minimal_preset
    t = ssz_types(p)
    state = _mk_state(p, "phase0", n=7)
    assert state_hash_tree_root(state) == state.type.hash_tree_root(state)
    # append a DEFAULT validator: serialization is all zeros, fingerprint
    # indistinguishable from list padding — only the length window saves us
    state.validators.append(t.Validator.default())
    state.balances.append(0)
    assert state_hash_tree_root(state) == state.type.hash_tree_root(state)
    # grow past the pow2 boundary (7 -> 9 elements)
    state.validators.append(_mk_validator(t, 77))
    state.balances.append(1)
    assert state_hash_tree_root(state) == state.type.hash_tree_root(state)
    # eth1 votes reset (the epoch-boundary shrink shape)
    ed = t.Eth1Data.default()
    ed.deposit_count = 5
    state.eth1_data_votes.append(ed)
    assert state_hash_tree_root(state) == state.type.hash_tree_root(state)
    state.eth1_data_votes = []
    assert state_hash_tree_root(state) == state.type.hash_tree_root(state)


def test_state_cache_drops_tracker(minimal_preset, device_on):
    """A state entering the chain's StateCache goes dormant (every
    consumer copies, and copy() drops tracking) — its snapshot/stack
    memory must not be pinned for the cache's lifetime."""
    from lodestar_tpu.chain.chain import StateCache
    from lodestar_tpu.state_transition.htr import _TRACKER_KEY

    state = _mk_state(params.active_preset(), "phase0")
    state_hash_tree_root(state)
    assert _TRACKER_KEY in state.__dict__
    cache = StateCache()
    cache.add(b"\x01" * 32, state)
    assert _TRACKER_KEY not in state.__dict__
    # rooting again simply rebuilds tracking
    assert state_hash_tree_root(state) == state.type.hash_tree_root(state)


def test_transient_root_builds_no_tracker(minimal_preset, device_on):
    """One-shot roots on throwaway states (block production's dial,
    replay header backfill) must not cold-build tracker snapshots —
    but a warm tracker is still used."""
    from lodestar_tpu.state_transition.htr import _TRACKER_KEY

    state = _mk_state(params.active_preset(), "phase0")
    expect = state.type.hash_tree_root(state)
    assert state_hash_tree_root(state, transient=True) == expect
    assert _TRACKER_KEY not in state.__dict__
    # warm tracker: transient rides it
    state_hash_tree_root(state)
    assert _TRACKER_KEY in state.__dict__
    state.slot = int(state.slot) + 1
    assert state_hash_tree_root(state, transient=True) == state.type.hash_tree_root(state)


def test_off_mode_is_value_path(minimal_preset):
    prev = dh.configure_device_htr(mode="off")
    try:
        state = _mk_state(params.active_preset(), "phase0")
        assert state_hash_tree_root(state) == state.type.hash_tree_root(state)
        # no tracker is attached in off mode
        from lodestar_tpu.state_transition.htr import _TRACKER_KEY

        assert _TRACKER_KEY not in state.__dict__
    finally:
        dh.configure_device_htr(mode=prev)
