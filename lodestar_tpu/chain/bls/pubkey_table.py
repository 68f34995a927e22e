"""The validator registry's pubkeys, deserialized once and resident on
every verify lane's device: what `IndexedSignatureSet`s are resolved
against.

Counterpart of the reference's `EpochContext.index2pubkey`
(`state-transition/src/cache/pubkeyCache.ts`): the registry is
deserialized when the node starts and on every deposit, never per
signature set, and `getAggregatedPubkey`
(`chain/bls/multithread/index.ts:152,177`) sums a set's signers from it
on the main thread. Here the sum is a stage of the verify launch
(`bls.aggregate`, `ops/msm.py:aggregate_rows_g1`), which gathers the
signers' points from this table's device copy, so the table keeps

* the compressed bytes on the host: the CPU oracle's resolver
  (`pubkey_at`) and the pool's counted host-aggregation fallback;
* one `(x, y)` pair of `(capacity, 33)` int32 Montgomery-limb arrays on
  each lane's device, in the form the kernels use. **Row 0 is the
  identity** (exact zeros, the form `cv.fold_sum` pads with) and
  registry index `i` lives in row `i + 1`: a padded column of a
  launch's index matrix names row 0, whatever the capacity is by the
  time the launch runs.

Rows are written once and the length only grows, so a launch may run
on any copy at least as new as the one its indices were checked
against: the parse checks `contains`, the dispatch takes `arrays_on`,
and an `extend` between the two changes neither answer's validity.
An append inside the capacity is one `dynamic_update_slice` on a block
padded to a power of two of rows (a deposit compiles no new shape);
past it the capacity doubles. It is data the pool owns
(`BlsDeviceVerifierPool.pubkey_table`): no option selects it.
"""

from __future__ import annotations

import functools
import threading
from typing import Sequence

import numpy as np

from lodestar_tpu import telemetry

__all__ = ["PubkeyTable", "IDENTITY_ROW", "LOAD_PROGRAM"]

IDENTITY_ROW = 0  # the device row every padded column gathers; registry index i is row i + 1
LOAD_PROGRAM = "bls_pubkey_table_load"  # the ledger entry of every `extend`
_APPEND_BLOCK = 16  # least rows of an in-place append: MAX_DEPOSITS of a block
_PADDED_APPEND_MOST = 4096  # appends up to this many keys are padded to a power of two of rows
_PYTHON_DECODE_MOST = 256  # keys decoded in Python (~1 ms each) rather than wait for the native library's build
_LIMBS = 33


def _decode(pubkeys: Sequence[bytes], check_subgroup: bool) -> tuple[np.ndarray, np.ndarray]:
    """((n, 2, 33) int32 Montgomery limbs, (n,) bool valid). A key that
    is malformed, the identity, off the curve or (checked) outside G1 is
    invalid and its row zero. One threaded native call; the pure-Python
    oracle where the library is unavailable, and for a handful of keys
    (a node's first seconds, a block's deposits) where it is still
    being built: a registry waits for the compiler, a deposit does not."""
    from lodestar_tpu.native import bls as nbls

    n = len(pubkeys)
    if (n > _PYTHON_DECODE_MOST or nbls.ready()) and all(len(pk) == 48 for pk in pubkeys):
        native = nbls.g1_decompress_limbs_native(b"".join(pubkeys), n, check_subgroup)
        if native is not None:
            return native
    from lodestar_tpu.crypto.bls import curve as C
    from lodestar_tpu.crypto.bls.serdes import PointDecodeError, g1_from_bytes
    from lodestar_tpu.ops.fp import mont_limbs_from_int

    xy = np.zeros((n, 2, _LIMBS), dtype=np.int32)
    ok = np.zeros(n, dtype=bool)
    for i, pk in enumerate(pubkeys):
        try:
            pt = g1_from_bytes(pk)
        except PointDecodeError:
            continue
        if pt is None or (check_subgroup and not C.g1_in_subgroup(pt)):
            continue
        xy[i, 0], xy[i, 1] = mont_limbs_from_int(pt[0]), mont_limbs_from_int(pt[1])
        ok[i] = True
    return xy, ok


@functools.lru_cache(maxsize=None)
def _write_rows():
    import jax

    @jax.jit
    def write(table, block, start):
        return jax.lax.dynamic_update_slice(table, block, (start, 0))

    return write


class PubkeyTable:
    """See the module docstring. `extend` is called from one thread at
    a time (node init, then the chain's import path); readers on any
    thread see a consistent snapshot."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        # written under _lock (`extend`, `place_on`); readers on other
        # threads take no lock: each reads one reference, and what it
        # names is never changed in place below the length it read
        self._bytes = bytearray()  # guarded by: advisory-only (appended under _lock; read below a length already read)
        self._invalid: frozenset[int] = frozenset()  # guarded by: advisory-only (replaced whole under _lock)
        self._length = 0  # guarded by: advisory-only (grows under _lock, after the rows it counts are written)
        self._capacity = 0  # guarded by: advisory-only (device rows, the identity row among them; under _lock)
        self._copies: dict = {}  # guarded by: advisory-only (replaced whole under _lock): device -> (label, x, y)
        self.entries_gauge = None  # `lodestar_bls_pubkey_table_entries{lane}`, set by the node

    def __len__(self) -> int:
        return self._length

    @property
    def on_device(self) -> bool:
        """Whether launches can gather from it: some lane holds a copy."""
        return bool(self._copies)

    def lanes(self) -> dict[str, int]:
        """Entries each lane's copy holds, by lane label."""
        return {label: self._length for label, _, _ in self._copies.values()}

    # -- host side -------------------------------------------------------------

    def pubkey_at(self, index: int) -> bytes | None:
        """The compressed pubkey at a registry index, None where the
        registry has none (out of range, or a key that did not decode):
        the oracle's resolver."""
        if not 0 <= index < self._length or index in self._invalid:
            return None
        return bytes(self._bytes[48 * index : 48 * index + 48])

    def contains(self, indices: np.ndarray) -> bool:
        """Whether every index names a valid entry. The launch's gather
        clamps an index outside the table silently, so the parse asks
        here for every row it writes."""
        if indices.size == 0:
            return True
        if int(indices.min()) < 0 or int(indices.max()) >= self._length:
            return False
        invalid = self._invalid
        return not invalid or not any(int(i) in invalid for i in indices)

    # -- device side -----------------------------------------------------------

    def place_on(self, devices: Sequence, labels: Sequence[str]) -> None:
        """Give each of these devices (None: JAX's default placement) a
        copy: the lanes that take indexed rows, said once by whoever
        builds them (`mesh.build_device_mesh`)."""
        import jax
        import jax.numpy as jnp

        with self._lock:
            copies = dict(self._copies)
            if self._length and not copies:
                raise RuntimeError("a pubkey table is placed on its lanes before it is filled")
            if not self._capacity:
                self._capacity = _APPEND_BLOCK
            for device, label in zip(devices, labels):
                if device in copies:
                    continue
                if copies:
                    _, x, y = next(iter(copies.values()))
                    x, y = (jax.device_put(a, device) if device is not None else a for a in (x, y))
                else:
                    zeros = np.zeros((self._capacity, _LIMBS), dtype=np.int32)
                    x, y = (jax.device_put(zeros, device) if device is not None else jnp.asarray(zeros)
                            for _ in range(2))
                copies[device] = (label, x, y)
            self._copies = copies
        self._note_entries()

    def arrays_on(self, device) -> tuple:
        """This device's `(x, y)`, each `(capacity, 33)` int32."""
        _, x, y = self._copies[device]
        return x, y

    def extend(self, pubkeys: Sequence[bytes], trusted: bool = False) -> int:
        """Append `pubkeys` (48-byte compressed G1) at the next registry
        indices; returns the new length. Every key is decompressed once,
        here. `trusted` skips the subgroup ladder for keys that passed
        it before (an anchor state's registry, validated at deposit, as
        the reference's `index2pubkey` load does); a deposit's key is
        checked. A key that does not decode keeps its index and is
        never valid (`contains`, `pubkey_at`)."""
        pubkeys = [bytes(pk) for pk in pubkeys]
        n = len(pubkeys)
        if not n:
            return self._length
        lane = ",".join(label for label, _, _ in self._copies.values()) or None
        with telemetry.launch(LOAD_PROGRAM, telemetry.size_class_of(n), lane=lane), self._lock:
            with telemetry.phase("table.decode"):
                xy, ok = _decode(pubkeys, check_subgroup=not trusted)
            start = self._length
            if self._copies:
                with telemetry.phase("table.limbs"):
                    block_x, block_y, rows = self._block(xy, start + 1)
                with telemetry.phase("table.place"):
                    self._place(block_x, block_y, start + 1, rows)
            self._bytes += b"".join(pk if len(pk) == 48 else bytes(48) for pk in pubkeys)
            if not ok.all():
                self._invalid = self._invalid | {start + int(i) for i in np.flatnonzero(~ok)}
            self._length = start + n
        self._note_entries()
        return self._length

    def _block(self, xy: np.ndarray, first_row: int):
        """The rows to write as two contiguous blocks, padded with zero
        rows to a power of two (the rows behind the length are zero
        anyway), growing the capacity first where they would not fit."""
        n = xy.shape[0]
        # a load of a whole registry is placed as it is: one shape, once
        rows = max(_APPEND_BLOCK, 1 << (n - 1).bit_length()) if n <= _PADDED_APPEND_MOST else n
        if first_row + rows > self._capacity:
            self._grow(first_row + rows)
        block_x = np.zeros((rows, _LIMBS), dtype=np.int32)
        block_y = np.zeros((rows, _LIMBS), dtype=np.int32)
        block_x[:n], block_y[:n] = xy[:, 0], xy[:, 1]
        return block_x, block_y, rows

    def _grow(self, rows_needed: int) -> None:
        """Double the capacity until `rows_needed` fit, and move every
        copy into the larger arrays on its device."""
        import jax.numpy as jnp

        capacity = self._capacity
        while capacity < rows_needed:
            capacity *= 2
        if rows_needed > 2 * self._capacity:
            # a first load (an anchor state's registry): its size and room for a sixteenth more
            capacity = rows_needed + max(_APPEND_BLOCK, rows_needed // 16)
        grown = {}
        for device, (label, x, y) in self._copies.items():
            pad = ((0, capacity - self._capacity), (0, 0))
            grown[device] = (label, jnp.pad(x, pad), jnp.pad(y, pad))
        self._copies, self._capacity = grown, capacity

    def _place(self, block_x: np.ndarray, block_y: np.ndarray, first_row: int, rows: int) -> None:
        import jax

        from lodestar_tpu.ops.prep import _dispatch  # the counted seam of every device program

        write = _write_rows()
        start = np.int32(first_row)
        placed = {}
        for device, (label, x, y) in self._copies.items():
            bx, by = (jax.device_put(b, device) if device is not None else b for b in (block_x, block_y))
            placed[device] = (
                label, _dispatch(write, x, bx, start), _dispatch(write, y, by, start)
            )
        for _, x, y in placed.values():
            x.block_until_ready()
            y.block_until_ready()
        self._copies = placed

    def _note_entries(self) -> None:
        gauge = self.entries_gauge
        if gauge is not None:
            for label, entries in self.lanes().items():
                gauge.labels(label).set(entries)
