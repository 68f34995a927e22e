"""Batched multi-scalar multiplication (MSM) and pubkey aggregation on
device.

`aggregate_rows_g1` is the verify launch's `bls.aggregate` stage: it
replaces the reference's main-thread pubkey aggregation
(`chain/bls/multithread/index.ts:152,177` PublicKey.aggregate) for
signature sets that name their signers by registry index
(`crypto.bls.api.IndexedSignatureSet`) — each row's pubkeys are gathered
from the resident registry table (`chain/bls/pubkey_table.py`) and
summed on the chip: the 512-pubkey fast-aggregate-verify workload
(BASELINE config 3; the benchmark's `range-sync-aggregate-sets`). The
MSM is the core KZG needs later.

TPU-first design note: classic Pippenger minimizes *scalar op count*
(N + 2^w adds per window) via data-dependent bucket scatter — the wrong
shape for SIMD lockstep. On a vector unit the batch dimension is free and
**sequential depth** is the cost, so this MSM is a select-based batched
double-and-add: all N points advance through the bit schedule in lockstep
(`scalar_mul_var`, depth = nbits) followed by one log2(N) tree fold
(`fold_sum`). Depth 255+9 for a 512-point G1 MSM vs Pippenger's
windows x bucket-reduction serial chain — and zero gather/scatter.

Plain (scalar-free) aggregation is just the fold, after one level of
affine + affine additions (`sum_affine_g1`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import curve as cv
from . import fp
from . import prep
from . import tower as tw

__all__ = ["bits_msb", "msm_g1", "msm_g2", "sum_affine_g1", "aggregate_points_g1", "aggregate_rows_g1"]


def bits_msb(scalars, width: int) -> np.ndarray:
    """(N,) ints -> (N, width) int32 bit matrix, MSB first."""
    out = np.zeros((len(scalars), width), dtype=np.int32)
    for i, s in enumerate(scalars):
        s = int(s)
        for j in range(width):
            out[i, j] = (s >> (width - 1 - j)) & 1
    return out


def msm_g1(points_aff, bit_matrix):
    """sum_i scalar_i * P_i over G1.

    points_aff: (x, y) mont-form (N, 33) arrays; bit_matrix: (N, nbits)
    int32 MSB-first. Returns a Jacobian point (no batch dim).
    Scalar 0 rows contribute infinity (their running point stays Z=0).
    """
    acc = prep._dispatch(
        cv.scalar_mul_var, cv.F1, points_aff, bit_matrix, fp.one_mont(), exact=True
    )
    return prep._dispatch(cv.fold_sum, cv.F1, acc)


def msm_g2(points_aff, bit_matrix):
    """sum_i scalar_i * Q_i over the G2 twist ((N, 2, 33) coords)."""
    acc = prep._dispatch(
        cv.scalar_mul_var, cv.F2, points_aff, bit_matrix, tw.fp2_one(), exact=True
    )
    return prep._dispatch(cv.fold_sum, cv.F2, acc)


def _add_affine_g1(p, q, one):
    """Complete P + Q of affine G1 points, Jacobian out (Z = x2 - x1):
    `cv.jac_add` with both Z = 1, which saves it 9 of its 16 products.
    The identity is the exact-zero pair (0, 0), which is no curve point
    (the table's padding row, `pubkey_table.PubkeyTable`); P == Q takes
    the doubling and P == -Q the exact-zero infinity, by value-level
    tests, as `cv.jac_add(exact=True)` does: a repeated signer and a
    cancelling pair are data."""
    F = cv.F1
    x1, y1 = p
    x2, y2 = q
    h = F.sub(x2, x1)
    r = F.sub(y2, y1)
    h2 = F.sq(h)
    h3 = F.mul(h, h2)
    v = F.mul(x1, h2)
    x3 = F.sub(F.sub(F.sq(r), h3), F.add(v, v))
    y3 = F.sub(F.mul(r, F.sub(v, x3)), F.mul(y1, h3))
    out = (x3, y3, h)

    p_inf = F.is_zero(x1) & F.is_zero(y1)
    q_inf = F.is_zero(x2) & F.is_zero(y2)
    p_jac = (x1, y1, jnp.where(p_inf[..., None], jnp.zeros_like(x1), one))
    q_jac = (x2, y2, jnp.where(q_inf[..., None], jnp.zeros_like(x2), one))
    finite = ~p_inf & ~q_inf
    h0 = F.is_zero_mod(h)
    r0 = F.is_zero_mod(r)
    out = cv._where_pt(F, h0 & r0 & finite, cv.jac_double(F, p_jac), out)
    out = cv._where_pt(F, h0 & ~r0 & finite, cv._zero_pt_like(x3), out)
    out = cv._where_pt(F, p_inf, q_jac, out)
    out = cv._where_pt(F, q_inf, p_jac, out)
    return out


@jax.jit
def sum_affine_g1(x, y):
    """Sum affine G1 points down axis 0, every other axis carried as
    batch: (K, ..., 33) each in, a Jacobian point (..., 33) out. An
    exact-zero (0, 0) entry is the identity. One level of affine +
    affine additions, then `cv.fold_sum`'s tree of complete Jacobian
    additions: ceil(log2 K) levels in all."""
    k = x.shape[0]
    if k == 1:
        inf = cv.F1.is_zero(x[0]) & cv.F1.is_zero(y[0])
        return (x[0], y[0], jnp.where(inf[..., None], jnp.zeros_like(x[0]), fp.one_mont()))
    if k % 2:
        pad = [(0, 1)] + [(0, 0)] * (x.ndim - 1)
        x, y = jnp.pad(x, pad), jnp.pad(y, pad)
    half = x.shape[0] // 2
    pt = _add_affine_g1((x[:half], y[:half]), (x[half:], y[half:]), fp.one_mont())
    return cv.fold_sum(cv.F1, pt)


def aggregate_points_g1(points_aff):
    """Plain sum of N affine G1 points (pubkey aggregation): one
    dispatch of `sum_affine_g1`, no scalars."""
    x, y = points_aff
    return prep._dispatch(sum_affine_g1, x, y)


def aggregate_rows_g1(table_x, table_y, idx):
    """Each row's aggregate pubkey from the registry table: the body of
    the verify launch's `bls.aggregate` stage (traced inline there).

    table_x, table_y: (capacity, 33) mont-form limbs, the identity
    (exact zeros) in the rows a padded column names; idx: (rows, K)
    int32, every entry inside the table (the host parse checks the
    bounds: a gather clamps silently). The gather runs signer-major,
    (K, rows), so that every level of the sum halves a leading axis
    with the rows as batch. Returns (pk_x, pk_y, ok): affine
    coordinates (rows, 33) and ok False where a row's sum is the
    identity (no signer, or signers that cancel), whose coordinates are
    then in-contract garbage."""
    with jax.named_scope("gather"):
        cols = idx.T
        x = jnp.take(table_x, cols, axis=0, mode="clip")
        y = jnp.take(table_y, cols, axis=0, mode="clip")
    with jax.named_scope("sum"):
        pt = sum_affine_g1(x, y)
        ok = ~cv.jac_is_inf_val(cv.F1, pt)
        pk_x, pk_y = cv.jac_to_affine_batch(cv.F1, pt)
    return pk_x, pk_y, ok
