"""Pallas TPU kernels for the relaxed Fp core (round-5 v2).

The XLA path in ops/fp.py materializes every conv through HBM and pays a
66x-redundant band matmul per convolution; measured on the v5e this caps
mont_mul at ~12 ms per 221k-element call. These kernels keep the whole
multiply in VMEM in a sublane-major layout — limbs on SUBLANES, batch on
LANES — so the schoolbook convolution is 33 VPU sublane rolls and the
Montgomery reduction runs in-register: measured 2.16 ms/call (5.5x) at
the same shape, differential-identical to the XLA path.

The r4 v1 kernel failed by putting limbs on the LANE axis (every shifted
window lowered to an expensive lane shift — see the r4 perf notes); the
in-kernel transpose to (limbs, batch) is what makes the shifts cheap.

Semantics are bit-compatible with ops/fp.py's relaxed contract
(signed limbs, exact-zero preservation, the 2Rp/-2p signed-redc offsets,
mod-R truncation in the m-step). `ops/fp.py` routes mul_acc/redc/
mont_mul here when the active backend is a TPU (`use_pallas()`); the
XLA path remains the CPU/test implementation and the correctness anchor.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from . import fp

__all__ = ["use_pallas", "mul_acc", "sq_acc", "redc", "mont_mul", "mont_sq"]

BLOCK = int(os.environ.get("LODESTAR_FP_PALLAS_BLOCK", "512"))

_L = fp.LIMBS  # 33
_A = fp.ACC_LIMBS  # 66
_PPRIME = [int(v) for v in fp.PPRIME_LIMBS]
_P_L = [int(v) for v in fp.P_LIMBS]
_TWO_RP_IN = np.asarray(fp._TWO_RP, dtype=np.int32)[None, :]  # (1, 66)
_TWO_P_IN = np.asarray(fp._TWO_P, dtype=np.int32)[None, :]  # (1, 33)


@functools.lru_cache(maxsize=1)
def use_pallas() -> bool:
    """Mosaic kernels run on the TPU backend only; CPU (tests, the
    multichip dryrun mesh) keeps the XLA path. Resolved lazily — never
    at import time (the r3 multichip-gate regression class). A backend
    that cannot initialise raises here (and nothing is cached): a TPU
    host must not answer "no accelerator" because its chip was busy."""
    forced = os.environ.get("LODESTAR_FP_PALLAS")
    if forced is not None:
        return forced not in ("0", "false", "")
    return jax.default_backend() == "tpu"


# --- kernel bodies (operate on transposed (rows, BLOCK) arrays) --------------


def _carry_once_rows(x, drop_top: bool):
    c = x >> fp.LIMB_BITS
    if not drop_top:
        c = jnp.concatenate([c[:-1], jnp.zeros_like(c[:1])], axis=0)
    lo = x - (c << fp.LIMB_BITS)
    return lo + jnp.concatenate([jnp.zeros_like(c[:1]), c[:-1]], axis=0)


def _carry2_rows(x, drop_top: bool = False):
    return _carry_once_rows(_carry_once_rows(x, drop_top), drop_top)


def _conv_var(at, bt, out_rows: int):
    """Schoolbook conv via sublane rolls; the zero padding wraps in."""
    at_pad = jnp.pad(at, ((0, out_rows - _L), (0, 0)))
    acc = jnp.zeros((out_rows, at.shape[1]), jnp.int32)
    for j in range(_L):
        rolled = at_pad if j == 0 else jnp.roll(at_pad, j, axis=0)
        acc = acc + rolled * bt[j][None, :]
    return acc


def _conv_const(xt, coeffs, out_rows: int):
    x_pad = jnp.pad(xt, ((0, out_rows - xt.shape[0]), (0, 0)))
    acc = jnp.zeros((out_rows, xt.shape[1]), jnp.int32)
    for j in range(_L):
        if coeffs[j] == 0:
            continue
        rolled = x_pad if j == 0 else jnp.roll(x_pad, j, axis=0)
        acc = acc + rolled * np.int32(coeffs[j])
    return acc


def _redc_rows(t, two_rp_col, two_p_col):
    t = _carry_once_rows(t, False)
    # full-width conv then truncate: position >= 33 coefficients are
    # multiples of R (drop), but a sublane ROLL would WRAP them in
    m = _carry2_rows(_conv_const(t[:_L], _PPRIME, _A)[:_L], drop_top=True)
    s = _carry2_rows(t + _conv_const(m, _P_L, _A) + two_rp_col)
    carry = (s[_L - 1] >= 2048).astype(jnp.int32)
    hi = s[_L:]
    hi = jnp.concatenate([hi[:1] + carry[None, :], hi[1:]], axis=0)
    return _carry_once_rows(hi - two_p_col, False)


def _mul_acc_kernel(a_ref, b_ref, out_ref):
    t = _carry2_rows(_conv_var(a_ref[...].T, b_ref[...].T, _A))
    out_ref[...] = t.T


def _redc_kernel(t_ref, two_rp_ref, two_p_ref, out_ref):
    out_ref[...] = _redc_rows(t_ref[...].T, two_rp_ref[...].T, two_p_ref[...].T).T


def _mont_mul_kernel(a_ref, b_ref, two_rp_ref, two_p_ref, out_ref):
    t = _carry2_rows(_conv_var(a_ref[...].T, b_ref[...].T, _A))
    out_ref[...] = _redc_rows(t, two_rp_ref[...].T, two_p_ref[...].T).T


def _sq_acc_kernel(a_ref, out_ref):
    at = a_ref[...].T
    out_ref[...] = _carry2_rows(_conv_var(at, at, _A)).T


def _mont_sq_kernel(a_ref, two_rp_ref, two_p_ref, out_ref):
    at = a_ref[...].T
    t = _carry2_rows(_conv_var(at, at, _A))
    out_ref[...] = _redc_rows(t, two_rp_ref[...].T, two_p_ref[...].T).T


# --- flatten/pad plumbing -----------------------------------------------------


def _call(kernel, out_limbs: int, *args, consts=()):
    # defensive tuple optimization_barrier: keeps XLA from CSE-merging
    # syntactically identical operands into one buffer feeding the call
    # twice. NOT sufficient on its own against the v5e identical-operand
    # miscompile (the tower's same-object->square routing is the real
    # guard, see tower.fp12_mul) — kept as defense in depth.
    if len(args) > 1:
        args = jax.lax.optimization_barrier(tuple(args))
    n = args[0].shape[0]
    grid = (n // BLOCK,)
    in_specs = [pl.BlockSpec((BLOCK, x.shape[1]), lambda i: (i, 0)) for x in args]
    in_specs += [pl.BlockSpec((1, c.shape[1]), lambda i: (0, 0)) for c in consts]
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((BLOCK, out_limbs), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, out_limbs), jnp.int32),
    )(*args, *consts)


def _flat(x, limbs: int):
    """(..., limbs) -> (N_padded, limbs), with the restore info. Zero
    padding is semantically safe: exact zeros flow through every kernel."""
    lead = x.shape[:-1]
    flat = x.reshape(-1, limbs)
    n = flat.shape[0]
    pad = (-n) % BLOCK
    if pad:
        flat = jnp.pad(flat, ((0, pad), (0, 0)))
    return flat, lead, n


def _unflat(out, lead, n):
    return out[:n].reshape(*lead, out.shape[-1])


def mul_acc(a, b):
    a = jnp.asarray(a)
    b = jnp.asarray(b)
    a, b = jnp.broadcast_arrays(a, b)
    fa, lead, n = _flat(a, _L)
    fb, _, _ = _flat(b, _L)
    return _unflat(_call(_mul_acc_kernel, _A, fa, fb), lead, n)


def sq_acc(a):
    fa, lead, n = _flat(jnp.asarray(a), _L)
    return _unflat(_call(_sq_acc_kernel, _A, fa), lead, n)


def mont_sq(a):
    fa, lead, n = _flat(jnp.asarray(a), _L)
    out = _call(_mont_sq_kernel, _L, fa, consts=(_TWO_RP_IN, _TWO_P_IN))
    return _unflat(out, lead, n)


def redc(t):
    ft, lead, n = _flat(jnp.asarray(t), _A)
    out = _call(_redc_kernel, _L, ft, consts=(_TWO_RP_IN, _TWO_P_IN))
    return _unflat(out, lead, n)


def mont_mul(a, b):
    a = jnp.asarray(a)
    b = jnp.asarray(b)
    a, b = jnp.broadcast_arrays(a, b)
    fa, lead, n = _flat(a, _L)
    fb, _, _ = _flat(b, _L)
    out = _call(_mont_mul_kernel, _L, fa, fb, consts=(_TWO_RP_IN, _TWO_P_IN))
    return _unflat(out, lead, n)
