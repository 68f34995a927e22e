"""The device program names its stages (`jax.named_scope`): ten names that
a profile of a verify launch groups its operations under. Checked on the
jaxprs of the shared stage bodies, which trace in seconds; the whole
single-launch program, lowered, under `slow`."""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import pytest

from lodestar_tpu.models import batch_verify as bv
from lodestar_tpu.ops import fp
from lodestar_tpu.ops import pairing as prg
from lodestar_tpu.ops import prep as dp

STAGES = (
    "bls.prep_field", "bls.prep_subgroup", "bls.hash_finish", "bls.blind", "bls.assemble",
    "bls.miller", "bls.fold", "bls.final_exp", "bls.final_exp/easy", "bls.final_exp/hard",
)
N = 2
I32 = jnp.int32


def shape(*dims, dtype=I32):
    return jax.ShapeDtypeStruct(dims, dtype)


G1 = shape(N, fp.LIMBS)
G2 = shape(N, 2, fp.LIMBS)
BITS = shape(N, bv.COEFF_BITS)
MASK = shape(N, dtype=jnp.bool_)
F12 = shape(N, 2, 3, 2, fp.LIMBS)


def scopes_of(jaxpr, found: set[str]) -> set[str]:
    """Every prefix of every equation's name stack, through nested jaxprs."""
    for eqn in jaxpr.eqns:
        parts = [p for p in str(eqn.source_info.name_stack).split("/") if p]
        found.update("/".join(parts[: i + 1]) for i in range(len(parts)))
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else (value,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    scopes_of(inner, found)
    return found


def stage_scopes(fn, *args) -> set[str]:
    found = scopes_of(jax.make_jaxpr(fn)(*args).jaxpr, set())
    return {s for s in found if s.startswith("bls.")}


@pytest.mark.parametrize("fn, args, want", [
    (bv._blind_and_aggregate_body, (G1, G1, G2, G2, BITS, MASK), {"bls.blind"}),
    (prg.miller_loop, ((G1, G1), (G2, G2)), {"bls.miller"}),
    (prg.fp12_product_fold, (F12, MASK), {"bls.fold"}),
    (prg.final_exponentiation, (shape(2, 3, 2, fp.LIMBS),),
     {"bls.final_exp", "bls.final_exp/easy", "bls.final_exp/hard"}),
    (bv._fold_verdict_body, (shape(N + 1, 2, 3, 2, fp.LIMBS), shape(N + 1, dtype=jnp.bool_)),
     {"bls.fold", "bls.final_exp", "bls.final_exp/easy", "bls.final_exp/hard"}),
], ids=["blind", "miller", "fold", "final_exp", "fold_verdict"])
def test_a_stage_body_traces_under_its_scope(fn, args, want):
    assert stage_scopes(fn, *args) == want


def test_assemble_names_its_scope():
    aff1, aff2 = (G1, G1), (shape(1, 2, fp.LIMBS),) * 2
    got = stage_scopes(bv._assemble_pairs, aff1, aff2, shape(1, dtype=jnp.bool_), G2, G2, MASK)  # one slot
    assert got == {"bls.assemble"}


@pytest.mark.parametrize("groups", [1, 2, 4])
def test_the_single_launch_program_carries_all_ten(monkeypatch, groups):
    """The three prep legs are scoped where the single launch calls
    them; stand-ins of the legs' shapes keep the trace short (the real
    legs are the split schedule's programs and are traced under `slow`).
    The multi-job program is the same body over `groups` slots."""

    def field_stage(pk_x_std, pk_sign, sig_x_std, sig_sign, lo, hi):
        g1 = pk_x_std[:, : fp.LIMBS] + 1
        g2 = jnp.stack([g1, g1], axis=1)
        return g1, g1, pk_sign, g2, g2, sig_sign, (g2, g2, g2), (g2, g2, g2)

    def subgroup_stage(pk_x, pk_y, pk_curve, sig_x, sig_y, sig_curve):
        return pk_curve & (pk_x[:, 0] >= 0), sig_curve & (sig_x[:, 0, 0] >= 0)

    def hash_finish(q0, q1):
        return q0[0] + q1[0], q0[1] + q1[1]

    monkeypatch.setattr(dp, "_prep_field_stage", field_stage)
    monkeypatch.setattr(dp, "_prep_subgroup_stage", subgroup_stage)
    monkeypatch.setattr(dp, "hash_finish", hash_finish)
    size = 8
    args = (shape(size, fp.LIMBS), shape(size, dtype=jnp.bool_), shape(size, 2, fp.LIMBS),
            shape(size, dtype=jnp.bool_), shape(size, 2, fp.LIMBS), shape(size, 2, fp.LIMBS),
            shape(size, dtype=jnp.bool_), shape(size, bv.COEFF_BITS), shape(size, dtype=jnp.bool_))
    # the bodies: a jit would cache the stand-ins' trace
    if groups == 1:
        program = bv._single_launch_verify.__wrapped__
    else:
        program = lambda *a: bv._grouped_launch_verify.__wrapped__(*a, groups=groups)
    assert stage_scopes(program, *args) == set(STAGES)


@pytest.mark.slow  # traces every leg of the 8 class for real (12 s alone, 37 MB of text)
def test_the_lowered_single_launch_program_carries_all_ten():
    sets = bv.make_synthetic_sets(2, seed=3)
    si = bv.prepare_single_launch_inputs(sets)
    text = bv._single_launch_verify.lower(*si.arrays, si.bits, si.mask).as_text(debug_info=True)
    for stage in STAGES:
        # a nested jit's operations are named from its own body on: `"bls.miller/while/..."`
        assert re.search(rf'["/]{re.escape(stage)}/', text), stage
