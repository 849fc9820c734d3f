"""The benchmark's own work arithmetic, peaks and reference, on the CPU."""

import jax.numpy as jnp
import numpy as np
import pytest

from conftest import ROOT

from chipbench import check, harness

POINTS = [("aatb", (30, 50, 70)), ("aatb", (7, 3, 11)),
          ("aatb", (128, 64, 1)), ("abcd", (30, 50, 70, 20, 40)),
          ("abcd", (5, 9, 2, 13, 4)), ("abcd", (1, 64, 3, 128, 7))]


@pytest.mark.parametrize("name,point", POINTS)
def test_closed_forms_match_the_enumeration(name, point):
    """Checked against the program's counts here; never imported at run
    time."""
    from repro.core.expressions import get_spec

    expr = harness.load_module(ROOT, "expressions", name)
    algos = get_spec(name).algorithms(point)
    assert {a.name: a.flops for a in algos} == expr.algorithm_flops(point)


def test_compulsory_elements_are_inputs_plus_output():
    aatb = harness.load_module(ROOT, "expressions", "aatb")
    abcd = harness.load_module(ROOT, "expressions", "abcd")
    assert aatb.compulsory_elements((3, 5, 7)) == 3 * 5 + 3 * 7 + 3 * 7
    assert abcd.compulsory_elements((2, 3, 4, 5, 6)) == (
        2 * 3 + 3 * 4 + 4 * 5 + 5 * 6 + 2 * 6)


def test_peaks_table_knows_v5e_and_refuses_the_unknown():
    peaks = harness.load_peaks(ROOT)
    assert harness.chip_peaks(peaks, "TPU v5 lite", "bfloat16") == (
        197e12, 819e9)
    with pytest.raises(KeyError):
        harness.chip_peaks(peaks, "TPU v9 imaginary", "bfloat16")
    with pytest.raises(KeyError):
        harness.chip_peaks(peaks, "TPU v5 lite", "float64")


@pytest.mark.parametrize("name,point", POINTS[:2] + POINTS[3:5])
def test_reference_matches_float64(name, point):
    expr = harness.load_module(ROOT, "expressions", name)
    rng = np.random.default_rng(0)
    ops = {b: rng.standard_normal(s).astype(np.float32)
           for b, s in expr.operand_shapes(point).items()}
    want = expr.reference({b: a.astype(np.float64) for b, a in ops.items()},
                          matmul=np.matmul, store=lambda x: x)
    got = np.asarray(check.reference(
        expr, {b: jnp.asarray(a) for b, a in ops.items()}))
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()


def test_control_reads_far_above_bfloat16():
    expr = harness.load_module(ROOT, "expressions", "abcd")
    point = (60, 70, 80, 90, 50)
    rng = np.random.default_rng(1)
    ops = {b: jnp.asarray(rng.standard_normal(s), jnp.bfloat16)
           for b, s in expr.operand_shapes(point).items()}
    ref = check.reference(expr, ops)
    bf16 = ref.astype(jnp.bfloat16)
    assert check.rel_gap(bf16, ref) < 0.004
    assert check.rel_gap(check.reference(expr, ops, control=True), ref) > 0.02
    assert check.rel_gap(None, ref) == float("inf")


def test_seed32_takes_seeds_past_32_bits():
    assert check.seed32(2**40 + 3) != check.seed32(3)
    assert check.seed32(2**40 + 3) == check.seed32(2**40 + 3)
    assert 0 <= check.seed32(2**62) < 2**32
