"""The controls come out not correct under the cell's limits.

`bench.control` runs the reference in the program's place one precision step
below the configuration's (the control) and with half of each batch left
out, against the float32 reference, and reads the numbers a run compares.
On the chip it runs at the cell's own size; here, on the CPU, at a small one.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check as chk
from bench import control
from bench.tests.small import small_spec


@pytest.mark.parametrize("cell", ["mamba2_130m.paper_fed"])
def test_controls_are_not_correct(cell, capsys):
    spec = small_spec(cell)
    # the race and the window are not the reference's to fail
    limits = {k: float(v) for k, v in spec.traffic["limits"].items()
              if k not in ("race_faults", "window_compiles")}
    rows = control.readings(spec, ["control", "half_batch"], [20261018])
    assert {r["what"] for r in rows} == {"control", "half_batch"}
    for r in rows:
        assert not chk.verdict({k: r[k] for k in limits}, limits), r


def test_leaves_that_move_by_a_few_roundings_are_left_out():
    w = {"norm": jnp.ones((64,), jnp.bfloat16), "mat": jnp.full((64, 64), 0.03, jnp.bfloat16),
         "f32": jnp.ones((64,), jnp.float32)}
    units = chk.leaf_units(w)          # leaf order: f32, mat, norm
    np.testing.assert_array_equal(units, [2.0**-23, 2.0**-6 * 2.0**-7, 2.0**-7])
    # the norm moved by 3 bfloat16 units and its program reads 1/3 off: left out;
    # the others moved by far more and read 1 % off: held
    ref = np.array([1e-3, 0.5, 3 * 2.0**-7])
    prog = ref * np.array([1.01, 1.01, 2.0 / 3.0])
    v = chk.numbers(prog, prog, [1.0], ref, ref, [1.0], units)
    assert abs(v["change1_gap"] - 0.01) < 1e-9 and abs(v["change_gap"] - 0.01) < 1e-9
    v = chk.numbers(prog, prog, [1.0], ref, ref, [1.0], units * 0)
    assert v["change1_gap"] > 0.3
