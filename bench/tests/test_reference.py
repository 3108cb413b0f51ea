"""The plain references agree with the program's models where both compute
in float32 (the CPU), at a small size: loss and every leaf's gradient."""
import jax
import jax.numpy as jnp
import pytest

from bench.harness import load_spec

SMALL = dict(n_layer=2, d_model=64, d_state=16, headdim=16, vocab_size=512,
             chunk_size=16, dtype="float32")


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b), 1e-30))


def _agree(m, batch, tol):
    w = m.init_weights(jax.random.PRNGKey(3))
    m.check_structure(w)
    assert abs(float(m.program_loss(w, batch)) - float(m.ref_loss(w, batch))) < tol
    gp, gr = jax.grad(m.program_loss)(w, batch), jax.grad(m.ref_loss)(w, batch)
    for a, b in zip(jax.tree_util.tree_leaves(gp), jax.tree_util.tree_leaves(gr)):
        assert _rel(a, b) < tol


@pytest.mark.parametrize("kernel", ["pallas", "jnp"])
def test_mamba2_reference_matches_program(kernel):
    spec = load_spec("mamba2_130m.paper_fed")
    m = spec.model_module.Model(dict(spec.sizes, **SMALL, ssd_kernel=kernel))
    toks = jax.random.randint(jax.random.PRNGKey(4), (2, 65), 0, 512)
    _agree(m, {"tokens": toks[:, :-1], "labels": toks[:, 1:]}, 1e-4)


def test_control_precision_departs_from_float32():
    spec = load_spec("mamba2_130m.paper_fed")
    m = spec.model_module.Model(dict(spec.sizes, **SMALL))
    w = m.init_weights(jax.random.PRNGKey(3))
    toks = jax.random.randint(jax.random.PRNGKey(4), (2, 65), 0, 512)
    b = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    g32 = jax.grad(m.ref_loss)(w, b)
    g8 = jax.grad(lambda ww: m.ref_loss(ww, b, "float8_e4m3"))(w)
    worst = max(_rel(a, c) for a, c in zip(jax.tree_util.tree_leaves(g8), jax.tree_util.tree_leaves(g32)))
    assert worst > 1e-2
