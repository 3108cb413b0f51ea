"""The per-event engine's snapshot ring: one (C, *leaf.shape) buffer per leaf.

A mixed bfloat16/float32 parameter tree runs through the per-event fused
runner and the host replay, and both are compared with a plain Python loop
over the same events: a float32 master per leaf, C float32 snapshot rows,
the gradient taken at a row cast back to each leaf's dtype, and no write for
an event on slot C.  The fused runner's events are exported from the same
key by `stream_device.generate_stream`.  Also: a slot-C write leaves every
ring row unchanged while an in-range write touches its row alone, and the
serving read path's row mean is the mean over all P parameters of the
known-good row.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import (
    KIND_COMPLETE,
    FaultConfig,
    GuardConfig,
    ServingConfig,
    generate_stream,
    make_fused_runner,
    make_runner,
)
from repro.core import engine_scan as es
from repro.core.serving import _row_mean

N, C, T, ETA = 6, 3, 200, 0.05
MU = np.linspace(0.5, 2.0, N)
P_UNIFORM = np.full(N, 1 / N)
FAULT = FaultConfig(off_rate=0.3, on_rate=1.0, crash_rate=0.1, timeout_rate=0.2)
GUARD = GuardConfig(max_grad_norm=200.0)

_rng = np.random.default_rng(0)
W0 = {
    "w": jnp.asarray(_rng.normal(size=(4, 3)), jnp.bfloat16),
    "stack": jnp.asarray(_rng.normal(size=(2, 2, 3)), jnp.bfloat16),
    "norm": jnp.asarray(_rng.normal(size=(3,)), jnp.float32),
    "bias": jnp.asarray(0.5, jnp.float32),
}
TARG = jnp.asarray(_rng.normal(size=(N,)), jnp.float32)


@jax.jit
def _grad(j, w, k):
    """Pulls every leaf towards client j's target."""
    return jax.tree_util.tree_map(
        lambda x: (x.astype(jnp.float32) - TARG[j]).astype(x.dtype), w
    )


@jax.jit
def _spiky_grad(j, w, k):
    """`_grad`, exploding at every 40th step and NaN at step 150: what the
    guard must reject."""
    spike = jnp.where(k % 40 == 39, 1e6, 0.0) + jnp.where(k == 150, jnp.nan, 0.0)
    return jax.tree_util.tree_map(
        lambda x: (x.astype(jnp.float32) + spike).astype(x.dtype), _grad(j, w, k)
    )


def _leaves(w):
    return jax.tree_util.tree_leaves(w)


def _reference(grad, stream, scale, guard=None):
    """Algorithm 1 over ``stream`` as a plain loop."""
    treedef = jax.tree_util.tree_structure(W0)
    dtypes = [x.dtype for x in _leaves(W0)]
    master = [np.asarray(x, np.float32) for x in _leaves(W0)]
    ring = [list(master) for _ in range(C)]
    rejects = 0
    for k, (j, s, sc) in enumerate(zip(stream.J, stream.slot, scale)):
        row = ring[min(s, C - 1)]  # slot C's gradient is discarded anyway
        w_disp = treedef.unflatten([jnp.asarray(x, d) for x, d in zip(row, dtypes)])
        g = [np.asarray(x, np.float32) for x in _leaves(grad(j, w_disp, k))]
        sq = sum(float(np.sum(np.square(x, dtype=np.float64))) for x in g)
        bad = guard is not None and not (
            np.isfinite(sq) and sq <= guard.max_grad_norm ** 2
        )
        rejects += int(bad and sc != 0)
        if not bad:
            master = [(m - np.float32(sc) * x).astype(np.float32)
                      for m, x in zip(master, g)]
        if s < C:
            ring[s] = list(master)
    out = treedef.unflatten([jnp.asarray(x, d) for x, d in zip(master, dtypes)])
    return out, rejects


def _assert_matches(w, w_ref):
    for a, b in zip(_leaves(w), _leaves(w_ref)):
        assert a.dtype == b.dtype and a.shape == b.shape
        # the float32 masters agree to rounding; a bfloat16 leaf is that
        # master rounded, so it may differ by one unit in the last place
        rtol = 2.0 ** -7 if a.dtype == jnp.bfloat16 else 0.0
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=1e-5, rtol=rtol,
        )


def _events(key, fault):
    stream = generate_stream(MU, P_UNIFORM, C, T, seed=key, fault=fault)
    scale = np.full(T, ETA, np.float32)
    if stream.kind is not None:
        scale = np.where(stream.kind == KIND_COMPLETE, scale, 0.0)
    return stream, scale.astype(np.float32)


CASES = {
    "plain": dict(fault=None, guard=None),
    "faults": dict(fault=FAULT, guard=None),
    "guard": dict(fault=None, guard=GUARD),
    "faults_guard": dict(fault=FAULT, guard=GUARD),
}


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("path", ["fused", "host"])
def test_per_event_matches_reference(path, case):
    fault, guard = CASES[case]["fault"], CASES[case]["guard"]
    grad = _grad if guard is None else _spiky_grad
    key = jax.random.PRNGKey(11)
    stream, scale = _events(key, fault)
    if fault is not None:
        assert (stream.slot == C).any()  # the stream does emit slot C
    if path == "fused":
        runner = jax.jit(make_fused_runner(
            grad, N, C, T, weighting="plain", fault=fault, guard=guard,
            collect_extras=False,
        ))
        w, _, extras = runner(W0, jnp.asarray(MU, jnp.float32),
                              jnp.asarray(P_UNIFORM, jnp.float32), key, ETA)
        rejects = None if guard is None else int(extras["guard_rejects"])
    else:
        runner = jax.jit(make_runner(grad, C, guard=guard))
        out = runner(W0, jnp.asarray(stream.J), jnp.asarray(stream.slot),
                     jnp.asarray(scale))
        w = out[0]
        rejects = None if guard is None else int(out[2][0])
    w_ref, rejects_ref = _reference(grad, stream, scale, guard)
    assert all(np.isfinite(np.asarray(x, np.float32)).all() for x in _leaves(w))
    _assert_matches(w, w_ref)
    if guard is not None:
        assert rejects == rejects_ref > 0


def _distinct_ring(drop):
    """A per-leaf ring of C rows, row r holding W0 + r, and its step."""
    ring = es._leaf_ring(W0, drop=drop)
    carry, _ = es._init_update_carry(W0, C, ring, True, 0)
    w, snaps, acc, gcnt = carry
    for r in range(C):
        snaps = ring.write(snaps, r, jax.tree_util.tree_map(lambda x: x + r, w))
    step = es._make_update_step(_grad, 0, None, ring, True)
    return ring, (w, snaps, acc, gcnt), step


def test_slot_c_write_leaves_every_row_unchanged():
    ring, carry, step = _distinct_ring(drop=True)
    out = step(carry, jnp.int32(0), jnp.int32(C), jnp.float32(0.0), jnp.int32(5))
    for a, b in zip(_leaves(carry[1]), _leaves(out[1])):
        assert a.shape[0] == C
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("drop", [False, True])
def test_write_touches_its_row_alone(drop):
    ring, carry, step = _distinct_ring(drop)
    s = 1
    out = step(carry, jnp.int32(2), jnp.int32(s), jnp.float32(ETA), jnp.int32(5))
    for w_new, before, after in zip(_leaves(out[0]), _leaves(carry[1]),
                                    _leaves(out[1])):
        before, after = np.asarray(before), np.asarray(after)
        for r in range(C):
            if r == s:
                np.testing.assert_array_equal(
                    after[r], np.asarray(w_new).reshape(after[r].shape))
            else:
                np.testing.assert_array_equal(after[r], before[r])
    # the read casts each leaf back to its own dtype and shape
    back = ring.read(out[1], s)
    for a, b in zip(_leaves(back), _leaves(W0)):
        assert a.dtype == b.dtype and a.shape == b.shape


def test_row_mean_is_the_mean_over_all_parameters():
    ring, (w, snaps, _, _), _ = _distinct_ring(drop=False)
    for s in range(C):
        flat = np.concatenate([
            np.asarray(x, np.float32).ravel() + s for x in _leaves(w)
        ])
        np.testing.assert_allclose(
            float(_row_mean(snaps, jnp.int32(s))), flat.mean(), rtol=1e-6
        )


def test_served_checksum_reads_the_known_good_row():
    """With eta = 0 every row holds W0, so each served request reads the
    mean of W0 over all P parameters."""
    serving = ServingConfig(arrival_rate=2.0, serve_rate=4.0, queue_cap=8)
    runner = jax.jit(make_fused_runner(
        _spiky_grad, N, C, 400, weighting="plain", serving=serving, guard=GUARD,
    ))
    w, _, extras = runner(W0, jnp.asarray(MU, jnp.float32),
                          jnp.asarray(P_UNIFORM, jnp.float32),
                          jax.random.PRNGKey(4), 0.0)
    served = int(extras["serve_served"])
    assert served > 0
    mean0 = np.concatenate(
        [np.asarray(x, np.float32).ravel() for x in _leaves(W0)]).mean()
    np.testing.assert_allclose(float(extras["serve_checksum"]),
                               served * mean0, rtol=1e-5)
    for a, b in zip(_leaves(w), _leaves(W0)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
