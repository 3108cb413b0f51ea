"""The plain references agree with the program's models where both compute
in float32 (the CPU), at a small size: loss and every leaf's gradient.  The
replay of Algorithm 1, event by event with its snapshot ring in host memory,
agrees with a plain loop that keeps the ring on the device, and its compiled
step does not grow with the ring."""
import jax
import jax.numpy as jnp
import numpy as np
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


def _replay_setup(C):
    from bench import check as chk
    from bench import traffic as gen
    from bench.tests.small import small_spec

    spec = small_spec("mamba2_130m.paper_fed")
    d, n = spec.traffic["data"], int(spec.traffic["federation"]["n_clients"])
    m = spec.model_module.Model(spec.sizes)
    shards, ev = gen.make_data(d, n, m.V)
    starts = chk.batch_starts(int(d["data_seed"]), int(d["shard_rows"]), int(d["batch"]))
    return m, chk.Reference(m, shards, ev, int(d["batch"]), starts, C), n


def _events(slots, n, seed):
    from bench import race

    rng = np.random.default_rng(seed)
    T = len(slots)
    return race.Events(J=rng.integers(0, n, T), slot=np.asarray(slots), k=np.arange(T),
                       scale=rng.uniform(0.05, 0.2, T), faults=0, ties=0)


def _plain_replay(m, ref, w0, calls):
    """Algorithm 1 as a plain loop over events with the ring on the device."""
    from bench.check import OFFSET_BLOCK

    grad = jax.jit(jax.grad(m.ref_loss))
    tmap = jax.tree_util.tree_map
    outs, losses, w_in = [], [], w0
    for e in calls:
        w = tmap(lambda a: a.astype(jnp.float32), w_in)
        ring = [w] * ref.C
        for j, s, k, sc in zip(e.J, e.slot, e.k, e.scale):
            st = int(ref.starts[k % OFFSET_BLOCK])
            batch = {key: v[j, st:st + ref.rows] for key, v in ref.shards.items()}
            g = grad(ring[s], batch)
            w = tmap(lambda a, b: a - np.float32(sc) * b, w, g)
            ring[s] = w
        w_in = tmap(lambda a, b: a.astype(b.dtype), w, w_in)
        outs.append(w_in)
        losses.append(float(m.ref_loss(w_in, ref.ev)))
    return outs, losses


def test_replay_matches_a_plain_loop_with_the_ring_on_the_device():
    m, ref, n = _replay_setup(3)
    w0 = jax.device_get(m.init_weights(jax.random.PRNGKey(5)))
    # a slot read right after its write, one read twice across the call, one
    # read long after the start, and slots interleaved
    calls = [_events([0, 0, 1, 2, 1, 0, 2, 2], n, 1), _events([2, 1, 1, 0, 2, 0], n, 2)]
    got_w, got_l = ref.replay(w0, calls)
    want_w, want_l = _plain_replay(m, ref, w0, calls)
    for g, w in zip(got_w, want_w):
        for a, b in zip(jax.tree_util.tree_leaves(g), jax.tree_util.tree_leaves(w)):
            assert _rel(a, b) < 1e-6
    np.testing.assert_allclose(got_l, want_l, rtol=1e-6)
    # the replay moved the weights, so the match is not of two no-ops
    assert _rel(jax.tree_util.tree_leaves(got_w[-1])[0], jax.tree_util.tree_leaves(w0)[0]) > 1e-4


def test_event_step_memory_does_not_grow_with_the_ring():
    sizes = {}
    for C in (2, 32):
        m, ref, n = _replay_setup(C)
        w0 = m.init_weights(jax.random.PRNGKey(5))
        ref.replay(w0, [_events([C - 1, 0, C - 1], n, 3)])
        sizes[C] = ref.step_bytes()
    assert sizes[2] == sizes[32]
    assert sizes[2]["total"] == (sizes[2]["argument"] + sizes[2]["output"] - sizes[2]["alias"]
                                 + sizes[2]["temp"]) > 0
