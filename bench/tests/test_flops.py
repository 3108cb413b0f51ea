"""The FLOP and byte counts against XLA's own count, at a small size, on the CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import load_spec
from bench.kernels import ssd_scan

# one layer: XLA counts the body of the scan over layers once, whatever its trip count
SMALL = dict(n_layer=1, d_model=256, d_state=64, headdim=32, vocab_size=2048,
             chunk_size=64, dtype="float32", remat="none", ssd_kernel="jnp")


def _xla_flops(fn, *args):
    return float(jax.jit(fn).lower(*args).compile().cost_analysis()["flops"])


def _model(name, **sizes):
    spec = load_spec(name)
    return spec.model_module.Model(dict(spec.sizes, **sizes))


def test_mamba2_update_flops_match_xla():
    m = _model("mamba2_130m.paper_fed", **SMALL)
    w = m.init_weights(jax.random.PRNGKey(0))
    B, S = 2, 256
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S + 1), 0, m.V)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    xla = _xla_flops(jax.grad(m.program_loss), w, batch)
    ours = m.flops(B, S)["update"]
    assert 0.9 < ours / xla < 1.05, (ours, xla)
    fwd = _xla_flops(m.program_loss, w, batch)
    assert 0.9 < m.flops(B, S)["forward"] / fwd < 1.05, (m.flops(B, S)["forward"], fwd)


def test_ssd_scan_flops_match_xla():
    from repro.models.mamba2 import ssd_chunked

    B, S, H, P, N, Q = 2, 512, 4, 64, 128, 64
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(k[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (B, S, H)))
    A = -jnp.arange(1.0, H + 1.0)
    Bm, Cm = jax.random.normal(k[2], (B, S, N)), jax.random.normal(k[3], (B, S, N))
    xla = _xla_flops(lambda *a: ssd_chunked(*a, Q)[0], x, dt, A, Bm, Cm)
    ours = ssd_scan.flops(B=B, S=S, H=H, P=P, N=N, Q=Q)
    assert 0.9 < ours / xla < 1.05, (ours, xla)


def test_ssd_scan_bytes_are_the_kernels_operands():
    from repro.kernels.ssd_scan import _ssd_impl

    B, S, H, P, N, Q = 2, 256, 4, 64, 128, 64
    args = (jnp.zeros((B, S, H, P), jnp.bfloat16), jnp.zeros((B, S, H), jnp.float32),
            jnp.zeros((H,), jnp.float32), jnp.zeros((B, S, N), jnp.bfloat16),
            jnp.zeros((B, S, N), jnp.bfloat16))
    jaxpr = jax.make_jaxpr(lambda *a: _ssd_impl(*a, Q, True))(*args)
    calls = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    size = lambda v: int(np.prod(v.aval.shape)) * v.aval.dtype.itemsize
    moved = sum(size(v) for v in calls[0].invars) + sum(size(v) for v in calls[0].outvars)
    assert ssd_scan.bytes_moved(B=B, S=S, H=H, P=P, N=N, Q=Q, x_bytes=2, bc_bytes=2) == moved


def test_peaks_refuse_an_unknown_device():
    from bench import peaks

    assert peaks.for_kind("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.for_kind("cpu")
