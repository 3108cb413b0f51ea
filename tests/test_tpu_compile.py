"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

Each test lowers one kernel at the widths of a supported model and compiles
it for device 0 of a described (not attached) ``v5e:2x2`` topology with the
TPU compiler that ships with jaxlib.  Nothing runs: these tests catch what
interpret mode cannot — block shapes Mosaic refuses, kernels that exceed
VMEM — before any chip time is spent.  ``interpret=False`` is passed
explicitly because the platform check in `repro.kernels.ops` sees the CPU.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU compiler library.
"""
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core.engine_scan import make_fused_runner
from repro.kernels.flash_attention import flash_attention
from repro.kernels.moe_gmm import moe_gmm
from repro.kernels.ssd_scan import ssd_scan
from repro.kernels.weighted_update import (
    BLOCK_TILE,
    block_prefix_update,
    block_scatter_rows,
    weighted_update,
)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler library in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled(fn, *specs):
    # a compile for a described device lands in the persistent cache but can
    # never be read back without the chip — keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        return jax.jit(fn).lower(*specs).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


def _compile_for_chip(fn, *specs):
    hlo = _compiled(fn, *specs).as_text()
    assert "tpu_custom_call" in hlo
    return hlo


def _mamba2_130m_padded_params() -> int:
    """Flat-packed Mamba2-130M parameter count, padded to the block tile."""
    from repro.configs import get_config
    from repro.models import api
    from repro.models.module import abstract_params

    leaves = jax.tree_util.tree_leaves(
        abstract_params(api.model_meta(get_config("mamba2-130m")))
    )
    P = sum(int(np.prod(l.shape)) for l in leaves)
    return -(-P // BLOCK_TILE) * BLOCK_TILE


def test_ssd_scan_mamba2_130m(one_chip):
    # Mamba2-130M: 24 SSD heads of dim 64, state 128, chunk 64, seq 2048
    B, S, H, P, N = 1, 2048, 24, 64, 128
    _compile_for_chip(
        lambda x, dt, A, Bm, Cm: ssd_scan(
            x, dt, A, Bm, Cm, chunk=64, interpret=False
        ),
        _spec((B, S, H, P), jnp.bfloat16, one_chip),
        _spec((B, S, H), jnp.float32, one_chip),
        _spec((H,), jnp.float32, one_chip),
        _spec((B, S, N), jnp.bfloat16, one_chip),
        _spec((B, S, N), jnp.bfloat16, one_chip),
    )


@pytest.mark.parametrize(
    "dtype,precision", [(jnp.bfloat16, None), (jnp.float32, "highest")]
)
def test_ssd_scan_lane_vmapped_grad_mamba2_130m(one_chip, dtype, precision):
    # the blocked engine differentiates its lanes under vmap; the loss value
    # is kept so the forward kernel is not dead code next to the jnp VJP
    L, B, S, H, P, N = 2, 4, 2048, 24, 64, 128

    def loss(x, dt, A, Bm, Cm):
        y, st = ssd_scan(x, dt, A, Bm, Cm, chunk=64, interpret=False)
        return jnp.sum(y.astype(jnp.float32)) + jnp.sum(st)

    grad = jax.vmap(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)))
    with jax.default_matmul_precision(precision):
        _compile_for_chip(
            grad,
            _spec((L, B, S, H, P), dtype, one_chip),
            _spec((L, B, S, H), jnp.float32, one_chip),
            _spec((L, H), jnp.float32, one_chip),
            _spec((L, B, S, N), dtype, one_chip),
            _spec((L, B, S, N), dtype, one_chip),
        )


def test_flash_attention_granite_3_2b(one_chip):
    # Granite-3-2B: 32 query heads, 8 KV heads (GQA), head dim 64
    B, S, H, K, D = 1, 2048, 32, 8, 64
    _compile_for_chip(
        lambda q, k, v: flash_attention(q, k, v, causal=True, interpret=False),
        _spec((B, S, H, D), jnp.bfloat16, one_chip),
        _spec((B, S, K, D), jnp.bfloat16, one_chip),
        _spec((B, S, K, D), jnp.bfloat16, one_chip),
    )


def test_moe_gmm_qwen2_moe(one_chip):
    # Qwen2-MoE-A2.7B expert FFN: d_model 2048 -> d_ff_expert 1408
    E, C, D, F = 8, 512, 2048, 1408
    _compile_for_chip(
        lambda x, w: moe_gmm(x, w, interpret=False),
        _spec((E, C, D), jnp.bfloat16, one_chip),
        _spec((E, D, F), jnp.bfloat16, one_chip),
    )


@pytest.mark.parametrize("momentum", [0.0, 0.9])
def test_weighted_update(one_chip, momentum):
    shape = (4096, 1024)
    m = _spec(shape, jnp.float32, one_chip) if momentum else None
    _compile_for_chip(
        lambda w, g, s, m: weighted_update(
            w, g, s, m=m, momentum=momentum, interpret=False
        ),
        _spec(shape, jnp.float32, one_chip),
        _spec(shape, jnp.float32, one_chip),
        _spec((), jnp.float32, one_chip),
        m,
    )


@pytest.mark.parametrize("kernel", [block_prefix_update, block_scatter_rows])
def test_block_kernels_mamba2_130m_ring(one_chip, kernel):
    # fp32-packed Mamba2-130M snapshot ring, C = 8 (R = C + 1 rows), E = 8
    P, R, E = _mamba2_130m_padded_params(), 9, 8
    _compile_for_chip(
        lambda snaps, w, D, slots: kernel(snaps, w, D, slots, interpret=False),
        _spec((R, P), jnp.float32, one_chip),
        _spec((P,), jnp.float32, one_chip),
        _spec((E, P), jnp.float32, one_chip),
        _spec((E,), jnp.int32, one_chip),
    )


def test_snapshot_ring_per_leaf_mamba2_130m(one_chip):
    # the per-event fused runner over Mamba2-130M's mixed bf16/fp32 tree,
    # cut to 2 layers at the published widths; a stand-in gradient touches
    # every leaf (the model step is not what this test compiles)
    from repro.configs import get_config
    from repro.models import api
    from repro.models.module import abstract_params

    n, C, T = 100, 10, 8
    tree = abstract_params(
        api.model_meta(get_config("mamba2-130m").replace(num_layers=2))
    )
    leaves = jax.tree_util.tree_leaves(tree)
    assert {l.dtype for l in leaves} == {jnp.dtype(jnp.bfloat16),
                                         jnp.dtype(jnp.float32)}
    P = sum(int(np.prod(l.shape)) for l in leaves)

    def grad(j, w, k):
        f = 1.0 + j.astype(jnp.float32) * 1e-3 + k.astype(jnp.float32) * 1e-4
        return jax.tree_util.tree_map(
            lambda x: (x.astype(jnp.float32) * f).astype(x.dtype), w
        )

    def eval_fn(w):
        return sum(jnp.sum(x.astype(jnp.float32))
                   for x in jax.tree_util.tree_leaves(w))

    run = make_fused_runner(grad, n, C, T, eval_fn=eval_fn, eval_every=T)
    compiled = _compiled(
        run,
        jax.tree_util.tree_map(
            lambda x: _spec(x.shape, x.dtype, one_chip), tree
        ),
        _spec((n,), jnp.float32, one_chip),
        _spec((n,), jnp.float32, one_chip),
        _spec((2,), jnp.uint32, one_chip),
        _spec((), jnp.float32, one_chip),
    )
    hlo = compiled.as_text()
    # no flat ring, no packed gradient or row
    assert f"[{C},{P}]" not in hlo and f"[{P}]" not in hlo
    # each leaf's ring buffer: C leads, outside the two tiled minor dims
    layouts = {}
    for dims, mtm in re.findall(r"\[(\d+(?:,\d+)+)\]\{([\d,]+)", hlo):
        layouts.setdefault(tuple(map(int, dims.split(","))), set()).add(mtm)
    for l in leaves:
        row = (1,) * (2 - len(l.shape)) + tuple(l.shape)
        found = layouts.get((C,) + row)
        assert found, f"no ring buffer of shape {(C,) + row}"
        for mtm in found:
            order = [int(d) for d in mtm.split(",")]
            assert order[-1] == 0 and 0 not in order[:2], (row, mtm)
    # the flat ring alone, C padded to the tile's 8 rows, in float32
    flat_ring_bytes = -(-C // 8) * 8 * P * 4
    assert compiled.memory_analysis().temp_size_in_bytes < flat_ring_bytes
