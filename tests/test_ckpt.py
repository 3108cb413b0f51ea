"""Checkpoint codec + engine kill-and-resume tests.

Two layers:

1. ``repro.ckpt.checkpoint`` codec round-trips: mixed-dtype trees (incl.
   the bf16 uint-view encoding), NamedTuple treedefs, rotation bookkeeping
   and user metadata.
2. ``repro.core.engine_ckpt`` resume semantics: truncating the checkpoint
   directory to an intermediate step and re-running with ``resume=True``
   must reproduce the uninterrupted run *bitwise* — across device/host
   streams, per-event/blocked paths and fp32/bf16 snapshot rings — and a
   genuinely SIGKILLed process (slow test) must resume the same way.
"""
import os
import shutil
import signal
import subprocess
import sys
import textwrap
from typing import NamedTuple

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.ckpt import checkpoint as ck
from repro.core import (
    FaultConfig,
    GuardConfig,
    SimConfig,
    blocked_inputs,
    export_blocks,
    export_stream,
    run_checkpointed,
    run_checkpointed_host,
    run_checkpointed_host_blocked,
    step_scales,
)
from repro.core import engine_scan as es

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")


def _bits(tree):
    """Concatenate all leaves as raw bytes (bitwise comparison helper)."""
    return np.concatenate(
        [np.asarray(x).ravel().view(np.uint8) for x in jax.tree_util.tree_leaves(tree)]
    )


def _zeros_like(tree):
    return jax.tree_util.tree_map(lambda x: jnp.zeros_like(x), tree)


# ---------------------------------------------------------------------------
# codec round-trips
# ---------------------------------------------------------------------------


class _Inner(NamedTuple):
    m: jnp.ndarray
    v: jnp.ndarray


class _Outer(NamedTuple):
    w: dict
    opt: _Inner
    step: jnp.ndarray


def test_roundtrip_mixed_dtypes(tmp_path):
    tree = {
        "f32": jnp.linspace(-3.0, 7.0, 11, dtype=jnp.float32),
        "bf16": jnp.linspace(-2.0, 2.0, 9).astype(jnp.bfloat16),
        "i32": jnp.arange(-4, 4, dtype=jnp.int32),
        "nested": (jnp.ones((2, 3), jnp.float32), {"u": jnp.zeros(5, jnp.int32)}),
    }
    ck.save(str(tmp_path), 7, tree)
    back = ck.restore(str(tmp_path), 7, _zeros_like(tree))
    for a, b in zip(jax.tree_util.tree_leaves(tree), jax.tree_util.tree_leaves(back)):
        assert np.asarray(a).dtype == np.asarray(b).dtype
    assert (_bits(tree) == _bits(back)).all()


def test_roundtrip_namedtuple_tree(tmp_path):
    tree = _Outer(
        w={"a": jnp.full(4, 1.5, jnp.bfloat16)},
        opt=_Inner(m=jnp.ones(3, jnp.float32), v=jnp.full(3, 0.25, jnp.float32)),
        step=jnp.asarray(17, jnp.int32),
    )
    ck.save(str(tmp_path), 3, tree)
    back = ck.restore(str(tmp_path), 3, _zeros_like(tree))
    assert isinstance(back, _Outer) and isinstance(back.opt, _Inner)
    assert (_bits(tree) == _bits(back)).all()


def test_bf16_codec_is_bitwise_exact(tmp_path):
    # adversarial bit patterns: every exponent, NaN payloads, signed zeros.
    # An astype(float32) round-trip would normalize some of these; the
    # uint16-view codec must preserve them verbatim.
    bits = np.arange(0, 1 << 16, 7, dtype=np.uint16)
    arr = jnp.asarray(bits).view(jnp.bfloat16)
    ck.save(str(tmp_path), 1, {"x": arr})
    back = ck.restore(str(tmp_path), 1, {"x": jnp.zeros_like(arr)})
    assert np.asarray(back["x"]).dtype == np.asarray(arr).dtype
    assert (np.asarray(back["x"]).view(np.uint16) == bits).all()


def test_rotation_latest_and_metadata(tmp_path):
    tree = {"x": jnp.arange(3, dtype=jnp.float32)}
    for s in (10, 20, 30, 40):
        ck.save(str(tmp_path), s, tree, metadata={"step": s, "tag": "t"}, keep=3)
    assert ck.available_steps(str(tmp_path)) == [20, 30, 40]
    assert ck.latest_step(str(tmp_path)) == 40
    meta = ck.load_metadata(str(tmp_path), 30)
    assert meta["step"] == 30 and meta["tag"] == "t"


# ---------------------------------------------------------------------------
# truncate-and-resume bitwise across engine paths
# ---------------------------------------------------------------------------

_N, _C, _T = 8, 4, 200
_MU = np.linspace(0.5, 2.0, _N).astype(np.float32)
_P = np.full(_N, 1 / _N, np.float32)
_W0 = {"a": jnp.zeros(6, jnp.float32), "b": jnp.ones(3, jnp.float32)}
_TARG = jnp.arange(_N, dtype=jnp.float32)
_FAULT = FaultConfig(off_rate=0.3, on_rate=1.0, crash_rate=0.1, timeout_rate=0.2)


def _grad(j, w, k):
    return jax.tree_util.tree_map(lambda x: x - _TARG[j], w)


def _loss(w):
    return sum(jnp.sum(x**2) for x in jax.tree_util.tree_leaves(w))


def _truncate(d, keep_step):
    for s in ck.available_steps(d):
        if s > keep_step:
            shutil.rmtree(os.path.join(d, f"step_{s:010d}"))


def _host_arrays():
    cfg = SimConfig(mu=_MU, p=_P, C=_C, T=_T, seed=5, fault=_FAULT)
    stream = export_stream(cfg)
    scale = es.step_scales(stream, 0.05, _P, "importance")
    return cfg, stream, scale


def _run_fused_f32(d, resume):
    return run_checkpointed(
        _grad, _N, _C, _T, w0=_W0, mu=_MU, p0=_P, key=jax.random.PRNGKey(3),
        eta=0.05, ckpt_dir=d, ckpt_every=50, eval_fn=_loss, eval_every=25,
        adaptive=True, refresh_every=25, fault=_FAULT,
        guard=GuardConfig(max_grad_norm=100.0, stale_cutoff=50), resume=resume,
    )


def _run_fused_bf16_blocked(d, resume):
    return run_checkpointed(
        _grad, _N, _C, _T, w0=_W0, mu=_MU, p0=_P, key=jax.random.PRNGKey(3),
        eta=0.05, ckpt_dir=d, ckpt_every=50, eval_fn=_loss, eval_every=50,
        block_size=8, snapshot_dtype=jnp.bfloat16, fault=_FAULT,
        guard=GuardConfig(max_grad_norm=100.0, stale_cutoff=50), resume=resume,
    )


def _run_host_bf16(d, resume):
    _, stream, scale = _host_arrays()
    return run_checkpointed_host(
        _grad, _C, _W0, stream.J, stream.slot, scale,
        ckpt_dir=d, ckpt_every=50, eval_fn=_loss, eval_every=25,
        guard=GuardConfig(max_grad_norm=100.0), snapshot_dtype=jnp.bfloat16,
        resume=resume,
    )


def _run_host_blocked(d, resume):
    cfg, stream, scale = _host_arrays()
    blocks = export_blocks(cfg, block_size=8, cut_every=50)
    J, slot, sc, k, mask, cb, nc = blocked_inputs(blocks, scale, eval_every=50)
    return run_checkpointed_host_blocked(
        _grad, _C, 8, _W0, J, slot, sc, k, mask,
        group_events=50, chunk_blocks=cb, n_chunks=nc,
        ckpt_dir=d, ckpt_every=50, eval_fn=_loss,
        guard=GuardConfig(max_grad_norm=100.0), resume=resume,
    )


_PATHS = {
    "fused_f32": _run_fused_f32,
    "fused_bf16_blocked": _run_fused_bf16_blocked,
    "host_bf16": _run_host_bf16,
    "host_blocked": _run_host_blocked,
}


@pytest.mark.parametrize("path", sorted(_PATHS))
def test_truncate_and_resume_bitwise(tmp_path, path):
    run = _PATHS[path]
    d = str(tmp_path / path)
    full = run(d, False)
    _truncate(d, 100)
    res = run(d, True)
    assert (_bits(full[0]) == _bits(res[0])).all()
    ef, er = np.asarray(full[1]), np.asarray(res[1])
    assert ef.shape == er.shape and (ef == er).all()


def test_resume_from_final_checkpoint_is_noop(tmp_path):
    # cursor == T in the latest checkpoint: resume must not re-run the tail.
    d = str(tmp_path / "final")
    full = _run_host_bf16(d, False)
    res = _run_host_bf16(d, True)
    assert (_bits(full[0]) == _bits(res[0])).all()
    assert (np.asarray(full[1]) == np.asarray(res[1])).all()


def test_resume_fingerprint_mismatch_raises(tmp_path):
    d = str(tmp_path / "fp")
    _, stream, scale = _host_arrays()
    kwargs = dict(ckpt_dir=d, ckpt_every=50, eval_fn=_loss, eval_every=25,
                  snapshot_dtype=jnp.bfloat16)
    run_checkpointed_host(
        _grad, _C, _W0, stream.J, stream.slot, scale,
        guard=GuardConfig(max_grad_norm=100.0), resume=False, **kwargs)
    with pytest.raises(ValueError, match="fingerprint"):
        run_checkpointed_host(
            _grad, _C, _W0, stream.J, stream.slot, scale,
            guard=GuardConfig(max_grad_norm=99.0), resume=True, **kwargs)


def _write_flat_ring_checkpoint(d, step=50):
    """A checkpoint as the per-event checkpointed runs wrote it while the
    snapshot ring was one flat (C, P) array: a packed master vector, the
    (C, P) ring, and no ring layout in its metadata."""
    P = sum(int(np.size(x)) for x in jax.tree_util.tree_leaves(_W0))
    carry = (np.zeros(P, np.float32), np.zeros((_C, P), np.float32), (),
             np.zeros(2, np.int32))
    ck.save(d, step, {"carry": carry, "evals": np.full(8, np.nan, np.float32),
                      "cursor": np.int64(step)},
            metadata={"fingerprint": "0" * 40, "events_done": step})


@pytest.mark.parametrize("path", ["fused", "host"])
def test_resume_refuses_flat_ring_checkpoint(tmp_path, path):
    d = str(tmp_path / path)
    _write_flat_ring_checkpoint(d)
    if path == "fused":
        run = lambda: run_checkpointed(
            _grad, _N, _C, _T, w0=_W0, mu=_MU, p0=_P,
            key=jax.random.PRNGKey(3), eta=0.05, ckpt_dir=d, ckpt_every=50,
            eval_fn=_loss, eval_every=25, resume=True,
        )
    else:
        _, stream, scale = _host_arrays()
        run = lambda: run_checkpointed_host(
            _grad, _C, _W0, stream.J, stream.slot, scale, ckpt_dir=d,
            ckpt_every=50, eval_fn=_loss, eval_every=25, resume=True,
        )
    with pytest.raises(ValueError, match=r"one flat \(C, P\) array"):
        run()


def test_run_experiment_resume_bitwise(tmp_path):
    from repro.configs.base import FLConfig
    from repro.fl.engine import run_experiment

    flc = FLConfig(n_clients=8, concurrency=4, server_steps=120, seed=1,
                   engine="scan")
    fault = FaultConfig(off_rate=0.2, on_rate=1.0, crash_rate=0.05,
                        timeout_rate=0.1)
    guard = GuardConfig(max_grad_norm=1e3, stale_cutoff=80)
    d = str(tmp_path / "fl")
    r1 = run_experiment(flc, "gen_async", eval_every=60, faults=fault,
                        guard=guard, ckpt_dir=d, ckpt_every=60)
    _truncate(d, 60)
    r2 = run_experiment(flc, "gen_async", eval_every=60, faults=fault,
                        guard=guard, ckpt_dir=d, ckpt_every=60, resume=True)
    assert (_bits(r1.final_params) == _bits(r2.final_params)).all()


# ---------------------------------------------------------------------------
# background-writer failure surfacing (_AsyncSaver)
# ---------------------------------------------------------------------------


def test_async_saver_unwritable_dir_raises_and_reaps(tmp_path, monkeypatch):
    # A read-only checkpoint dir (EROFS; the test injects the failure at
    # the save layer because the suite may run as root, which chmod does
    # not stop).  The background writer captures the OSError; the driver
    # must surface it at the next put()/close() AND reap the worker thread
    # — the regression was a writer thread leaked forever on the queue
    # when put() raised.
    import threading

    from repro.core import engine_ckpt as ec

    def boom(*a, **k):
        raise OSError(30, "Read-only file system")

    monkeypatch.setattr(ec, "_save_state", boom)
    before = threading.active_count()
    with pytest.raises(OSError, match="Read-only file system"):
        _run_fused_f32(str(tmp_path / "ro"), False)
    assert threading.active_count() == before  # no leaked writer thread


def test_async_saver_abort_idempotent(tmp_path, monkeypatch):
    import time

    from repro.core import engine_ckpt as ec

    def boom(*a, **k):
        raise OSError(28, "No space left on device")

    monkeypatch.setattr(ec, "_save_state", boom)
    saver = ec._AsyncSaver(str(tmp_path), "fp", keep=2)
    saver.put(1, {"x": np.zeros(2)}, np.zeros(1))
    for _ in range(200):  # wait for the worker to capture the failure
        if saver._err is not None:
            break
        time.sleep(0.01)
    assert saver._err is not None
    with pytest.raises(OSError, match="No space left"):
        saver.put(2, {"x": np.zeros(2)}, np.zeros(1))
    assert not saver._worker.is_alive()  # put() reaped it before raising
    saver.abort()  # idempotent after the reap
    saver.abort()
    with pytest.raises(OSError, match="No space left"):
        saver.close()  # close still surfaces the captured error


# ---------------------------------------------------------------------------
# true kill-and-resume: child process SIGKILLs itself mid-run
# ---------------------------------------------------------------------------

_CHILD = textwrap.dedent("""
    import os, signal, sys
    sys.path.insert(0, {src!r})
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.ckpt import checkpoint as ck
    from repro.core import FaultConfig, GuardConfig, run_checkpointed

    n_saves = [0]
    _orig_save = ck.save

    def killing_save(*args, **kwargs):
        _orig_save(*args, **kwargs)
        n_saves[0] += 1
        if n_saves[0] == 2:
            os.kill(os.getpid(), signal.SIGKILL)

    ck.save = killing_save

    targ = jnp.arange(8, dtype=jnp.float32)
    def grad(j, w, k):
        return jax.tree_util.tree_map(lambda x: x - targ[j], w)

    run_checkpointed(
        grad, 8, 4, 200,
        w0={{"a": jnp.zeros(6, jnp.float32), "b": jnp.ones(3, jnp.float32)}},
        mu=np.linspace(0.5, 2.0, 8).astype(np.float32),
        p0=np.full(8, 1 / 8, np.float32), key=jax.random.PRNGKey(3), eta=0.05,
        ckpt_dir=sys.argv[1], ckpt_every=50,
        fault=FaultConfig(off_rate=0.3, on_rate=1.0, crash_rate=0.1,
                          timeout_rate=0.2),
        guard=GuardConfig(max_grad_norm=100.0, stale_cutoff=50))
    raise SystemExit("child survived past the kill point")
""")


@pytest.mark.slow
def test_sigkill_mid_run_then_resume_bitwise(tmp_path):
    d_kill = str(tmp_path / "killed")
    d_ref = str(tmp_path / "reference")
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD.format(src=SRC), d_kill],
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == -signal.SIGKILL, (proc.returncode, proc.stderr)
    steps = ck.available_steps(d_kill)
    assert steps and max(steps) < 200, steps  # died mid-run with real ckpts

    def run(d, resume):
        return run_checkpointed(
            _grad, _N, _C, _T, w0=_W0, mu=_MU, p0=_P,
            key=jax.random.PRNGKey(3), eta=0.05, ckpt_dir=d, ckpt_every=50,
            fault=_FAULT,
            guard=GuardConfig(max_grad_norm=100.0, stale_cutoff=50),
            resume=resume)

    resumed = run(d_kill, True)
    reference = run(d_ref, False)
    assert (_bits(resumed[0]) == _bits(reference[0])).all()
