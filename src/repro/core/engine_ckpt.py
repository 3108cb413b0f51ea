"""Checkpointed engine drivers: kill-and-resume with a bitwise guarantee.

The scan engines of `engine_scan` run as one compiled call — a SIGKILL loses
everything.  This module drives the same per-event / blocked machinery
chunk-at-a-time from the host and snapshots the COMPLETE carry at chunk
boundaries through `repro.ckpt.checkpoint`:

  * the parameter vector, the snapshot ring buffer (fp32 or the bf16 codec —
    stored bit-exactly via the checkpoint layer's uint view), the FedBuff
    accumulator and the guard counters (``ucarry``),
  * the closed-network `StreamState` (occupancy, FIFO ring, head/tail
    cursors, clock, availability) and `StatsState` (rate accumulators the
    adaptive controller feeds on),
  * the controller state: sampling vector p, its dispatch CDF and the
    per-slot dispatch-time importance scales,
  * the eval curve so far and the event cursor.

Determinism contract: the fused driver derives each chunk's uniforms from
the run key via ``jax.random.fold_in(key_part, chunk_index)`` — nothing
depends on *when* a chunk executes, so restoring the latest checkpoint and
continuing reproduces the uninterrupted checkpointed run bit for bit (the
host drivers replay pre-simulated event arrays, which are deterministic by
construction).  Note the fold-in chunking is a different (equally valid)
draw order than `make_fused_runner`'s single upfront ``(T,)`` draw, so the
checkpointed fused engine is its own deterministic trajectory; its law is
identical.

A config fingerprint and the snapshot ring's layout are stored in every
checkpoint's metadata and validated on ``resume=True`` — resuming under a
different engine configuration, or a checkpoint whose ring is laid out
otherwise, is an error, not a silent divergence.
"""
from __future__ import annotations

import hashlib
import json
from typing import Any, Callable

import numpy as np

from .. import obs
from .engine_scan import (
    GuardConfig,
    _default_update,
    _flat_ring,
    _init_update_carry,
    _leaf_ring,
    _make_block_step,
    _make_fused_advance,
    _make_update_step,
    _runner_cache,
)
from .queue_sim import FaultConfig
from .theory import BoundConstants

__all__ = [
    "run_checkpointed",
    "run_checkpointed_host",
    "run_checkpointed_host_blocked",
]


# ------------------------------------------------------------------ #
# shared checkpoint plumbing
# ------------------------------------------------------------------ #
def _fingerprint(kind: str, fields: dict) -> str:
    """Stable config fingerprint for resume validation."""
    blob = json.dumps({"kind": kind, **fields}, sort_keys=True, default=str)
    return hashlib.sha1(blob.encode()).hexdigest()


def _array_digest(*arrays) -> str:
    h = hashlib.sha1()
    for a in arrays:
        a = np.ascontiguousarray(np.asarray(a))
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _key_fingerprint(key) -> list:
    import jax

    try:
        kd = np.asarray(jax.random.key_data(key))
    except Exception:
        kd = np.asarray(key)
    return [int(x) for x in np.ravel(kd)]


#: how a checkpoint's carry holds the snapshot ring, by its ``ring`` metadata
#: (checkpoints written before the per-leaf ring carry none: "flat")
_RING_LAYOUTS = {
    "flat": "as one flat (C, P) array of packed rows",
    "leaf": "as one (C, *leaf.shape) buffer per parameter leaf",
}


def _resume_state(ckpt_dir: str, like, fingerprint: str, ring: str = "flat"):
    """Latest checkpoint tree (validated against ``fingerprint`` and the
    ring layout ``ring``) or None."""
    from ..ckpt import checkpoint as ck

    step = ck.latest_step(ckpt_dir)
    if step is None:
        raise FileNotFoundError(
            f"resume=True but no checkpoint found under {ckpt_dir!r}"
        )
    meta = ck.load_metadata(ckpt_dir, step)
    got = meta.get("ring", "flat")
    if got != ring:
        raise ValueError(
            f"checkpoint/config mismatch: the checkpoint under {ckpt_dir!r} "
            f"holds the snapshot ring {_RING_LAYOUTS.get(got, got)}, but this "
            f"engine keeps it {_RING_LAYOUTS[ring]}; a checkpoint cannot be "
            "resumed across ring layouts (start the run afresh)"
        )
    if meta.get("fingerprint") != fingerprint:
        raise ValueError(
            "checkpoint/config mismatch: the run under "
            f"{ckpt_dir!r} was written by a different engine configuration "
            "(refusing to resume into a divergent trajectory)"
        )
    return ck.restore(ckpt_dir, step, like), step


def _save_state(ckpt_dir: str, step: int, tree, fingerprint: str, keep: int,
                ring: str = "flat"):
    import jax

    from ..ckpt import checkpoint as ck

    with obs.span(obs.CKPT_SAVE):
        tree = jax.tree_util.tree_map(np.asarray, tree)
        ck.save(
            ckpt_dir, step, tree,
            metadata={"fingerprint": fingerprint, "events_done": step,
                      "ring": ring},
            keep=keep,
        )


class _AsyncSaver:
    """Background checkpoint writer for the chunked drivers.

    Intermediate saves overlap the next chunk's compute: jax arrays are
    immutable, so the worker thread can run the device->host transfer and
    the atomic `checkpoint.save` (tmp dir + rename) while the main thread
    dispatches ahead.  Saves stay strictly ordered (single worker, FIFO
    queue); a SIGKILL mid-write leaves only an ignored ``.tmp_ckpt_*``
    directory, so resume falls back to the last *completed* step — the
    same guarantee as synchronous saving, minus the wall-clock stall.
    ``close()`` drains the queue and re-raises the first worker failure;
    the drivers call it before returning, so the final checkpoint is
    always on disk when the run completes.
    """

    def __init__(self, ckpt_dir: str, fingerprint: str, keep: int,
                 ring: str = "flat"):
        import queue
        import threading

        self._dir, self._fp, self._keep = ckpt_dir, fingerprint, keep
        self._ring = ring
        self._q: Any = queue.Queue(maxsize=2)
        self._err: BaseException | None = None
        self._worker = threading.Thread(target=self._loop, daemon=True)
        self._worker.start()

    def _loop(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                return
            step, tree = item
            try:
                if self._err is None:
                    _save_state(self._dir, step, tree, self._fp, self._keep,
                                self._ring)
            except BaseException as exc:  # surfaced at put()/close()
                self._err = exc

    def put(self, step: int, carry, evals_buf: np.ndarray) -> None:
        if self._err is not None:
            # fail loudly at the *next* save after a write error (full
            # disk, permissions): reap the worker first so the failure
            # doesn't leak a thread blocked on the queue
            self.abort()
            raise self._err
        # snapshot the (host-mutable) eval buffer; the carry's jax arrays
        # are immutable and safe to hand across threads as-is
        self._q.put((step, {"carry": carry, "evals": evals_buf.copy(),
                            "cursor": np.int64(step)}))

    def abort(self) -> None:
        """Reap the worker without raising — error-path cleanup.  Safe to
        call repeatedly and after `close` (no-op once the worker exited);
        the drivers call it in a ``finally`` so an exception anywhere in
        the chunk loop never leaks the writer thread."""
        if self._worker.is_alive():
            self._q.put(None)
            self._worker.join()

    def close(self) -> None:
        self.abort()
        if self._err is not None:
            raise self._err


def _chunk_layout(T: int, ckpt_every: int, eval_every: int,
                  refresh_every: int = 0) -> int:
    """Chunk length L: refresh, eval and checkpoint all land on chunk
    boundaries, so L divides all the active cadences."""
    if ckpt_every <= 0:
        raise ValueError("ckpt_every > 0 required")
    L = min(ckpt_every, T)
    if refresh_every:
        L = min(L, refresh_every)
    if eval_every:
        L = min(L, eval_every)
    for name, every in (("refresh_every", refresh_every),
                        ("eval_every", eval_every),
                        ("ckpt_every", ckpt_every)):
        if every and every % L:
            raise ValueError(
                f"{name}={every} must be a multiple of the chunk length {L} "
                "(refresh/eval/checkpoint cadences must nest)"
            )
    return L


class _EvalBuffer:
    """NaN-padded fixed-size eval curve that rides inside the checkpoint."""

    def __init__(self, n_evals: int, restored: np.ndarray | None = None):
        if restored is not None:
            self.buf = np.array(restored, np.float32)
        else:
            self.buf = np.full(n_evals, np.nan, np.float32)
        self.n = n_evals

    def put(self, idx: int, value) -> None:
        if 0 <= idx < self.n:
            self.buf[idx] = np.float32(value)

    def curve(self) -> np.ndarray:
        return self.buf[~np.isnan(self.buf)]


# ------------------------------------------------------------------ #
# fused (device-stream) checkpointed driver
# ------------------------------------------------------------------ #
def run_checkpointed(
    grad_fn: Callable[[Any, Any, Any], Any],
    n: int,
    C: int,
    T: int,
    *,
    w0,
    mu,
    p0,
    key,
    eta,
    ckpt_dir: str,
    ckpt_every: int,
    weighting: str = "importance",
    eval_fn=None,
    eval_every: int = 0,
    adaptive: bool = False,
    refresh_every: int = 0,
    bound: BoundConstants | None = None,
    ctrl_lr: float = 0.3,
    ctrl_iters: int = 4,
    init: str = "distinct",
    unroll: int = 1,
    block_size: int = 1,
    snapshot_dtype=None,
    fault: FaultConfig | None = None,
    guard: GuardConfig | None = None,
    serving=None,
    resume: bool = False,
    keep: int = 3,
):
    """Checkpointed fused engine (host-driven chunks of the device stream).

    Same event semantics as `engine_scan.make_fused_runner` (via the shared
    `_make_fused_advance` core — faults, guards, adaptive control and the
    blocked window all compose); the outer scan is replaced by a host loop
    over L-event chunks whose uniforms derive from ``fold_in(key, chunk)``,
    with a full-carry checkpoint every ``ckpt_every`` events.  Returns
    ``(w_final, evals, extras)``.  ``resume=True`` restores the latest
    checkpoint under ``ckpt_dir`` (config-fingerprint validated) and
    continues; kill-and-resume is bitwise-identical to the uninterrupted
    call.  Lanes/scenario meshes are not supported here — checkpoint the
    per-scenario runs individually instead.
    """
    import jax
    import jax.numpy as jnp

    from . import stream_device as sd

    if weighting not in ("importance", "plain"):
        raise ValueError(weighting)
    faulty = fault is not None and fault.enabled
    guard_stale = guard is not None and int(guard.stale_cutoff) > 0
    if adaptive and refresh_every <= 0:
        raise ValueError("adaptive=True requires refresh_every > 0")
    E = max(int(block_size), 1)
    serving_on = serving is not None and serving.enabled
    if serving_on:
        from . import serving as sp

        serving.validate()
        if E > 1:
            raise ValueError("serving= requires block_size=1")
    importance = weighting == "importance"
    need_stats = True  # stats ride in the checkpoint either way
    L = _chunk_layout(T, ckpt_every, eval_every if eval_fn else 0,
                      refresh_every if adaptive else 0)
    n_chunks, tail = T // L, T % L
    eval_on = eval_fn is not None and eval_every > 0
    eval_stride = max(eval_every // L, 1) if eval_on else 0
    bound = bound if bound is not None else BoundConstants(C=C, T=T)

    update_fn, default_update = _default_update(None)
    if E > 1:
        ring, rows, layout = _flat_ring(w0, snapshot_dtype), C + 1, "flat"
    else:
        ring = _leaf_ring(w0, snapshot_dtype, drop=faulty or serving_on)
        rows, layout = C, "leaf"
    master = default_update and ring.master is not None
    update_step = _make_update_step(grad_fn, 0, update_fn, ring, master, guard)
    ucarry0, to_tree = _init_update_carry(w0, rows, ring, master, 0)

    mu = jnp.asarray(mu, jnp.float32)
    p0 = jnp.asarray(p0, jnp.float32)
    eta = jnp.asarray(eta, jnp.float32)
    fr = sd.resolve_fault_rates(fault, n) if faulty else None
    k_init, k_race, k_exp, k_disp = jax.random.split(key, 4)
    sstate0, init_nodes = sd.stream_init(k_init, n, C, p0, init=init,
                                         fault=faulty)
    stats0 = sd.stats_init(n, C, fault=faulty)
    if importance:
        slot_scale0 = eta / (n * p0[init_nodes])
    else:
        slot_scale0 = jnp.broadcast_to(eta, (C,))
    carry0 = (ucarry0, sstate0, stats0, slot_scale0, p0, jnp.cumsum(p0))
    if serving_on:
        # serving state/counters ride inside the checkpointed carry, so
        # kill-and-resume of the serve plane is bitwise for free
        carry0 = carry0 + (sp.serve_init(serving), sp.serve_stats_init())

    # the jitted chunk is memoized on the gradient source (same idiom as
    # jit_runner/jit_fused_runner): mu/eta/fault-rates are call-time
    # arguments, so repeated runs — and the warm calls of a benchmark —
    # reuse one compiled executable instead of re-tracing the closure
    cache, func = _runner_cache(grad_fn)
    w0_sig = (
        jax.tree_util.tree_structure(w0),
        tuple((tuple(x.shape), jnp.asarray(x).dtype.name)
              for x in jax.tree_util.tree_leaves(w0)),
    )
    memo_key = (
        "ckpt_fused", func, n, C, E, importance, adaptive,
        (bound.A, bound.L, bound.B, bound.C, bound.T, bound.rho)
        if adaptive else None,
        float(ctrl_lr), int(ctrl_iters), eval_fn, unroll,
        str(snapshot_dtype), faulty,
        None if guard is None else guard.cache_key(),
        None if not serving_on else serving.cache_key(), w0_sig,
    )
    if memo_key in cache:
        jchunk = cache[memo_key]
    else:
        make_adv = _make_fused_advance(
            grad_fn, n, C, E, update_step, ring, 0, guard,
            importance=importance, faulty=faulty, guard_stale=guard_stale,
            need_stats=need_stats, axis=None, lane_devices=1, unroll=unroll,
            serving=serving,
        )

        def chunk(carry, mu_, eta_, fr_, kr, ke, kd, c, k0, Lc, do_eval):
            if serving_on:
                ucarry, sstate, stats, slot_scale, p, cdf, sv, svs = carry
            else:
                ucarry, sstate, stats, slot_scale, p, cdf = carry
            advance = make_adv(mu_, eta_, fr_)
            ur = jax.random.uniform(jax.random.fold_in(kr, c), (Lc,))
            ue = jax.random.uniform(jax.random.fold_in(ke, c), (Lc,))
            ud = jax.random.uniform(jax.random.fold_in(kd, c), (Lc,))
            Kc = jnp.minimum(
                jnp.searchsorted(cdf, ud, side="right"), n - 1
            ).astype(jnp.int32)
            if serving_on:
                ucarry, sstate, stats, slot_scale, _, sv, svs = advance(
                    ucarry, sstate, stats, slot_scale, p, ur, ue, Kc, k0,
                    None, sv, svs,
                )
            else:
                ucarry, sstate, stats, slot_scale, _ = advance(
                    ucarry, sstate, stats, slot_scale, p, ur, ue, Kc, k0
                )
            if adaptive:
                p = sd.ctrl_refresh(
                    p, stats.comp, stats.busy_t, bound, lr=ctrl_lr,
                    iters=ctrl_iters,
                )
                cdf = jnp.cumsum(p)
            ev = (
                jnp.asarray(eval_fn(to_tree(ucarry[0])), jnp.float32)
                if do_eval else jnp.float32(0.0)
            )
            out = (ucarry, sstate, stats, slot_scale, p, cdf)
            if serving_on:
                out = out + (sv, svs)
            return out, ev

        jchunk = jax.jit(chunk, static_argnames=("Lc", "do_eval"))
        cache[memo_key] = jchunk

    fingerprint = _fingerprint("fused", dict(
        n=n, C=C, T=T, L=L, ckpt_every=ckpt_every, weighting=weighting,
        eval_every=eval_every if eval_on else 0, adaptive=adaptive,
        refresh_every=refresh_every, init=init, block_size=E,
        snapshot_dtype=str(snapshot_dtype), ring=layout,
        fault=None if fault is None else fault.cache_key(),
        guard=None if guard is None else guard.cache_key(),
        serving=None if not serving_on else serving.cache_key(),
        key=_key_fingerprint(key), eta=float(np.asarray(eta)),
        mu=_array_digest(mu), p0=_array_digest(p0),
        ctrl=(float(ctrl_lr), int(ctrl_iters)),
    ))

    n_evals = T // eval_every if eval_on else 0
    like = {"carry": carry0, "evals": np.full(n_evals, np.nan, np.float32),
            "cursor": np.int64(0)}
    carry, evals, cursor0 = carry0, _EvalBuffer(n_evals), 0
    if resume:
        state, _ = _resume_state(ckpt_dir, like, fingerprint, layout)
        carry = jax.tree_util.tree_map(jnp.asarray, state["carry"])
        evals = _EvalBuffer(n_evals, restored=state["evals"])
        cursor0 = int(state["cursor"])

    saver = _AsyncSaver(ckpt_dir, fingerprint, keep, layout)
    try:
        for c in range(cursor0 // L, n_chunks):
            do_eval = eval_on and ((c + 1) % eval_stride == 0)
            carry, ev = jchunk(
                carry, mu, eta, fr, k_race, k_exp, k_disp, jnp.int32(c),
                jnp.int32(c * L), Lc=L, do_eval=do_eval,
            )
            if do_eval:
                evals.put((c + 1) // eval_stride - 1, ev)
            events_done = (c + 1) * L
            if events_done % ckpt_every == 0 and events_done < T:
                saver.put(events_done, carry, evals.buf)
        if tail and cursor0 < T:  # cursor0 == T: resumed post-tail final
            carry, _ = jchunk(
                carry, mu, eta, fr, k_race, k_exp, k_disp,
                jnp.int32(n_chunks), jnp.int32(n_chunks * L), Lc=tail,
                do_eval=False,
            )
        # final checkpoint: a later resume returns instantly from here
        saver.put(T, carry, evals.buf)
        saver.close()
    finally:
        saver.abort()

    if serving_on:
        ucarry, sstate, stats, slot_scale, p, cdf, sv, svs = carry
    else:
        ucarry, sstate, stats, slot_scale, p, cdf = carry
    extras = {
        "p_final": p,
        "comp": stats.comp,
        "busy_time": stats.busy_t,
        "delay_sum": stats.delay_sum,
        "t_final": sstate.t,
    }
    if guard is not None:
        extras["guard_rejects"] = ucarry[3][0]
        extras["stale_drops"] = ucarry[3][1]
    if faulty:
        extras["kind_count"] = stats.kind_count
        extras["avail_time"] = stats.avail_tw
    if serving_on:
        extras.update({
            "serve_arrivals": svs.arrivals,
            "serve_served": svs.served,
            "serve_shed": svs.shed,
            "serve_timed_out": svs.timed_out,
            "serve_retried": svs.retried,
            "serve_pending": jnp.sum((sv.stt != 0).astype(jnp.int32)),
            "serve_sojourn_sum": svs.sojourn - svs.sojourn_c,
            "serve_sojourn_hist": svs.sojourn_hist,
            "serve_stale_hist": svs.stale_hist,
            "serve_qdepth_time": svs.qdepth_tw - svs.qdepth_tw_c,
            "serve_qdepth_max": svs.qdepth_max,
            "serve_checksum": svs.checksum - svs.checksum_c,
            "serve_kg_step": sv.kg_step,
            "serve_kg_slot": sv.kg_slot,
            "serve_tokens": sv.tokens,
            "serve_t_final": sstate.t,
        })
    return to_tree(ucarry[0]), jnp.asarray(evals.curve()), extras


# ------------------------------------------------------------------ #
# host-replay checkpointed drivers
# ------------------------------------------------------------------ #
def run_checkpointed_host(
    grad_fn,
    C: int,
    w0,
    J,
    slot,
    scale,
    *,
    ckpt_dir: str,
    ckpt_every: int,
    eval_fn=None,
    eval_every: int = 0,
    fedbuff_Z: int = 0,
    update_fn=None,
    unroll: int = 1,
    snapshot_dtype=None,
    guard: GuardConfig | None = None,
    resume: bool = False,
    keep: int = 3,
):
    """Checkpointed per-event host replay (`_make_host_runner` semantics).

    Replays the pre-simulated ``(J, slot, scale)`` event arrays in L-event
    jitted chunks with a full-carry checkpoint every ``ckpt_every`` events.
    The event arrays themselves are deterministic host data (re-exported
    from the same `SimConfig` on resume), so only the carry + cursor need
    saving.  Returns ``(w_final, evals)`` (+ the guard counter when
    ``guard``), matching the un-checkpointed runner.
    """
    import jax
    import jax.numpy as jnp

    J = np.asarray(J, np.int32)
    slot_h = np.asarray(slot, np.int32)
    scale_h = np.asarray(scale, np.float32)
    T = int(J.shape[0])
    eval_on = eval_fn is not None and eval_every > 0
    L = _chunk_layout(T, ckpt_every, eval_every if eval_on else 0)
    n_chunks, tail = T // L, T % L
    eval_stride = max(eval_every // L, 1) if eval_on else 0

    update_fn, default_update = _default_update(update_fn)
    ring = _leaf_ring(w0, snapshot_dtype, drop=True)
    master = default_update and ring.master is not None
    update_step = _make_update_step(
        grad_fn, fedbuff_Z, update_fn, ring, master, guard
    )
    carry0, to_tree = _init_update_carry(w0, C, ring, master, fedbuff_Z)

    def chunk(carry, Jc, sc_, scc, k0, do_eval):
        def body(c, xs):
            j, s, sc, k = xs
            return update_step(c, j, s, sc, k), ()

        ks = k0 + jnp.arange(Jc.shape[0], dtype=jnp.int32)
        carry = jax.lax.scan(body, carry, (Jc, sc_, scc, ks), unroll=unroll)[0]
        ev = (
            jnp.asarray(eval_fn(to_tree(carry[0])), jnp.float32)
            if do_eval else jnp.float32(0.0)
        )
        return carry, ev

    jchunk = jax.jit(chunk, static_argnames=("do_eval",))

    fingerprint = _fingerprint("host", dict(
        C=C, T=T, L=L, ckpt_every=ckpt_every, fedbuff_Z=fedbuff_Z,
        eval_every=eval_every if eval_on else 0,
        snapshot_dtype=str(snapshot_dtype), ring="leaf",
        guard=None if guard is None else guard.cache_key(),
        stream=_array_digest(J, slot_h, scale_h),
    ))
    n_evals = T // eval_every if eval_on else 0
    like = {"carry": carry0, "evals": np.full(n_evals, np.nan, np.float32),
            "cursor": np.int64(0)}
    carry, evals, cursor0 = carry0, _EvalBuffer(n_evals), 0
    if resume:
        state, _ = _resume_state(ckpt_dir, like, fingerprint, "leaf")
        carry = jax.tree_util.tree_map(jnp.asarray, state["carry"])
        evals = _EvalBuffer(n_evals, restored=state["evals"])
        cursor0 = int(state["cursor"])

    saver = _AsyncSaver(ckpt_dir, fingerprint, keep, "leaf")
    try:
        for c in range(cursor0 // L, n_chunks):
            lo, hi = c * L, (c + 1) * L
            do_eval = eval_on and ((c + 1) % eval_stride == 0)
            carry, ev = jchunk(
                carry, jnp.asarray(J[lo:hi]), jnp.asarray(slot_h[lo:hi]),
                jnp.asarray(scale_h[lo:hi]), jnp.int32(lo), do_eval=do_eval,
            )
            if do_eval:
                evals.put((c + 1) // eval_stride - 1, ev)
            if hi % ckpt_every == 0 and hi < T:
                saver.put(hi, carry, evals.buf)
        if tail and cursor0 < T:  # cursor0 == T: resumed post-tail final
            lo = n_chunks * L
            carry, _ = jchunk(
                carry, jnp.asarray(J[lo:]), jnp.asarray(slot_h[lo:]),
                jnp.asarray(scale_h[lo:]), jnp.int32(lo), do_eval=False,
            )
        saver.put(T, carry, evals.buf)
        saver.close()
    finally:
        saver.abort()
    w = to_tree(carry[0])
    ev_curve = jnp.asarray(evals.curve())
    if guard is not None:
        return w, ev_curve, carry[3]
    return w, ev_curve


def run_checkpointed_host_blocked(
    grad_fn,
    C: int,
    block_size: int,
    w0,
    J,
    slot,
    scale,
    k,
    mask,
    *,
    group_events: int,
    chunk_blocks: int,
    n_chunks: int,
    ckpt_dir: str,
    ckpt_every: int,
    eval_fn=None,
    kernel: str = "jnp",
    unroll: int = 1,
    snapshot_dtype=None,
    fedbuff_Z: int = 0,
    guard: GuardConfig | None = None,
    resume: bool = False,
    keep: int = 3,
):
    """Checkpointed blocked host replay (`_make_host_block_runner` semantics).

    Consumes the grouped blocked layout of `engine_scan.blocked_inputs`
    (``eval_every=group_events``): each group of ``chunk_blocks`` rows covers
    exactly ``group_events`` events (the conflict-free cut guarantees it),
    giving exact event cursors for the checkpoint cadence —
    ``ckpt_every`` must be a multiple of ``group_events``.  Trailing rows
    past the last group replay after the loop, un-checkpointed.  When
    ``eval_fn`` is set, the eval fires at every group boundary (cadence
    ``group_events``), matching the grouped layout's contract.  Returns
    ``(w_final, evals)`` (+ the guard counter when ``guard``).
    """
    import jax
    import jax.numpy as jnp

    if block_size < 2:
        raise ValueError("use run_checkpointed_host for block_size <= 1")
    if n_chunks < 1 or chunk_blocks < 1:
        raise ValueError(
            "the blocked checkpoint driver needs the grouped layout: pass "
            "blocked_inputs(blocks, scale, eval_every=group_events) arrays"
        )
    if ckpt_every <= 0 or ckpt_every % group_events:
        raise ValueError("ckpt_every must be a positive multiple of "
                         "group_events")
    pad_to = 1
    if kernel == "pallas":
        from ..kernels.weighted_update import BLOCK_TILE

        pad_to = BLOCK_TILE

    J = np.asarray(J, np.int32)
    slot_h = np.asarray(slot, np.int32)
    scale_h = np.asarray(scale, np.float32)
    k_h = np.asarray(k, np.int32)
    mask_h = np.asarray(mask, bool)

    ring = _flat_ring(w0, snapshot_dtype, pad_to=pad_to)
    block_step = _make_block_step(grad_fn, fedbuff_Z, ring, kernel, None, guard)
    carry0, to_tree = _init_update_carry(w0, C + 1, ring, True, fedbuff_Z)

    def chunk(carry, Jc, sc_, scc, kc, mc, do_eval):
        def body(c, xs):
            return block_step(c, *xs), ()

        carry = jax.lax.scan(
            body, carry, (Jc, sc_, scc, kc, mc), unroll=unroll
        )[0]
        ev = (
            jnp.asarray(eval_fn(to_tree(carry[0])), jnp.float32)
            if do_eval else jnp.float32(0.0)
        )
        return carry, ev

    jchunk = jax.jit(chunk, static_argnames=("do_eval",))

    fingerprint = _fingerprint("host_blocked", dict(
        C=C, E=block_size, group_events=group_events, ckpt_every=ckpt_every,
        chunk_blocks=chunk_blocks, n_chunks=n_chunks, kernel=kernel,
        fedbuff_Z=fedbuff_Z, snapshot_dtype=str(snapshot_dtype),
        guard=None if guard is None else guard.cache_key(),
        stream=_array_digest(J, slot_h, scale_h, k_h, mask_h),
    ))
    eval_on = eval_fn is not None
    n_evals = n_chunks if eval_on else 0
    # the grouped layout's tail rows sit past the last exact event cursor;
    # count their real (unmasked) events so the final cursor is unambiguous
    Bm = n_chunks * chunk_blocks
    total = n_chunks * group_events
    tail_events = (
        int(mask_h[Bm:].sum()) if Bm < int(J.shape[0]) else 0
    )
    total_all = total + tail_events
    like = {"carry": carry0, "evals": np.full(n_evals, np.nan, np.float32),
            "cursor": np.int64(0)}
    carry, evals, cursor0 = carry0, _EvalBuffer(n_evals), 0
    if resume:
        state, _ = _resume_state(ckpt_dir, like, fingerprint)
        carry = jax.tree_util.tree_map(jnp.asarray, state["carry"])
        evals = _EvalBuffer(n_evals, restored=state["evals"])
        cursor0 = int(state["cursor"])

    saver = _AsyncSaver(ckpt_dir, fingerprint, keep)
    try:
        for g in range(min(cursor0, total) // group_events, n_chunks):
            lo, hi = g * chunk_blocks, (g + 1) * chunk_blocks
            carry, ev = jchunk(
                carry, jnp.asarray(J[lo:hi]), jnp.asarray(slot_h[lo:hi]),
                jnp.asarray(scale_h[lo:hi]), jnp.asarray(k_h[lo:hi]),
                jnp.asarray(mask_h[lo:hi]), do_eval=eval_on,
            )
            if eval_on:
                evals.put(g, ev)
            events_done = (g + 1) * group_events
            if events_done % ckpt_every == 0 and events_done < total_all:
                saver.put(events_done, carry, evals.buf)
        if Bm < int(J.shape[0]) and cursor0 < total_all:  # tail rows
            carry, _ = jchunk(
                carry, jnp.asarray(J[Bm:]), jnp.asarray(slot_h[Bm:]),
                jnp.asarray(scale_h[Bm:]), jnp.asarray(k_h[Bm:]),
                jnp.asarray(mask_h[Bm:]), do_eval=False,
            )
        saver.put(total_all, carry, evals.buf)
        saver.close()
    finally:
        saver.abort()
    w = to_tree(carry[0])
    ev_curve = jnp.asarray(evals.curve())
    if guard is not None:
        return w, ev_curve, carry[3]
    return w, ev_curve
