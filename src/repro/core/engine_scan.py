"""Compiled device-resident Generalized-AsyncSGD engine (one `lax.scan`).

The Python reference loop in `async_sgd.py` pays a host<->device round trip
per CS step, which caps the §5 experiment at toy sizes.  The queuing
structure removes the need for that: the event stream (J_k, K_{k+1}, t_k) of
the closed Jackson network is independent of the gradient values, so
Algorithm 1 runs on device as a single XLA program:

  * the C in-flight dispatch snapshots live in a stacked ring buffer: one
    (C, *leaf.shape) buffer per parameter leaf, the ring index the major
    axis, so a slot's read and write touch only its own bytes (the blocked
    engine packs its ring into ONE (C+1, P) array of flat rows for its
    kernels); optionally stored in a narrower dtype —
    ``snapshot_dtype="bfloat16"`` halves the ring buffer's footprint and
    bandwidth at an O(2^-8) snapshot quantization;
  * `update_step` — the algorithm half — gathers the completing task's
    snapshot from its slot, computes the client gradient with a traceable
    `grad_fn(j, w, k)`, applies the importance-weighted update, and scatters
    the updated parameters back into the same slot (the freed slot hosts the
    new dispatch — exactly one task completes and one departs per step,
    Lemma 9);
  * with ``block_size=E > 1`` the engine replays *event micro-blocks*
    instead of single events: gradients read dispatch-time snapshots, not
    the live server weight, so a block of events whose ring slots don't
    collide is data-parallel — `block_step` batch-gathers the E snapshots,
    computes all E gradients in one vmapped call, reconstructs the exact
    sequential iterates via a prefix sum over the scaled updates
    (w_i = w_0 - sum_{j<=i} eta_j g_j), and scatters the E intermediate
    weights back in one pass (optionally through the fused Pallas kernel
    `kernels.weighted_update.block_prefix_update`, which streams tiles once
    and updates the ring buffer in place).  On the host path the blocks come
    from `queue_sim.export_blocks` (greedy conflict-free cut, padded lanes
    target a trash row C); on the device path `stream_step` advances E CS
    steps per scan iteration and a sequential fixup pass recomputes the
    (rare) gradients whose dispatch landed inside the same window, so both
    paths stay law- and trajectory-equivalent to the per-event oracle;
  * the event half comes from one of two *streams* (`make_runner(stream=)`):

      "host"    replay a pre-simulated `queue_sim.EventStream` — the parity
                oracle.  `run(w0, J, slot, scale[, eval_every])`, or the
                blocked `run(w0, J, slot, scale, k, mask[, chunk_blocks,
                n_chunks])` over `queue_sim.EventBlocks` arrays.
      "device"  fuse `stream_device.stream_step` with `update_step` behind a
                single scan carry: the closed network advances one CS step
                (or one E-event micro-block) per iteration *inside* the
                compiled program — zero host pre-simulation, and the
                sampling vector p becomes state.
                `run(w0, mu, p0, key, eta) -> (w, evals, extras)`.

  * on the fused path an optional control loop (``adaptive=True``)
    re-optimizes p every `refresh_every` steps from the running occupancy /
    rate estimates (`stream_device.ctrl_refresh` — projected analytic
    simplex gradient steps on the Theorem-1 bound), giving an adaptive
    variant of the paper's sampling scheme.  Importance weights stay
    unbiased under time-varying p because each in-flight slot remembers the
    scale computed from its *dispatch-time* p.
  * evaluation runs as an outer scan over chunks, so the whole run —
    updates and metric curve — is one compiled call.  Eval points land on
    micro-block boundaries by construction (`segment_blocks(cut_every=)`),
    so the blocked curve samples the identical iterates.

`make_runner` returns a pure function: jit it for a single run, `jax.vmap`
it over stacked streams / (mu, p, key) triples for the scenario matrix
(seeds x sampling policies x heterogeneity levels in one compiled call).

FedBuff rides the same scan: gradients accumulate into a buffer pytree and
the (masked, branch-free) server update fires every Z-th step; the blocked
replay reproduces it exactly through a closed-form per-event delta
decomposition (each gradient is applied once, at the first buffer flush at
or after its arrival).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial, wraps
from typing import Any, Callable, NamedTuple, Protocol

import numpy as np

from .. import obs
from .queue_sim import KIND_COMPLETE, EventBlocks, EventStream, FaultConfig
from .theory import BoundConstants

__all__ = [
    "DeviceGradientSource",
    "GuardConfig",
    "blocked_inputs",
    "blocked_inputs_batch",
    "jit_runner",
    "jit_fused_runner",
    "make_runner",
    "make_fused_runner",
    "step_scales",
    "stream_arrays",
]

Pytree = Any


class DeviceGradientSource(Protocol):
    """A gradient source the compiled engine can trace.

    Unlike `GradientSource.grad` (host Python, one call per step),
    `device_grad` is called once under tracing with abstract scalar
    `client_id` / `server_step`; it must be expressible as pure JAX ops over
    device-resident data (e.g. a gather from stacked per-client shards).
    """

    def device_grad(self, client_id, params: Pytree, server_step) -> Pytree:
        ...


def _scoped(scope: str, fn):
    """``fn`` with the ops it traces under ``jax.named_scope(scope)``; a
    scope opened inside it (the innermost) wins."""
    import jax

    @wraps(fn)
    def f(*args, **kw):
        with jax.named_scope(scope):
            return fn(*args, **kw)

    return f


def step_scales(
    stream: EventStream, eta: float, p: np.ndarray, weighting: str
) -> np.ndarray:
    """Per-step update scale as a (T,) array: eta/(n p_{J_k}) or plain eta.

    On fault-injected streams (``stream.kind`` set) non-completion events —
    crashes, timeouts, availability flips — carry scale 0: the replay engine
    then applies them exactly as the paper's update does nothing, while the
    re-dispatch side effect (the freed slot re-hosting the task with the
    current server weights) still happens through the normal scatter.
    """
    if weighting == "importance":
        sc = (eta / (stream.n * np.asarray(p, float)))[stream.J]
    elif weighting == "plain":
        sc = np.full(stream.T, eta)
    else:
        raise ValueError(weighting)
    if stream.kind is not None:
        sc = np.where(stream.kind == KIND_COMPLETE, sc, 0.0)
    return sc


def stream_arrays(stream: EventStream):
    """Device copies of the scan inputs (J, slot) for one stream."""
    import jax.numpy as jnp

    return jnp.asarray(stream.J), jnp.asarray(stream.slot)


# ------------------------------------------------------------------ #
# blocked input layout (host-side numpy prep)
# ------------------------------------------------------------------ #
def _blocked_layout(
    blocks: EventBlocks,
    scale: np.ndarray,
    eval_every: int,
    chunk_blocks: int | None = None,
    tail_blocks: int | None = None,
) -> tuple:
    """(J, slot, scale, k, mask) rows + (chunk_blocks, n_chunks) layout.

    With ``eval_every`` the blocks are grouped per eval interval and each
    group is padded with all-masked rows to a common ``chunk_blocks`` width,
    so the chunked outer scan evaluates after exactly `eval_every` events;
    trailing blocks past the last eval point are appended flat.  Padded rows
    are no-ops: mask False, trash slot C, zero scale.
    """
    E = blocks.block_size
    sc_all = blocks.blocked_scales(scale).astype(np.float32)
    if not eval_every:
        n_tail = blocks.B if tail_blocks is None else tail_blocks
        if n_tail < blocks.B:
            raise ValueError("tail_blocks smaller than block count")
        pad = n_tail - blocks.B
        def padded(a, fill):
            return np.concatenate(
                [a, np.full((pad, E), fill, a.dtype)]) if pad else a
        return (
            padded(blocks.J, 0),
            padded(blocks.slot, blocks.C),
            padded(sc_all, 0.0),
            padded(blocks.idx, 0),
            padded(blocks.mask, False),
            0,
            0,
        )
    if blocks.cut_every != eval_every:
        raise ValueError(
            f"blocks were cut every {blocks.cut_every} events; eval_every="
            f"{eval_every} requires segment_blocks(cut_every={eval_every})"
        )
    n_chunks = blocks.T // eval_every
    group = np.minimum(blocks.idx[:, 0] // eval_every, n_chunks)
    counts = np.bincount(group, minlength=n_chunks + 1)
    G = int(counts[:n_chunks].max()) if n_chunks else 0
    if chunk_blocks is not None:
        if chunk_blocks < G:
            raise ValueError("chunk_blocks smaller than densest eval interval")
        G = chunk_blocks
    n_tail = int(counts[n_chunks])
    if tail_blocks is not None:
        if tail_blocks < n_tail:
            raise ValueError("tail_blocks smaller than tail block count")
        n_tail = tail_blocks
    rows = n_chunks * G + n_tail
    J = np.zeros((rows, E), np.int32)
    slot = np.full((rows, E), blocks.C, np.int32)
    sc = np.zeros((rows, E), np.float32)
    kb = np.zeros((rows, E), np.int32)
    mask = np.zeros((rows, E), bool)
    pos = 0
    for g in range(n_chunks + 1):
        cnt = int(counts[g])
        r0 = g * G if g < n_chunks else n_chunks * G
        src = slice(pos, pos + cnt)
        dst = slice(r0, r0 + cnt)
        J[dst], slot[dst], sc[dst] = blocks.J[src], blocks.slot[src], sc_all[src]
        kb[dst], mask[dst] = blocks.idx[src], blocks.mask[src]
        pos += cnt
    return J, slot, sc, kb, mask, G, n_chunks


def blocked_inputs(blocks: EventBlocks, scale: np.ndarray, eval_every: int = 0):
    """Device-ready blocked scan inputs for one stream.

    Returns ``(J, slot, scale, k, mask, chunk_blocks, n_chunks)`` — the
    array arguments plus the two static layout ints of the blocked runner.
    """
    return _blocked_layout(blocks, scale, eval_every)


def blocked_inputs_batch(
    blocks_list: list[EventBlocks],
    scales_list: list[np.ndarray],
    eval_every: int = 0,
):
    """Stacked blocked inputs over scenarios, padded to one common layout.

    The per-scenario greedy cuts produce different block counts; every
    scenario is padded (all-masked no-op rows) to the batch-wide maximum, so
    the result vmaps as one (S, B, E) program with shared static
    ``(chunk_blocks, n_chunks)``.
    """
    layouts = [
        _blocked_layout(b, s, eval_every)
        for b, s in zip(blocks_list, scales_list)
    ]
    if eval_every:
        G = max(l[5] for l in layouts)
        n_chunks = layouts[0][6]
        tails = [l[0].shape[0] - n_chunks * l[5] for l in layouts]
        tail = max(tails)
        layouts = [
            _blocked_layout(b, s, eval_every, chunk_blocks=G, tail_blocks=tail)
            for b, s in zip(blocks_list, scales_list)
        ]
    else:
        B = max(l[0].shape[0] for l in layouts)
        layouts = [
            _blocked_layout(b, s, 0, tail_blocks=B)
            for b, s in zip(blocks_list, scales_list)
        ]
    stacked = tuple(np.stack([l[i] for l in layouts]) for i in range(5))
    return stacked + (layouts[0][5], layouts[0][6])


# ------------------------------------------------------------------ #
# shared pieces: the snapshot ring + the algorithm step
# ------------------------------------------------------------------ #
def _snapshot_codec(w0, snapshot_dtype=None, pad_to: int = 1):
    """Flat-packed rows for the blocked engine's snapshot ring.

    The blocked engine's ring is ONE (C+1, P) array: its batched gather,
    prefix-sum and scatter kernels work on flat rows.  Uniform-dtype trees
    pack losslessly; mixed *float* trees (e.g. bf16 matmul weights with
    fp32 norms — the real-model presets) pack per-leaf into the common
    promoted dtype (``jnp.result_type`` over the leaves, so bf16+fp32
    packs to an fp32 master vector) and ``unpack`` casts each leaf back
    to its original dtype.  Trees with non-float leaves cannot be packed.

    Returns ``(pack, unpack, enc)``: ``pack`` flattens a pytree to the
    padded compute-dtype vector, ``unpack`` restores the pytree from a
    stored row (casting back to the leaf dtype), and ``enc`` casts a
    compute-dtype vector to the snapshot *storage* dtype — the optional
    ``snapshot_dtype`` codec (e.g. ``"bfloat16"`` ring storage for fp32
    params).  ``pad_to`` rounds the packed length up (once, at init) so the
    fused block kernel's column tiling never re-pads inside the scan.
    Non-float trees return ``(None, None, None)``.
    """
    import jax
    import jax.numpy as jnp

    leaves, treedef = jax.tree_util.tree_flatten(w0)
    leaf_dtypes = [jnp.asarray(l).dtype for l in leaves]
    dtypes = set(leaf_dtypes)
    if not all(jnp.issubdtype(d, jnp.inexact) for d in dtypes):
        if snapshot_dtype is not None:
            raise ValueError(
                "snapshot_dtype requires all-float parameters "
                "(flat-packed snapshot storage)"
            )
        return None, None, None
    compute_dtype = jnp.result_type(*dtypes) if len(dtypes) > 1 else dtypes.pop()
    store_dtype = (
        jnp.dtype(snapshot_dtype) if snapshot_dtype is not None else compute_dtype
    )
    shapes = [jnp.shape(l) for l in leaves]
    sizes = [int(np.prod(s)) if s else 1 for s in shapes]
    offs = np.concatenate([[0], np.cumsum(sizes)]).tolist()
    P = offs[-1]
    P_pad = ((P + pad_to - 1) // pad_to) * pad_to

    def pack(w):
        ls = jax.tree_util.tree_leaves(w)
        flat = jnp.concatenate(
            [jnp.ravel(x).astype(compute_dtype) for x in ls]
        )
        if P_pad != P:
            flat = jnp.pad(flat, (0, P_pad - P))
        return flat

    def unpack(flat):
        ls = [
            flat[offs[i] : offs[i + 1]].reshape(shapes[i]).astype(leaf_dtypes[i])
            for i in range(len(shapes))
        ]
        return jax.tree_util.tree_unflatten(treedef, ls)

    if store_dtype == compute_dtype:
        enc = lambda x: x
    else:
        enc = lambda x: x.astype(store_dtype)
    return pack, unpack, enc


class _Ring(NamedTuple):
    """How the snapshot ring is stored and addressed.

    The update step reads and writes one slot per event through these,
    whatever the layout; ``master`` is None for a tree that has no master
    iterate (non-float leaves), which then trains in its own dtypes.
    """

    fill: Callable            # (w0, rows) -> ring, every row holding w0
    read: Callable            # (snaps, s) -> params pytree in the leaf dtypes
    write: Callable           # (snaps, s, w) -> snaps, slot s holding w
    master: Callable | None   # params pytree -> master layout, compute dtype
    to_tree: Callable         # master layout (or a stored row) -> params


def _leaf_ring(w0, snapshot_dtype=None, drop: bool = False) -> _Ring:
    """The per-event engine's ring: one ``(C, *leaf.shape)`` buffer per
    parameter leaf, with the ring index as the major axis.

    A slot's read is a ``dynamic_index_in_dim`` and its write an in-place
    dynamic-update-slice on each leaf, so both touch only that slot's
    bytes.  (One flat (C, P) array puts C on the chip's tiled row axis:
    every single-row access then works on tiles shared by 8 rows, and the
    row must be re-laid out to slice the leaves out of it.)  On an
    all-float tree the master iterate is kept per leaf in the promoted
    compute dtype (``jnp.result_type`` over the leaves: bf16 matmul weights
    with fp32 norms keep an fp32 master), the ring stores that dtype or
    ``snapshot_dtype``, and a read casts back to each leaf's dtype.  A tree
    with non-float leaves stores each leaf in its own dtype.

    ``drop`` is for event sources that emit slot C (fault, flip, scenario
    stage and serve events): their writes are dropped, never clamped onto
    row C-1.  Otherwise every slot is in bounds and the write is unmasked.
    """
    import jax
    import jax.numpy as jnp

    leaves, treedef = jax.tree_util.tree_flatten(w0)
    dtypes = [jnp.asarray(l).dtype for l in leaves]
    shapes = [jnp.shape(l) for l in leaves]
    # a row of fewer than two axes gets leading 1s, so that C never lands
    # on the chip's tiled (8, 128) axes
    row_shapes = [(1,) * (2 - len(sh)) + sh for sh in shapes]
    if all(jnp.issubdtype(d, jnp.inexact) for d in dtypes):
        compute = jnp.result_type(*dtypes)
        store = compute if snapshot_dtype is None else jnp.dtype(snapshot_dtype)
        stored = [store] * len(dtypes)
    elif snapshot_dtype is not None:
        raise ValueError("snapshot_dtype requires all-float parameters")
    else:
        compute, stored = None, dtypes

    def fill(w0, rows):
        return treedef.unflatten([
            jnp.broadcast_to(jnp.asarray(x, d).reshape(r)[None], (rows,) + r)
            for x, d, r in zip(jax.tree_util.tree_leaves(w0), stored, row_shapes)
        ])

    def read(snaps, s):
        return treedef.unflatten([
            jax.lax.dynamic_index_in_dim(b, s, 0, keepdims=False)
            .reshape(sh).astype(d)
            for b, sh, d in zip(jax.tree_util.tree_leaves(snaps), shapes, dtypes)
        ])

    def write(snaps, s, w):
        def put(b, x):
            x = x.astype(b.dtype).reshape(b.shape[1:])
            if drop:
                return b.at[s].set(x, mode="drop")
            return jax.lax.dynamic_update_index_in_dim(b, x, s, 0)

        return jax.tree_util.tree_map(put, snaps, w)

    def master(tree):
        return jax.tree_util.tree_map(lambda x: jnp.asarray(x, compute), tree)

    def to_tree(w):
        return treedef.unflatten([
            x.astype(d) for x, d in zip(jax.tree_util.tree_leaves(w), dtypes)
        ])

    return _Ring(fill, read, write, None if compute is None else master, to_tree)


def _flat_ring(w0, snapshot_dtype=None, pad_to: int = 1) -> _Ring:
    """The blocked engine's ring: ONE (C+1, P) array of `_snapshot_codec`
    rows, the layout its batched gather and Pallas prefix/scatter kernels
    work on.  Its slots are always in bounds: padded lanes and slot-C
    events target the trash row C.  The master iterate is the packed
    vector, and ``to_tree`` also decodes a stored row."""
    import jax.numpy as jnp

    pack, unpack, enc = _snapshot_codec(w0, snapshot_dtype, pad_to=pad_to)
    if unpack is None:
        raise ValueError(
            "block_size > 1 requires all-float parameters "
            "(flat-packed snapshot storage)"
        )

    def fill(w0, rows):
        stored0 = enc(pack(w0))
        return jnp.broadcast_to(stored0[None], (rows, stored0.shape[0]))

    return _Ring(
        fill=fill,
        read=lambda snaps, s: unpack(snaps[s]),
        write=lambda snaps, s, w: snaps.at[s].set(enc(w)),
        master=pack,
        to_tree=unpack,
    )


@dataclass(frozen=True)
class GuardConfig:
    """Divergence guard on the server update (graceful degradation).

    Guard order per event, applied after fault-kind masking (a crash /
    timeout / flip already carries scale 0 and is never counted):

      1. staleness cutoff — an update whose task has been in flight for more
         than ``stale_cutoff`` server steps is dropped (scale -> 0) and
         counted in ``stale_drops``;
      2. divergence — a gradient that is non-finite, or whose l2 norm
         exceeds ``max_grad_norm`` (0 disables the norm cap; non-finite
         rejection is always on), is zeroed and counted in
         ``guard_rejects``; the re-dispatch scatter still happens, so the
         event stream's queue dynamics are untouched.

    Guarded runners return the (2,) int32 counter ``[guard_rejects,
    stale_drops]`` alongside the final iterate.  Requires all-float
    parameters and the default linear update.
    """

    max_grad_norm: float = 0.0
    stale_cutoff: int = 0

    @property
    def enabled(self) -> bool:
        return True  # non-finite rejection is unconditional

    def cache_key(self):
        return (float(self.max_grad_norm), int(self.stale_cutoff))


def _make_guard(guard: GuardConfig):
    """``check(g, scale, gcnt, stale) -> (bad, scale, gcnt)`` on one
    gradient in the master layout (its squared norm sums over the leaves)
    — the single place the guard semantics (ordering, counting) live;
    every engine path composes it around its update axpy.

    The divergence verdict ``bad`` is returned instead of a pre-zeroed
    gradient so the caller can compute the candidate update concurrently
    with the norm reduction and suppress it afterwards
    (``where(bad, w, w_new)``): zeroing ``g`` or ``scale`` up front would
    serialize the axpy behind the full reduction over ``g``, an extra
    sweep of stall per event.
    """
    import jax
    import jax.numpy as jnp

    max_sq = float(guard.max_grad_norm) ** 2
    cutoff = int(guard.stale_cutoff)

    def check(g, scale, gcnt, stale=None):
        live = scale != 0
        if cutoff > 0 and stale is not None:
            st = live & (stale > cutoff)
            gcnt = gcnt.at[1].add(st.astype(jnp.int32))
            scale = jnp.where(st, 0.0, scale)
            live = live & ~st
        sq = sum(
            jnp.sum(jnp.square(x.astype(jnp.float32)))
            for x in jax.tree_util.tree_leaves(g)
        )
        bad = ~jnp.isfinite(sq)
        if max_sq > 0.0:
            bad = bad | (sq > max_sq)
        gcnt = gcnt.at[0].add((bad & live).astype(jnp.int32))
        return bad, scale, gcnt

    return check


def _make_apply_event(fedbuff_Z, ring: _Ring, guard=None):
    """Server update for one event, given its gradient in the master layout.

    ``apply_event((w, snaps, acc, gcnt), g, s, scale, k[, stale])`` is
    Algorithm 1 lines 10-11 — the axpy on the master iterate, one write of
    slot s through ``ring`` (plus the masked FedBuff buffer flush every
    Z-th step).  Shared by the per-event `update_step` and the
    device-blocked fixup pass so the update semantics exist exactly once.
    With ``guard`` the gradient passes the divergence/staleness check first
    and the carry's (2,) reject counter accumulates.
    """
    import jax
    import jax.numpy as jnp

    tree_map = jax.tree_util.tree_map
    check = _make_guard(guard) if guard is not None else None

    def apply_event(ucarry, g, s, scale, k, stale=None):
        w, snaps, acc, gcnt = ucarry
        bad = None
        if check is not None:
            bad, scale, gcnt = check(g, scale, gcnt, stale)
        if fedbuff_Z > 0:
            if bad is not None:  # the accumulator consumes g beyond the axpy
                g = tree_map(lambda y: jnp.where(bad, jnp.zeros_like(y), y), g)
            acc = tree_map(lambda a, y: a + y, acc, g)
            fire = ((k + 1) % fedbuff_Z) == 0
            eff = jnp.where(fire, scale / fedbuff_Z, 0.0)
            w = tree_map(lambda x, a: (x - eff * a).astype(x.dtype), w, acc)
            acc = tree_map(lambda a: a * (~fire).astype(a.dtype), acc)
        else:
            w_new = tree_map(lambda x, y: (x - scale * y).astype(x.dtype), w, g)
            # candidate axpy and norm reduction run concurrently; rejection
            # is a select on the result, not a stall before it
            w = w_new if bad is None else tree_map(
                lambda a, b: jnp.where(bad, a, b), w, w_new
            )
        snaps = ring.write(snaps, s, w)
        return (w, snaps, acc, gcnt)

    return _scoped(obs.RING, apply_event)


def _make_update_step(grad_fn, fedbuff_Z, update_fn, ring: _Ring, master,
                      guard=None):
    """The algorithm half of a CS step, independent of the event source.

    ``update_step(ucarry, j, s, scale, k[, stale]) -> ucarry`` consumes one
    event (completing client j, ring slot s, update scale, server step k)
    exactly as Algorithm 1 lines 9-11 — both the host-replay scan body and
    the fused device-stream body compose it with their event producer.
    ``stale`` (server steps the task spent in flight) feeds the optional
    staleness cutoff of ``guard``.  With ``master`` (the default update on
    an all-float tree) ``w`` is the master iterate and the update is
    `_make_apply_event`'s; otherwise ``w`` is the parameter pytree and
    ``update_fn`` steps it.
    """
    import jax
    import jax.numpy as jnp

    if guard is not None and not master:
        raise ValueError(
            "the divergence guard requires all-float parameters and the "
            "default linear update"
        )
    tree_map = jax.tree_util.tree_map
    apply_event = _make_apply_event(fedbuff_Z, ring, guard) if master else None

    def update_step(ucarry, j, s, scale, k, stale=None):
        w, snaps, acc, gcnt = ucarry
        # gather the completing task's dispatch-time snapshot (Alg. 1 line 9)
        w_disp = ring.read(snaps, s)
        with jax.named_scope(obs.GRAD):
            g = grad_fn(j, w_disp, k)
        if master:
            return apply_event(ucarry, ring.master(g), s, scale, k, stale)
        if fedbuff_Z > 0:
            acc = tree_map(lambda a, y: a + y, acc, g)
            fire = ((k + 1) % fedbuff_Z) == 0
            eff = jnp.where(fire, scale / fedbuff_Z, 0.0)
            w = update_fn(w, acc, eff)
            acc = tree_map(lambda a: a * (~fire).astype(a.dtype), acc)
        else:
            w = update_fn(w, g, scale)
        # the freed slot hosts the new dispatch with the updated params
        return (w, ring.write(snaps, s, w), acc, gcnt)

    return _scoped(obs.RING, update_step)


def _make_batched_grads(grad_fn, ring: _Ring):
    """Batched gradient call over one micro-block of the flat ring: (E,)
    client ids, (E, P) stored rows, (E,) server steps -> (E, P) packed
    gradients.  Shared by the host block step and the fused device window;
    the gradient calls are `obs.GRAD`, the row codec `obs.RING`."""
    import jax

    grad = _scoped(obs.GRAD, grad_fn)
    return _scoped(obs.RING, jax.vmap(
        lambda j, wi, k: ring.master(grad(j, ring.to_tree(wi), k))
    ))


def _fedbuff_block_deltas(Gm, scm, k, m, acc, Z):
    """Closed-form FedBuff per-event deltas over one (full) micro-block.

    Gradient g_j is applied exactly once — at the first buffer flush at or
    after its arrival — so D_i = 1{flush at i} * (scale_i/Z) * (carried
    buffer + gradients since the previous flush), computed from the in-block
    flush positions.  Returns ``(D, acc')``: the (E, P) scaled update deltas
    (prefix-summable like the gen_async path) and the buffer carried out of
    the block.
    """
    import jax
    import jax.numpy as jnp

    cum = jnp.cumsum(Gm, axis=0)
    fire = m & (((k + 1) % Z) == 0)
    E = m.shape[0]
    fi = jnp.where(fire, jnp.arange(E, dtype=jnp.int32), -1)
    last_incl = jax.lax.cummax(fi)  # last flush at or before i
    prev = jnp.concatenate([jnp.full((1,), -1, jnp.int32), last_incl[:-1]])
    prevcum = jnp.where((prev >= 0)[:, None], cum[jnp.maximum(prev, 0)], 0.0)
    first = jnp.where(prev < 0, 1.0, 0.0)[:, None]
    acc_at = cum - prevcum + first * acc.astype(jnp.float32)
    D = jnp.where(fire, scm / Z, 0.0)[:, None] * acc_at
    lastf = last_incl[-1]
    flushed = jnp.where(lastf >= 0, cum[jnp.maximum(lastf, 0)], 0.0)
    acc = (
        jnp.where(lastf >= 0, 0.0, 1.0) * acc.astype(jnp.float32)
        + (cum[-1] - flushed)
    ).astype(acc.dtype)
    return D, acc


def _make_block_step(
    grad_fn, fedbuff_Z, ring: _Ring, kernel, lane_axis=None, guard=None,
):
    """One event micro-block of the blocked engine over the flat ring.

    ``block_step(ucarry, j, s, scale, k, mask) -> ucarry`` consumes up to E
    conflict-free events: one batched snapshot gather, one vmapped gradient
    call, then the exact sequential iterates via a prefix sum over the
    per-event update deltas D_i (w_i = w_0 - sum_{j<=i} D_j), scattered back
    in one pass.  Padded lanes (mask False) carry zero scale and the trash
    ring row, so they are arithmetic no-ops.

    FedBuff decomposes into the same prefix form via
    `_fedbuff_block_deltas`.

    With ``lane_axis`` set, the step is the per-device body of a `shard_map`
    whose E lanes are partitioned over that mesh axis: each device gathers
    the snapshots of — and differentiates — only its E/D lanes, the local
    per-lane prefix sums are combined with ONE cross-device ``all_gather``
    (lane prefixes and slot ids ride in the same collective; the exclusive
    per-device offsets fall out of the gathered lane totals), and the
    scatter into the replicated ring buffer is applied identically on every
    device (`kernels.ref.block_scatter_rows_ref` / the Pallas
    `kernels.weighted_update.block_scatter_rows`).  FedBuff gathers the
    masked lane *gradients* instead — its flush positions couple all lanes —
    then applies the same closed-form decomposition; either way the
    gradient FLOPs, the gather bandwidth and the pack/unpack work are all
    divided by the lane-device count.
    """
    import jax
    import jax.numpy as jnp

    if kernel == "pallas":
        # the ops wrappers consult the cached autotune table (backend, P, E)
        # for the column tile (a miss is the plain full-BLOCK_TILE kernel)
        # and pick interpret mode from the platform
        from ..kernels.ops import block_prefix_update as apply_block
        from ..kernels.ops import block_scatter_rows as scatter_rows
    elif kernel == "jnp":
        from ..kernels.ref import block_prefix_update_ref, block_scatter_rows_ref

        apply_block = block_prefix_update_ref
        scatter_rows = block_scatter_rows_ref
    else:
        raise ValueError(kernel)

    grads = _make_batched_grads(grad_fn, ring)
    max_sq = float(guard.max_grad_norm) ** 2 if guard is not None else 0.0

    def guard_rows(G, scm, gcnt):
        # row-wise divergence check over the (local) lane batch; the zeroed
        # rows flow through the prefix sum as exact no-ops.  Staleness is a
        # host-side concern on the blocked replay path (the event arrays are
        # pre-simulated, so stale scales arrive already zeroed).
        sq = jnp.sum(jnp.square(G.astype(jnp.float32)), axis=1)
        bad = ~jnp.isfinite(sq)
        if max_sq > 0.0:
            bad = bad | (sq > max_sq)
        cnt = jnp.sum((bad & (scm != 0)).astype(jnp.int32))
        if lane_axis is not None:
            cnt = jax.lax.psum(cnt, lane_axis)
        gcnt = gcnt.at[0].add(cnt)
        return jnp.where(bad[:, None], jnp.zeros_like(G), G), gcnt

    def block_step(ucarry, j, s, sc, k, m):
        w, snaps, acc, gcnt = ucarry
        G = grads(j, snaps[s], k)  # (E_local, P) batched over (local) lanes
        scm = jnp.where(m, sc, 0.0).astype(jnp.float32)
        if guard is not None:
            G, gcnt = guard_rows(G, scm, gcnt)
        if lane_axis is None:
            if fedbuff_Z > 0:
                Gm = jnp.where(m[:, None], G, 0).astype(jnp.float32)
                D, acc = _fedbuff_block_deltas(Gm, scm, k, m, acc, fedbuff_Z)
            else:
                D = scm[:, None] * G.astype(jnp.float32)
            snaps, w = apply_block(snaps, w, D, s)
            return (w, snaps, acc, gcnt)
        if fedbuff_Z > 0:
            # flush positions couple all lanes: gather the masked lane
            # gradients (+ metadata) in one collective, then run the same
            # closed form on the full block, replicated
            Gm = jnp.where(m[:, None], G, 0).astype(jnp.float32)
            Gm, s_all, scm_all, k_all, m_all = jax.lax.all_gather(
                (Gm, s, scm, k, m), lane_axis, tiled=True
            )
            D, acc = _fedbuff_block_deltas(
                Gm, scm_all, k_all, m_all, acc, fedbuff_Z
            )
            snaps, w = apply_block(snaps, w, D, s_all)
            return (w, snaps, acc, gcnt)
        # gen_async: local lane prefix + one collective, then the global
        # iterates W_i = w - (S_all + exclusive device offset), replicated
        Dl = scm[:, None] * G.astype(jnp.float32)
        S = jnp.cumsum(Dl, axis=0)  # local inclusive lane prefix
        S_all, s_all = jax.lax.all_gather((S, s), lane_axis)  # (D, El, P/[El])
        totals = S_all[:, -1, :]
        off = jnp.cumsum(totals, axis=0) - totals  # exclusive device offsets
        E = s_all.size
        W = w.astype(jnp.float32)[None] - (S_all + off[:, None, :]).reshape(
            E, -1
        )
        snaps, w = scatter_rows(snaps, w, W, s_all.reshape(E))
        return (w, snaps, acc, gcnt)

    return _scoped(obs.RING, block_step)


def _init_update_carry(w0, rows, ring: _Ring, master, fedbuff_Z):
    """(w, snaps, acc, gcnt) initial carry + the carry->pytree decoder.

    ``rows`` is the snapshot ring height — C for the per-event engine, C+1
    for the blocked engine (the extra trash row absorbs padded scatters).
    With ``master`` the carried ``w`` is the master iterate in the ring's
    compute dtype, else the parameter pytree itself.  ``gcnt`` is the (2,)
    int32 ``[guard_rejects, stale_drops]`` counter — carried
    unconditionally (two scalars) so the carry structure does not depend on
    whether a guard is active.
    """
    import jax
    import jax.numpy as jnp

    w_init = ring.master(w0) if master else w0
    acc0 = (
        jax.tree_util.tree_map(jnp.zeros_like, w_init) if fedbuff_Z > 0 else ()
    )
    to_tree = ring.to_tree if master else (lambda w: w)
    gcnt0 = jnp.zeros((2,), jnp.int32)
    return (w_init, ring.fill(w0, rows), acc0, gcnt0), to_tree


def _default_update(update_fn):
    """Resolve update_fn; the default casts back per leaf so the scan carry
    dtype stays stable (bf16 params with an fp32 scale would otherwise
    promote)."""
    import jax

    if update_fn is not None:
        return update_fn, False
    tree_map = jax.tree_util.tree_map
    return (
        lambda w, g, s: tree_map(lambda x, y: (x - s * y).astype(x.dtype), w, g),
        True,
    )


# ------------------------------------------------------------------ #
# host stream: replay a pre-simulated EventStream
# ------------------------------------------------------------------ #
def _make_host_runner(
    grad_fn: Callable[[Any, Pytree, Any], Pytree],
    C: int,
    *,
    fedbuff_Z: int = 0,
    eval_fn: Callable[[Pytree], Any] | None = None,
    eval_every: int = 0,
    update_fn: Callable[[Pytree, Pytree, Any], Pytree] | None = None,
    unroll: int = 1,
    snapshot_dtype=None,
    guard: GuardConfig | None = None,
):
    """Build the replay engine for a fixed algorithm shape.

    Returns ``run(w0, J, slot, scale, eval_every=...) -> (w_final, evals)``
    — with ``guard`` set, ``(w_final, evals, gcnt)`` where ``gcnt`` is the
    (2,) ``[guard_rejects, stale_drops]`` counter (staleness on the replay
    path is enforced host-side by zeroing scales before the call, so only
    the divergence slot accumulates here) —
    a pure function: `jax.jit` it directly (``eval_every`` is a Python
    int, pass it via ``static_argnames``), or `jax.vmap(run, in_axes=(None,
    0, 0, 0))` to execute a whole scenario matrix in one compiled call.
    ``evals`` is the eval_fn curve sampled every `eval_every` steps (empty
    array when evaluation is off).  The factory-level ``eval_every`` only
    sets the run-time default, so one runner serves any eval cadence.

    grad_fn(j, w, k): traceable stochastic gradient of client j at params w,
    server step k.  update_fn(w, g, scale) defaults to w - scale*g.
    """
    import jax
    import jax.numpy as jnp

    update_fn, default_update = _default_update(update_fn)
    eval_every_default = eval_every

    def run(w0, J, slot, scale, eval_every=eval_every_default):
        # a replayed stream may carry slot-C (fault) events: drop their writes
        ring = _leaf_ring(w0, snapshot_dtype, drop=True)
        master = default_update and ring.master is not None
        update_step = _make_update_step(
            grad_fn, fedbuff_Z, update_fn, ring, master, guard
        )

        def body(carry, xs):
            j, s, sc, k = xs
            return update_step(carry, j, s, sc, k), ()

        def scan(carry, Jc, slotc, scalec, k0):
            ks = k0 + jnp.arange(Jc.shape[0], dtype=Jc.dtype)
            return jax.lax.scan(body, carry, (Jc, slotc, scalec, ks), unroll=unroll)[0]

        carry, to_tree = _init_update_carry(w0, C, ring, master, fedbuff_Z)
        T = int(J.shape[0])
        if eval_fn is not None and eval_every and T >= eval_every:
            n_chunks = T // eval_every
            Tc = n_chunks * eval_every

            # per-chunk absolute step offsets ride along as a scan input
            def chunk_body(c, xs):
                Jc, sc, scc, k0 = xs
                c = scan(c, Jc, sc, scc, k0)
                return c, eval_fn(to_tree(c[0]))

            xs = (
                J[:Tc].reshape(n_chunks, eval_every),
                slot[:Tc].reshape(n_chunks, eval_every),
                scale[:Tc].reshape(n_chunks, eval_every),
                jnp.arange(n_chunks, dtype=J.dtype) * eval_every,
            )
            carry, evals = jax.lax.scan(chunk_body, carry, xs)
            if Tc < T:  # tail events past the last eval point
                carry = scan(carry, J[Tc:], slot[Tc:], scale[Tc:], k0=Tc)
            if guard is not None:
                return to_tree(carry[0]), evals, carry[3]
            return to_tree(carry[0]), evals
        carry = scan(carry, J, slot, scale, k0=0)
        if guard is not None:
            return to_tree(carry[0]), jnp.zeros((0,)), carry[3]
        return to_tree(carry[0]), jnp.zeros((0,))

    return run


def _check_lane_devices(lane_devices: int, block_size: int) -> None:
    """Validate the lane-shard request against the block shape and platform."""
    import jax

    if lane_devices < 1:
        raise ValueError("lane_devices >= 1 required")
    if lane_devices == 1:
        return
    if block_size < 2:
        raise ValueError(
            "lane_devices > 1 shards the E-lane micro-block gradient batch "
            "across devices and requires block_size > 1"
        )
    if block_size % lane_devices:
        raise ValueError(
            f"block_size={block_size} must be a multiple of "
            f"lane_devices={lane_devices} (each device owns E/D lanes)"
        )
    avail = jax.device_count()
    if lane_devices > avail:
        raise ValueError(
            f"lane_devices={lane_devices} but only {avail} device(s) are "
            "visible (on CPU, set XLA_FLAGS="
            "--xla_force_host_platform_device_count=N)"
        )


def _lane_mesh(lane_devices: int):
    """1-D ("lanes",) mesh over the first lane_devices visible devices."""
    import jax
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:lane_devices]), ("lanes",))


def _make_host_block_runner(
    grad_fn: Callable[[Any, Pytree, Any], Pytree],
    C: int,
    block_size: int,
    *,
    fedbuff_Z: int = 0,
    eval_fn: Callable[[Pytree], Any] | None = None,
    update_fn: Callable[[Pytree, Pytree, Any], Pytree] | None = None,
    unroll: int = 1,
    kernel: str = "jnp",
    snapshot_dtype=None,
    lane_devices: int = 1,
    vmap_streams: bool = False,
    guard: GuardConfig | None = None,
):
    """Build the blocked replay engine over `queue_sim.EventBlocks` arrays.

    Returns ``run(w0, J, slot, scale, k, mask, chunk_blocks=0, n_chunks=0)
    -> (w_final, evals)`` consuming (B, E) blocked arrays (see
    `blocked_inputs`).  ``chunk_blocks``/``n_chunks`` are the static eval
    layout: the first ``n_chunks * chunk_blocks`` rows are eval-interval
    groups (eval fires after each group — the conflict-free cut guarantees
    group boundaries land on exact event multiples), trailing rows replay
    flat.

    The blocked engine requires the flat-packed snapshot ring (all-float
    parameters) and the default linear update; ``kernel`` picks the
    jnp fallback ("jnp", the CPU/parity path) or the fused Pallas kernel
    ("pallas") for the prefix-scan + scatter.

    ``lane_devices=D > 1`` shards the E gradient lanes of every micro-block
    across D devices via `shard_map`: the (B, E) event arrays are
    partitioned over their lane axis, each device gathers/differentiates
    its E/D lanes, and one all-gather per block recombines the lane
    prefixes (see `_make_block_step`).  The scan structure, eval chunking
    and results are identical to the unsharded runner (≤1e-5 in fp32 —
    fp32 lane prefixes are re-associated per device).  ``vmap_streams``
    additionally maps the per-device body over a leading scenario axis —
    the scenario × lane 2-D layout `fl.run_matrix` uses.
    """
    import jax
    import jax.numpy as jnp

    if update_fn is not None:
        raise ValueError(
            "block_size > 1 requires the default update w - scale*g "
            "(the blocked replay reconstructs iterates via a prefix sum)"
        )
    if block_size < 2:
        raise ValueError("use _make_host_runner for block_size <= 1")
    _check_lane_devices(lane_devices, block_size)
    lane_axis = "lanes" if lane_devices > 1 else None
    pad_to = 1
    if kernel == "pallas":
        from ..kernels.weighted_update import BLOCK_TILE

        pad_to = BLOCK_TILE

    def run_local(w0, J, slot, scale, k, mask, chunk_blocks=0, n_chunks=0):
        ring = _flat_ring(w0, snapshot_dtype, pad_to=pad_to)
        block_step = _make_block_step(
            grad_fn, fedbuff_Z, ring, kernel, lane_axis, guard,
        )
        carry, to_tree = _init_update_carry(w0, C + 1, ring, True, fedbuff_Z)

        def body(c, xs):
            return block_step(c, *xs), ()

        def scan(c, *arrs):
            return jax.lax.scan(body, c, arrs, unroll=unroll)[0]

        B = int(J.shape[0])
        Bm = n_chunks * chunk_blocks
        if eval_fn is not None and n_chunks and chunk_blocks:
            resh = lambda a: a[:Bm].reshape(
                (n_chunks, chunk_blocks) + a.shape[1:]
            )

            def chunk_body(c, xs):
                c = scan(c, *xs)
                return c, eval_fn(to_tree(c[0]))

            carry, evals = jax.lax.scan(
                chunk_body, carry, tuple(resh(a) for a in (J, slot, scale, k, mask))
            )
            if Bm < B:  # tail blocks past the last eval point
                carry = scan(
                    carry, J[Bm:], slot[Bm:], scale[Bm:], k[Bm:], mask[Bm:]
                )
            if guard is not None:
                return to_tree(carry[0]), evals, carry[3]
            return to_tree(carry[0]), evals
        carry = scan(carry, J, slot, scale, k, mask)
        if guard is not None:
            return to_tree(carry[0]), jnp.zeros((0,)), carry[3]
        return to_tree(carry[0]), jnp.zeros((0,))

    if lane_devices == 1:
        if not vmap_streams:
            return run_local

        def run_batched(w0, J, slot, scale, k, mask, chunk_blocks=0,
                        n_chunks=0):
            return jax.vmap(
                lambda w, a, b, c, d, e: run_local(
                    w, a, b, c, d, e, chunk_blocks, n_chunks
                ),
                in_axes=(None, 0, 0, 0, 0, 0),
            )(w0, J, slot, scale, k, mask)

        return run_batched

    from jax.sharding import PartitionSpec as P

    mesh = _lane_mesh(lane_devices)
    # blocked arrays are (B, E) — or (S, B, E) under vmap_streams — and are
    # partitioned over their trailing lane axis; w0 and outputs replicate
    lane_spec = (
        P(None, None, "lanes") if vmap_streams else P(None, "lanes")
    )

    def run(w0, J, slot, scale, k, mask, chunk_blocks=0, n_chunks=0):
        if vmap_streams:
            base = jax.vmap(
                lambda w, a, b, c, d, e: run_local(
                    w, a, b, c, d, e, chunk_blocks, n_chunks
                ),
                in_axes=(None, 0, 0, 0, 0, 0),
            )
        else:
            base = lambda w, a, b, c, d, e: run_local(
                w, a, b, c, d, e, chunk_blocks, n_chunks
            )
        f = jax.shard_map(
            base,
            mesh=mesh,
            in_specs=(P(),) + (lane_spec,) * 5,
            out_specs=(P(), P(), P()) if guard is not None else (P(), P()),
            check_vma=False,
        )
        return f(w0, J, slot, scale, k, mask)

    return run


# ------------------------------------------------------------------ #
# device stream: fused generator + control loop
# ------------------------------------------------------------------ #
def _make_fused_advance(
    grad_fn, n, C, E, update_step, ring, fedbuff_Z, guard, *,
    importance, faulty, guard_stale, need_stats, axis, lane_devices, unroll,
    classes=None, serving=None, scenario=False,
):
    """The chunk-advance core of the fused engine, shared with `engine_ckpt`.

    ``build(mu, eta, fr)`` closes over the traced scalars and returns
    ``advance(ucarry, sstate, stats, slot_scale, p, ur, ue, Kc, k0[, ub])
    -> (ucarry, sstate, stats, slot_scale, ts)`` — fused CS steps over one
    chunk of pre-drawn uniforms: E-event windows plus a per-event remainder.
    Factoring it out of `make_fused_runner` keeps exactly one copy of the
    event semantics for the monolithic runner and the checkpointed
    chunk-at-a-time driver (`core.engine_ckpt`).

    ``classes`` (a `stream_device.ClassSpec`) switches the event source to
    the sparse O(C) stream: ``mu``/``p`` become (m,) class-level values,
    stats accumulate per class, and per-event cost is flat in n.  The
    algorithm side is untouched — events still carry global client ids and
    slot indices, so the snapshot ring, guards and update math are shared.
    Sparse is per-event only (E == 1); ``ub`` is the extra per-event
    uniform resolving idle-pool availability bits under faults.
    """
    import jax
    import jax.numpy as jnp

    from . import stream_device as sd

    sparse = classes is not None
    if sparse and E > 1:
        raise ValueError("the sparse stream supports block_size=1 only")
    scen_on = bool(scenario)
    if scen_on:
        # scenario streams reuse the fault-mode masking idiom (stage/flip
        # events carry slot C and scale 0); `fr` carries the ScenarioRates
        if faulty:
            raise ValueError("scenario= and fault= are separate injection "
                             "paths; compose via ScenarioConfig modulation")
        if sparse:
            raise ValueError(
                "the fused engine's scenario path is dense-only; use "
                "sparse_stats_stream_fn for class-level scenario laws"
            )
        if E > 1:
            raise ValueError("scenario= requires block_size=1")
    spec = classes.device() if sparse else None
    m_cls = classes.m if sparse else 0
    serving_on = serving is not None and serving.enabled
    if serving_on:
        from . import serving as sp

        if sparse:
            raise ValueError("serving= requires the dense stream (classes=None)")
        if E > 1:
            raise ValueError("serving= requires block_size=1")
        if fedbuff_Z:
            raise ValueError("serving= composes with Algorithm 1, not FedBuff")
        serving.validate()

    def build(mu, eta, fr):
        def event_body(c, x):
            """One fused CS step (stream advance + algorithm update).

            With ``serving`` the step first races the closed network
            against the open serving stream (`merged_stream_step`), then
            runs BOTH halves unconditionally in the masked style: a serve
            event carries ``j = n``, ``slot = C`` and a zeroed scale, so
            the gradient it computes is discarded by the masked axpy and
            every scatter drops — exactly the fault-masking idiom
            (`KIND_CRASH` etc.) — while on train events `serve_apply`
            masks to a no-op via ``live=is_ext``.  No `lax.cond`: routing
            the snapshot ring through a cond output forces XLA to
            re-materialize the ring every step instead of aliasing it
            through the scan carry (measured ~1.6x wall), and even a
            small-state cond pays per-step buffer marshaling that exceeds
            the masked ops it skips.  The known-good snapshot pointer
            advances only on *accepted* training updates (nonzero scale
            and no guard counter movement), so the serving read path can
            never observe a guard-rejected iterate.
            """
            if serving_on:
                ucarry, sstate, stats, slot_scale, p, sv, sstats = c
            else:
                ucarry, sstate, stats, slot_scale, p = c
            if serving_on:
                urk, uek, kn, k = x
                occ_pre = sstate.occ
                if faulty:
                    avail_pre = sstate.avail
                r_ext = sv.cdf[-1]  # rate cache refreshed in serve_apply
                sstate, ev, is_ext, u_ext = sd.merged_stream_step(
                    sstate, mu, r_ext, (urk, uek, kn), fr
                )
            elif sparse:
                if faulty:
                    urk, uek, kn, ubk, k = x
                else:
                    urk, uek, kn, k = x
                if need_stats:
                    occ_pre, busy_pre, avail_pre = sd.sparse_class_stats(
                        sstate, m_cls, fault=faulty
                    )
                if faulty:
                    sstate, ev = sd.sparse_fault_stream_step(
                        sstate, mu, spec, fr, (urk, uek, kn, ubk)
                    )
                else:
                    sstate, ev = sd.sparse_stream_step(
                        sstate, mu, spec, (urk, uek, kn)
                    )
            elif scen_on:
                urk, uek, kn, uphk, k = x
                occ_pre = sstate.occ
                avail_pre = sstate.avail
                speed_pre = avail_pre + (1.0 - avail_pre) * fr.rate_scale
                sstate, ev = sd.scenario_stream_step(
                    sstate, mu, fr, (urk, uek, kn, uphk)
                )
            else:
                urk, uek, kn, k = x
                occ_pre = sstate.occ
                if faulty:
                    avail_pre = sstate.avail
                    sstate, ev = sd.fault_stream_step(
                        sstate, mu, fr, (urk, uek, kn)
                    )
                else:
                    sstate, ev = sd.stream_step(sstate, mu, (urk, uek, kn))
            # flips carry slot C: the (C,) gather clamps but the scale is
            # masked to 0, and every scatter below drops out of bounds
            scale = slot_scale[ev.slot] if importance else eta
            if faulty or serving_on or scen_on:
                scale = jnp.where(ev.kind == KIND_COMPLETE, scale, 0.0)
            stale = (k - stats.slot_step[ev.slot]) if guard_stale else None
            if serving_on:
                # pre-event depth persists over dt: integrate it before
                # the transition, on every (train or serve) event
                with jax.named_scope(obs.SERVE):
                    sstats = sp.serve_time_step(sstats, sv, ev.dt)
                gcnt_pre = ucarry[3]
                ucarry = update_step(ucarry, ev.j, ev.slot, scale, k, stale)
                # unguarded runs never move gcnt: skip the per-step compare
                accepted = scale != 0.0
                if guard is not None:
                    accepted &= jnp.all(ucarry[3] == gcnt_pre)
                sv = sv._replace(
                    kg_slot=jnp.where(accepted, ev.slot, sv.kg_slot),
                    kg_step=jnp.where(accepted, jnp.int32(k) + 1, sv.kg_step),
                )
                # unconditional masked call (live=is_ext): a lax.cond
                # here would marshal the ~25 serve-state buffers through
                # the conditional every step, which costs more than the
                # masked small ops themselves
                with jax.named_scope(obs.SERVE):
                    sv, sstats = sp.serve_apply(
                        serving, sv, sstats, u_ext, ev.t, k, ucarry[1],
                        live=is_ext,
                    )
            else:
                ucarry = update_step(ucarry, ev.j, ev.slot, scale, k, stale)
            if need_stats:
                if sparse:
                    cls_j = spec.inv_cls[ev.j]
                    occ_post = sd.class_occupancy(sstate.cls, m_cls)
                    if faulty:
                        stats = sd.sparse_fault_stats_step(
                            stats, ev, cls_j, occ_pre, busy_pre, avail_pre,
                            occ_post, k,
                        )
                    else:
                        stats = sd.sparse_stats_step(
                            stats, ev, cls_j, occ_pre, busy_pre, occ_post, k
                        )
                elif scen_on:
                    stats = sd.scenario_stats_step(
                        stats, ev, occ_pre, avail_pre, speed_pre,
                        sstate.occ, k,
                    )
                elif faulty:
                    stats = sd.fault_stats_step(
                        stats, ev, occ_pre, avail_pre, sstate.occ, k
                    )
                else:
                    stats = sd.stats_step(stats, ev, occ_pre, sstate.occ, k)
            if importance:
                pk = p[spec.inv_cls[ev.k]] if sparse else p[ev.k]
                if serving_on:
                    # serve events carry slot C: no re-dispatch, the
                    # importance-scale scatter must drop, not overwrite
                    slot_scale = slot_scale.at[ev.slot].set(
                        eta / (n * pk), mode="drop"
                    )
                else:
                    slot_scale = slot_scale.at[ev.slot].set(eta / (n * pk))
            if serving_on:
                return (ucarry, sstate, stats, slot_scale, p, sv, sstats), ev.t
            return (ucarry, sstate, stats, slot_scale, p), ev.t

        def window_body(c, x):
            """One E-event micro-block of fused CS steps.

            Phase 1 advances the closed network E steps (cheap integer /
            scalar ops, one inner scan); phase 2 batch-gathers the E
            window-entry snapshots and computes all gradients in one vmapped
            call; phase 3 replays the exact sequential updates, recomputing
            a gradient only when its task was dispatched *inside* this
            window (``conf >= 0`` — its snapshot was written after the batch
            gather).
            """
            ucarry, sstate, stats, slot_scale, p = c
            urw, uew, knw, kw = x

            def sbody(cc, xx):
                sstate, stats, slot_scale, lastw, i = cc
                urk, uek, kn, k = xx
                occ_pre = sstate.occ
                if faulty:
                    avail_pre = sstate.avail
                    sstate, ev = sd.fault_stream_step(
                        sstate, mu, fr, (urk, uek, kn)
                    )
                else:
                    sstate, ev = sd.stream_step(sstate, mu, (urk, uek, kn))
                sc = slot_scale[ev.slot] if importance else eta
                if faulty:
                    sc = jnp.where(ev.kind == KIND_COMPLETE, sc, 0.0)
                conf = lastw[ev.slot]
                lastw = lastw.at[ev.slot].set(i)
                dl = (k - stats.slot_step[ev.slot]) if guard_stale else None
                if need_stats:
                    if faulty:
                        stats = sd.fault_stats_step(
                            stats, ev, occ_pre, avail_pre, sstate.occ, k
                        )
                    else:
                        stats = sd.stats_step(stats, ev, occ_pre, sstate.occ, k)
                if importance:
                    slot_scale = slot_scale.at[ev.slot].set(eta / (n * p[ev.k]))
                ys = (ev.j, ev.slot, sc, conf, ev.t)
                if guard_stale:
                    ys = ys + (dl,)
                return (sstate, stats, slot_scale, lastw, i + 1), ys

            lastw0 = jnp.full((C,), -1, jnp.int32)
            (sstate, stats, slot_scale, _, _), sys_ = jax.lax.scan(
                sbody,
                (sstate, stats, slot_scale, lastw0, jnp.int32(0)),
                (urw, uew, knw, kw),
            )
            if guard_stale:
                jv, sv, scv, confv, tv, dlv = sys_
            else:
                jv, sv, scv, confv, tv = sys_
            snaps = ucarry[1]
            batched_grads = _make_batched_grads(grad_fn, ring)
            if axis is None:
                with jax.named_scope(obs.RING):
                    rows = snaps[sv]
                G0 = batched_grads(jv, rows, kw)
            else:
                # lane-sharded: this device differentiates E/D of the
                # window's lanes; one all-gather recombines the batch
                El = E // lane_devices
                d = jax.lax.axis_index(axis)
                sl = lambda a: jax.lax.dynamic_slice_in_dim(a, d * El, El, 0)
                jl, svl, kl = sl(jv), sl(sv), sl(kw)
                with jax.named_scope(obs.RING):
                    rows = snaps[svl]
                Gl = batched_grads(jl, rows, kl)
                with jax.named_scope(obs.GRAD):
                    G0 = jax.lax.all_gather(Gl, axis, tiled=True)

            apply_event = _make_apply_event(fedbuff_Z, ring, guard)

            def fbody(cc, xx):
                if guard_stale:
                    j, s, sc, conf, g0, k, dl = xx
                else:
                    j, s, sc, conf, g0, k = xx
                    dl = None
                with jax.named_scope(obs.RING):
                    row = cc[1][s]
                with jax.named_scope(obs.GRAD):
                    g = jax.lax.cond(
                        conf >= 0,
                        lambda r: ring.master(grad_fn(j, ring.to_tree(r), k)),
                        lambda r: g0,
                        row,
                    )
                return apply_event(cc, g, s, sc, k, dl), ()

            xs_f = (jv, sv, scv, confv, G0, kw)
            if guard_stale:
                xs_f = xs_f + (dlv,)
            ucarry, _ = jax.lax.scan(fbody, ucarry, xs_f)
            return (ucarry, sstate, stats, slot_scale, p), tv

        def advance(ucarry, sstate, stats, slot_scale, p, ur, ue, Kc, k0,
                    ub=None, sv=None, sstats=None, uph=None):
            """Fused CS steps over one chunk: E-event windows + remainder.

            With serving the carry widens by ``(sv, sstats)`` — the serve
            table + counters ride the scan (and the checkpoint) like any
            other state — and the return gains the updated pair.
            """
            if serving_on:
                c = (ucarry, sstate, stats, slot_scale, p, sv, sstats)
            else:
                c = (ucarry, sstate, stats, slot_scale, p)
            Lc = Kc.shape[0]
            ks = k0 + jnp.arange(Lc, dtype=jnp.int32)
            nW = Lc // E if E > 1 else 0
            Wc = nW * E
            ts_parts = []
            if nW:
                resh = lambda a: a[:Wc].reshape(nW, E)
                c, tsw = jax.lax.scan(
                    _scoped(obs.RACE, window_body), c,
                    (resh(ur), resh(ue), resh(Kc), resh(ks)), unroll=unroll,
                )
                ts_parts.append(tsw.reshape(Wc))
            if Wc < Lc:
                if sparse and faulty:
                    xse = (ur[Wc:], ue[Wc:], Kc[Wc:], ub[Wc:], ks[Wc:])
                elif scen_on:
                    xse = (ur[Wc:], ue[Wc:], Kc[Wc:], uph[Wc:], ks[Wc:])
                else:
                    xse = (ur[Wc:], ue[Wc:], Kc[Wc:], ks[Wc:])
                c, tse = jax.lax.scan(
                    _scoped(obs.RACE, event_body), c, xse, unroll=unroll
                )
                ts_parts.append(tse)
            ts = ts_parts[0] if len(ts_parts) == 1 else jnp.concatenate(ts_parts)
            if serving_on:
                ucarry, sstate, stats, slot_scale, p, sv, sstats = c
                return ucarry, sstate, stats, slot_scale, ts, sv, sstats
            ucarry, sstate, stats, slot_scale, p = c
            return ucarry, sstate, stats, slot_scale, ts

        return advance

    return build


def make_fused_runner(
    grad_fn: Callable[[Any, Pytree, Any], Pytree],
    n: int,
    C: int,
    T: int,
    *,
    weighting: str = "importance",
    fedbuff_Z: int = 0,
    eval_fn: Callable[[Pytree], Any] | None = None,
    eval_every: int = 0,
    adaptive: bool = False,
    refresh_every: int = 0,
    bound: BoundConstants | None = None,
    ctrl_lr: float = 0.3,
    ctrl_iters: int = 4,
    update_fn: Callable[[Pytree, Pytree, Any], Pytree] | None = None,
    init: str = "distinct",
    unroll: int = 1,
    block_size: int = 1,
    collect_extras: bool = True,
    snapshot_dtype=None,
    lane_devices: int = 1,
    lane_axis: str | None = None,
    fault: FaultConfig | None = None,
    guard: GuardConfig | None = None,
    classes=None,
    serving=None,
    scenario=None,
):
    """Build the fused engine: `stream_device.stream_step` ∘ `update_step`.

    Returns ``run(w0, mu, p0, key, eta) -> (w_final, evals, extras)``.  The
    closed network advances inside the scan (exponential service only), so
    nothing is pre-simulated on the host; `jax.vmap(run, in_axes=(None, 0,
    0, 0, None))` executes a scenario matrix in one compiled call.

    With ``adaptive=True`` the sampling vector is re-optimized from the
    running occupancy/rate estimates every `refresh_every` steps
    (`stream_device.ctrl_refresh`); each in-flight task keeps the
    importance scale of its dispatch-time p, so the weighted update stays
    unbiased under the time-varying policy.  ``extras`` carries the
    per-step event times plus the final/trajectory sampling vectors and
    the on-device occupancy, busy-time, delay and completion statistics;
    ``collect_extras=False`` prunes all of that dead weight (benchmark /
    fire-and-forget runs) — only ``p_final`` survives.

    With ``block_size=E > 1`` the stream advances E CS steps per scan
    iteration and the E gradients are computed in one vmapped call from the
    window-entry snapshots; a sequential fixup pass re-derives the exact
    iterates, recomputing (under `lax.cond`) only the gradients whose task
    was dispatched inside the same window — the device-side analogue of the
    host path's conflict-free cut, trajectory-identical to ``block_size=1``
    with the same PRNG key.  Caveat: under `jax.vmap` (`vmap_scenarios` /
    `run_matrix(stream="device")`) the conflict predicate is batched, so the
    cond lowers to a both-branches select and every gradient is computed
    twice — blocked fused runs are only a win un-vmapped; the vmapped
    scenario matrix should prefer the host blocked path or ``block_size=1``.

    ``lane_devices=D > 1`` shards each window's E-lane batched gradient
    call across D devices (`shard_map` over a "lanes" mesh axis; one
    all-gather per window recombines the lane gradients).  The stream
    advance and the sequential fixup replay replicated — they are cheap
    integer/axpy work — so results match the unsharded fused runner.
    ``lane_axis`` is for callers that already run inside a `shard_map`
    (the scenario × lane 2-D mesh of `jit_fused_runner`): it names the
    existing lane axis instead of self-wrapping.

    ``fault`` injects client churn / crashes / straggler timeouts into the
    on-device generator (`stream_device.fault_stream_step`): non-completion
    events carry scale 0 through the shared `_make_apply_event`, so a
    crashed or timed-out task's work is discarded and its slot re-dispatched
    with the *current* server weights — exactly FedBuff's non-fire masking.
    ``guard`` adds the divergence/staleness checks of `GuardConfig`;
    ``extras`` then reports ``guard_rejects`` / ``stale_drops`` (and, under
    faults, the per-kind event counts and availability integrals).  Both
    compose with blocks, lanes and the scenario mesh; neither composes with
    FedBuff (the buffer flush has no per-event masking semantics).

    ``classes`` (a `stream_device.ClassSpec`, from `build_class_spec`)
    switches the event source to the sparse O(C) per-event stream for
    large n: the closed network is represented by its C in-flight slots
    and per-event cost is flat in n.  ``run`` then takes **class-level**
    ``mu``/``p0`` of shape (m,) — per-*node* rates/probabilities, one per
    speed class — and the occupancy/busy/delay/completion extras come back
    with shape (m,) (per class) instead of (n,).  The adaptive control
    loop runs on the class simplex (`ctrl_refresh(..., counts=...)`).
    Requires ``block_size=1`` and ``lane_devices=1``; dispatch draws use
    the O(log m) class tree + within-class uniform member draw, exact in
    law versus the dense path by exchangeability within a class.

    ``serving`` (a `serving.ServingConfig`) merges an **open** Poisson
    inference stream into the event race: requests are admitted through a
    token bucket + queue-depth threshold, served FIFO from the snapshot
    ring at the known-good (last-accepted-update) row, deadline-timed-out
    with capped jittered exponential-backoff retries, and shed under
    overload — the resilient serving plane of `core.serving`.  Requires
    ``block_size=1``, the dense stream, all-float parameters and no
    FedBuff; composes with faults, guards and the adaptive controller.
    Serve events skip the gradient entirely (a `lax.cond` branch,
    un-vmapped) and are invisible to the training-side scatters (slot C /
    client n, the flip masking pattern); the Palm accumulator ``occ_sum``
    does sample at serve epochs too, which is still unbiased for the
    time-average by PASTA.  ``extras`` gains the ``serve_*`` counters,
    histograms and the final serve state.

    ``scenario`` (a `scenario.ScenarioConfig`) swaps the event source to
    `stream_device.scenario_stream_step`: phase-type service (Erlang-k /
    hyperexponential stage chains) + Markov-modulated on/off availability.
    Stage advances and flips carry scale 0 / slot C exactly like fault
    events, so the algorithm half is untouched; ``busy_t`` integrates the
    modulated exposure, keeping the adaptive controller's `ctrl_refresh`
    unbiased per scenario.  Requires ``block_size=1``, the dense stream
    (use `sparse_stats_stream_fn(scenario=True)` for class-level laws),
    and excludes ``fault`` / ``serving`` / FedBuff.  A disabled scenario
    (``exponential`` + always-on) routes through the unmodified engine —
    bitwise-identical to ``scenario=None``.
    """
    import jax
    import jax.numpy as jnp

    from . import stream_device as sd

    if weighting not in ("importance", "plain"):
        raise ValueError(weighting)
    if adaptive:
        if fedbuff_Z:
            raise ValueError("adaptive sampling applies to Algorithm 1, not FedBuff")
        if refresh_every <= 0:
            raise ValueError("adaptive=True requires refresh_every > 0")
        if eval_fn is not None and eval_every and eval_every % refresh_every:
            raise ValueError("eval_every must be a multiple of refresh_every")
    if block_size > 1 and update_fn is not None:
        raise ValueError(
            "block_size > 1 requires the default update w - scale*g"
        )
    bound = bound if bound is not None else BoundConstants(C=C, T=T)
    importance = weighting == "importance"
    E = max(int(block_size), 1)
    if lane_axis is not None and lane_devices <= 1:
        raise ValueError("lane_axis requires lane_devices > 1")
    if lane_devices > 1 and lane_axis is None:
        _check_lane_devices(lane_devices, E)  # incl. device availability
    elif lane_devices > 1 and (E < 2 or E % lane_devices):
        raise ValueError(
            f"block_size={E} must be a >1 multiple of "
            f"lane_devices={lane_devices}"
        )
    wrap_lanes = lane_devices > 1 and lane_axis is None
    axis = lane_axis if lane_axis is not None else (
        "lanes" if lane_devices > 1 else None
    )
    faulty = fault is not None and fault.enabled
    guard_stale = guard is not None and int(guard.stale_cutoff) > 0
    sparse = classes is not None
    scen_on = scenario is not None and scenario.enabled
    if scen_on:
        if faulty:
            raise ValueError(
                "scenario= and fault= are separate injection paths; model "
                "suspension via ScenarioConfig modulation (rate_scale)"
            )
        if sparse:
            raise ValueError(
                "the fused engine's scenario path is dense-only; use "
                "sparse_stats_stream_fn(scenario=True) for class-level laws"
            )
        if E > 1:
            raise ValueError("scenario= requires block_size=1")
        if fedbuff_Z:
            raise ValueError("scenario= composes with Algorithm 1, not FedBuff")
        if serving is not None and serving.enabled:
            raise ValueError("scenario= does not compose with serving=")
    if sparse:
        if E > 1:
            raise ValueError("classes= (sparse stream) requires block_size=1")
        if lane_devices > 1:
            raise ValueError("classes= (sparse stream) requires lane_devices=1")
        if classes.n != n:
            raise ValueError(
                f"ClassSpec covers n={classes.n} clients, runner built "
                f"for n={n}"
            )
    if faulty and fedbuff_Z:
        raise ValueError(
            "fault injection composes with Algorithm 1, not FedBuff "
            "(a crash/timeout at a flush step has no masking semantics)"
        )
    if guard_stale and fedbuff_Z:
        raise ValueError(
            "the staleness cutoff requires the per-event update (fedbuff_Z=0)"
        )
    serving_on = serving is not None and serving.enabled
    if serving_on:
        from . import serving as sp

        serving.validate()
        if E > 1:
            raise ValueError("serving= requires block_size=1")
        if fedbuff_Z:
            raise ValueError("serving= composes with Algorithm 1, not FedBuff")
        if sparse:
            raise ValueError(
                "serving= requires the dense stream (classes=None)"
            )
        if lane_devices > 1:
            raise ValueError("serving= requires lane_devices=1")
        if update_fn is not None:
            raise ValueError("serving= requires the default update w - scale*g")
    # the staleness cutoff reads StatsState.slot_step, so stats must run
    need_stats = collect_extras or adaptive or guard_stale

    # chunk length: refresh and eval both happen at chunk boundaries
    if adaptive:
        L = min(refresh_every, T)
    elif eval_fn is not None and eval_every:
        L = min(eval_every, T)
    else:
        L = T
    n_chunks, Tc = T // L, (T // L) * L
    eval_on = eval_fn is not None and eval_every > 0
    eval_stride = max(eval_every // L, 1) if eval_on else 0

    update_fn, default_update = _default_update(update_fn)

    def run(w0, mu, p0, key, eta):
        if E > 1:
            ring, rows = _flat_ring(w0, snapshot_dtype), C + 1
        else:
            ring = _leaf_ring(
                w0, snapshot_dtype, drop=faulty or serving_on or scen_on
            )
            rows = C
        master = default_update and ring.master is not None
        if serving_on and not master:
            raise ValueError("serving= requires all-float parameters")
        update_step = _make_update_step(
            grad_fn, fedbuff_Z, update_fn, ring, master, guard
        )
        with jax.named_scope(obs.RING):
            ucarry, to_tree = _init_update_carry(
                w0, rows, ring, master, fedbuff_Z
            )

        mu = jnp.asarray(mu, jnp.float32)
        p0 = jnp.asarray(p0, jnp.float32)
        eta = jnp.asarray(eta, jnp.float32)
        spec = classes.device() if sparse else None
        if sparse:
            fr = sd.resolve_fault_rates_classes(fault, classes) if faulty \
                else None
            k_init, k_race, k_exp, k_disp, k_mem, k_bit = jax.random.split(
                key, 6
            )
            u_mem = jax.random.uniform(k_mem, (T,))
            u_bit = jax.random.uniform(k_bit, (T,)) if faulty else None
            u_ph = None
            sstate, init_nodes = sd.sparse_stream_init(
                k_init, spec, C, p0, init=init, fault=faulty
            )
            stats = sd.sparse_stats_init(classes.m, C, fault=faulty)
        elif scen_on:
            fr = sd.resolve_scenario(scenario, n)
            k_init, k_race, k_exp, k_disp, k_ph = jax.random.split(key, 5)
            u_mem = u_bit = None
            u_ph = jax.random.uniform(k_ph, (T,))
            sstate, init_nodes = sd.scenario_stream_init(
                k_init, n, C, p0, fr, init=init
            )
            stats = sd.stats_init(n, C, scenario=True)
        else:
            fr = sd.resolve_fault_rates(fault, n) if faulty else None
            k_init, k_race, k_exp, k_disp = jax.random.split(key, 4)
            u_mem = u_bit = u_ph = None
            sstate, init_nodes = sd.stream_init(
                k_init, n, C, p0, init=init, fault=faulty
            )
            stats = sd.stats_init(n, C, fault=faulty)
        u_race = jax.random.uniform(k_race, (T,))
        u_exp = jax.random.uniform(k_exp, (T,))
        u_disp = jax.random.uniform(k_disp, (T,))
        # dispatch-time importance scale per in-flight slot (Alg. 1 line 10)
        if importance:
            p_nodes0 = p0[spec.inv_cls[init_nodes]] if sparse \
                else p0[init_nodes]
            slot_scale0 = eta / (n * p_nodes0)
        else:
            slot_scale0 = jnp.broadcast_to(eta, (C,))

        advance = _make_fused_advance(
            grad_fn, n, C, E, update_step, ring, fedbuff_Z, guard,
            importance=importance, faulty=faulty, guard_stale=guard_stale,
            need_stats=need_stats, axis=axis, lane_devices=lane_devices,
            unroll=unroll, classes=classes, serving=serving,
            scenario=scen_on,
        )(mu, eta, fr)
        with jax.named_scope(obs.SERVE):
            sv0 = sp.serve_init(serving) if serving_on else None
            sstats0 = sp.serve_stats_init() if serving_on else None

        if sparse:
            # O(log m) class draw + uniform member — flat in n
            def sample_dispatch(p, u, um):
                return sd.sample_dispatch_classes(p, spec, u, um)
        else:
            # segment-tree inverse-CDF: O(log n) ulp error and never lands
            # on a zero-probability client (the clamped fp32
            # cumsum+searchsorted over-selected index n-1 at large n)
            def sample_dispatch(p, u, um=None):
                ptree = sd.tree_build(p)
                return jax.vmap(
                    lambda uu: sd.tree_sample(ptree, uu)
                )(u).astype(jnp.int32)

        def chunk_step(carry, xs):
            if serving_on:
                ucarry, sstate, stats, slot_scale, p, sv, sstats = carry
            else:
                ucarry, sstate, stats, slot_scale, p = carry
                sv = sstats = None
            if sparse and faulty:
                ur, ue, ud, um, ub, k0 = xs
                uph = None
            elif sparse:
                ur, ue, ud, um, k0 = xs
                ub = uph = None
            elif scen_on:
                ur, ue, ud, uph, k0 = xs
                um = ub = None
            else:
                ur, ue, ud, k0 = xs
                um = ub = uph = None
            Kc = sample_dispatch(p, ud, um)
            if serving_on:
                ucarry, sstate, stats, slot_scale, ts, sv, sstats = advance(
                    ucarry, sstate, stats, slot_scale, p, ur, ue, Kc, k0, ub,
                    sv, sstats,
                )
            else:
                ucarry, sstate, stats, slot_scale, ts = advance(
                    ucarry, sstate, stats, slot_scale, p, ur, ue, Kc, k0, ub,
                    uph=uph,
                )
            if adaptive:
                p = sd.ctrl_refresh(
                    p, stats.comp, stats.busy_t, bound, lr=ctrl_lr,
                    iters=ctrl_iters,
                    counts=tuple(int(c) for c in classes.counts) if sparse
                    else None,
                )
            with jax.named_scope(obs.EVAL):
                if not eval_on:
                    ev_val = jnp.float32(0.0)
                elif eval_stride == 1:
                    ev_val = eval_fn(to_tree(ucarry[0]))
                else:
                    # eval fires every eval_stride-th refresh chunk; the
                    # predicate is unbatched under vmap, so the cond stays a
                    # real branch and off-cadence chunks skip the eval work
                    fire = ((k0 // L + 1) % eval_stride) == 0
                    ev_val = jax.lax.cond(
                        fire,
                        lambda u: jnp.asarray(eval_fn(to_tree(u)), jnp.float32),
                        lambda u: jnp.float32(0.0),
                        ucarry[0],
                    )
            ys = (ts, ev_val, p) if collect_extras else (ev_val,)
            if serving_on:
                return (ucarry, sstate, stats, slot_scale, p, sv, sstats), ys
            return (ucarry, sstate, stats, slot_scale, p), ys

        carry = (ucarry, sstate, stats, slot_scale0, p0)
        if serving_on:
            carry = carry + (sv0, sstats0)
        resh = lambda a: a[:Tc].reshape(n_chunks, L)
        xs = (resh(u_race), resh(u_exp), resh(u_disp))
        if sparse:
            xs = xs + (resh(u_mem),)
            if faulty:
                xs = xs + (resh(u_bit),)
        elif scen_on:
            xs = xs + (resh(u_ph),)
        xs = xs + (jnp.arange(n_chunks, dtype=jnp.int32) * L,)
        carry, ys = jax.lax.scan(chunk_step, carry, xs)
        if collect_extras:
            ts, evals, p_traj = ys
            ts = ts.reshape(Tc)
        else:
            (evals,) = ys
        if serving_on:
            ucarry, sstate, stats, slot_scale, p, sv, sstats = carry
        else:
            ucarry, sstate, stats, slot_scale, p = carry
            sv = sstats = None
        if Tc < T:  # tail events past the last chunk boundary
            Kc = sample_dispatch(
                p, u_disp[Tc:], u_mem[Tc:] if sparse else None
            )
            if serving_on:
                (ucarry, sstate, stats, slot_scale, ts_tail, sv,
                 sstats) = advance(
                    ucarry, sstate, stats, slot_scale, p,
                    u_race[Tc:], u_exp[Tc:], Kc, Tc, None, sv, sstats,
                )
            else:
                ucarry, sstate, stats, slot_scale, ts_tail = advance(
                    ucarry, sstate, stats, slot_scale, p,
                    u_race[Tc:], u_exp[Tc:], Kc, Tc,
                    u_bit[Tc:] if sparse and faulty else None,
                    uph=u_ph[Tc:] if scen_on else None,
                )
            if collect_extras:
                ts = jnp.concatenate([ts, ts_tail])
        if eval_on:
            evals = evals[eval_stride - 1 :: eval_stride]
        else:
            evals = jnp.zeros((0,))
        @partial(_scoped, obs.SERVE)
        def _serve_extras():
            return {
                "serve_arrivals": sstats.arrivals,
                "serve_served": sstats.served,
                "serve_shed": sstats.shed,
                "serve_timed_out": sstats.timed_out,
                "serve_retried": sstats.retried,
                "serve_pending": jnp.sum((sv.stt != 0).astype(jnp.int32)),
                "serve_sojourn_sum": sstats.sojourn - sstats.sojourn_c,
                "serve_sojourn_hist": sstats.sojourn_hist,
                "serve_stale_hist": sstats.stale_hist,
                "serve_qdepth_time": sstats.qdepth_tw - sstats.qdepth_tw_c,
                "serve_qdepth_max": sstats.qdepth_max,
                "serve_checksum": sstats.checksum - sstats.checksum_c,
                "serve_kg_step": sv.kg_step,
                "serve_kg_slot": sv.kg_slot,
                "serve_tokens": sv.tokens,
                "serve_t_final": sstate.t,
            }

        with jax.named_scope(obs.RING):
            w_final = to_tree(ucarry[0])
        if not collect_extras:
            extras = {"p_final": p}
            if guard is not None:
                extras["guard_rejects"] = ucarry[3][0]
                extras["stale_drops"] = ucarry[3][1]
            if serving_on:
                extras.update(_serve_extras())
            return w_final, evals, extras
        extras = {
            "t": ts,
            "p_final": p,
            "p_traj": p_traj,
            "occ_mean": stats.occ_sum.astype(jnp.float32) / T,
            "occ_time_avg": stats.occ_tw / ts[-1],
            "busy_time": stats.busy_t,
            "delay_sum": stats.delay_sum,
            "comp": stats.comp,
        }
        if guard is not None:
            extras["guard_rejects"] = ucarry[3][0]
            extras["stale_drops"] = ucarry[3][1]
        if faulty or scen_on:
            extras["kind_count"] = stats.kind_count
            extras["avail_time"] = stats.avail_tw
        if sparse:
            # class-level extras: consumers expand per class via the counts
            extras["class_counts"] = jnp.asarray(classes.counts, jnp.int32)
        if serving_on:
            extras.update(_serve_extras())
        return w_final, evals, extras

    # what no inner scope claims -- the draws, the race's state and
    # statistics, dispatch sampling -- is the race's
    run = _scoped(obs.RACE, run)
    if not wrap_lanes:
        return run

    from jax.sharding import PartitionSpec as P

    mesh = _lane_mesh(lane_devices)

    def run_sharded(w0, mu, p0, key, eta):
        # every operand replicates; only the window gradient batch (inside
        # `run`, via the "lanes" axis) is partitioned across the mesh
        f = jax.shard_map(
            run, mesh=mesh, in_specs=(P(),) * 5, out_specs=P(),
            check_vma=False,
        )
        return f(w0, mu, p0, key, eta)

    return run_sharded


def make_runner(
    grad_fn: Callable[[Any, Pytree, Any], Pytree],
    C: int,
    *,
    stream: str = "host",
    fedbuff_Z: int = 0,
    eval_fn: Callable[[Pytree], Any] | None = None,
    eval_every: int = 0,
    update_fn: Callable[[Pytree, Pytree, Any], Pytree] | None = None,
    unroll: int = 1,
    block_size: int = 1,
    kernel: str = "jnp",
    snapshot_dtype=None,
    lane_devices: int = 1,
    guard: GuardConfig | None = None,
    **device_kw,
):
    """Build the scan engine; ``stream`` selects the event source.

    ``stream="host"`` (default) replays a pre-simulated `EventStream` — the
    parity oracle: ``run(w0, J, slot, scale[, eval_every])``.  With
    ``block_size=E > 1`` it replays `EventBlocks` micro-blocks instead:
    ``run(w0, J, slot, scale, k, mask[, chunk_blocks, n_chunks])`` (see
    `blocked_inputs`); ``kernel`` picks the jnp fallback or the fused
    Pallas prefix-scan kernel, ``snapshot_dtype`` an optional narrower ring
    storage dtype.

    ``stream="device"`` fuses the on-device closed-network generator with
    the update step (zero host pre-simulation): ``run(w0, mu, p0, key, eta)``.
    Requires ``n=`` and ``T=`` (and accepts `make_fused_runner`'s
    ``weighting / adaptive / refresh_every / bound / ctrl_lr / ctrl_iters /
    init / collect_extras`` knobs); ``block_size`` advances E CS steps per
    scan iteration.

    ``lane_devices=D > 1`` (either stream; requires ``block_size`` a >1
    multiple of D) shards each micro-block's E-lane gradient batch across D
    devices — the block axis stays sequential, the lane axis becomes the
    unit of distribution (see `_make_block_step` / `make_fused_runner`).
    """
    if stream == "host":
        if device_kw:
            raise TypeError(f"host stream does not accept {sorted(device_kw)}")
        if block_size > 1:
            if eval_every:
                raise ValueError(
                    "block_size > 1: the eval cadence is encoded in the "
                    "blocked layout — pass chunk_blocks/n_chunks from "
                    "blocked_inputs(..., eval_every=...) at call time "
                    "instead of eval_every"
                )
            return _make_host_block_runner(
                grad_fn, C, block_size, fedbuff_Z=fedbuff_Z, eval_fn=eval_fn,
                update_fn=update_fn, unroll=unroll, kernel=kernel,
                snapshot_dtype=snapshot_dtype, lane_devices=lane_devices,
                guard=guard,
            )
        _check_lane_devices(lane_devices, block_size)  # rejects D>1 at E=1
        return _make_host_runner(
            grad_fn, C, fedbuff_Z=fedbuff_Z, eval_fn=eval_fn,
            eval_every=eval_every, update_fn=update_fn, unroll=unroll,
            snapshot_dtype=snapshot_dtype, guard=guard,
        )
    if stream == "device":
        try:
            n, T = device_kw.pop("n"), device_kw.pop("T")
        except KeyError as e:
            raise TypeError(f"stream='device' requires {e.args[0]}=") from None
        return make_fused_runner(
            grad_fn, n, C, T, fedbuff_Z=fedbuff_Z, eval_fn=eval_fn,
            eval_every=eval_every, update_fn=update_fn, unroll=unroll,
            block_size=block_size, snapshot_dtype=snapshot_dtype,
            lane_devices=lane_devices, guard=guard,
            **device_kw,
        )
    raise ValueError(stream)


# ------------------------------------------------------------------ #
# memoized jitted runners
# ------------------------------------------------------------------ #
def _runner_cache(grad_fn):
    """Per-owner memo for jitted runners.

    `make_runner` builds a fresh closure per call, which would defeat
    `jax.jit`'s compilation cache, so the jitted runner is memoized on the
    object owning `grad_fn` (its `__self__` for bound methods like
    `source.device_grad`, else the function's own `__dict__`): repeated runs
    with the same source reuse the compiled executable, and the memo — a
    plain attribute forming an internal reference cycle — is garbage
    collected together with the source instead of pinning device shards and
    executables in a process-global cache.
    """
    owner = getattr(grad_fn, "__self__", grad_fn)
    func = getattr(grad_fn, "__func__", grad_fn)
    try:
        cache = owner.__dict__.setdefault("_scan_runner_cache", {})
    except AttributeError:  # no instance dict (slots/builtin): skip memoization
        cache = {}
    return cache, func


def jit_runner(
    grad_fn,
    C: int,
    fedbuff_Z: int = 0,
    eval_fn=None,
    eval_every: int = 0,
    update_fn=None,
    unroll: int = 1,
    vmap_streams: bool = False,
    block_size: int = 1,
    kernel: str = "jnp",
    snapshot_dtype=None,
    donate: bool = False,
    lane_devices: int = 1,
    guard: GuardConfig | None = None,
):
    """Jitted, memoized host-replay runner.

    The memo key deliberately excludes ``eval_every``: the cadence is a
    static *call-time* argument of the jitted function (``static_argnames``),
    so sweeps over eval cadence reuse one runner object and share
    `jax.jit`'s compilation cache instead of rebuilding the closure (and
    with it the whole trace) per cadence.  ``vmap_streams=True`` returns the
    batched variant mapping over stacked (J, slot, scale).

    ``block_size=E > 1`` returns the blocked runner (`blocked_inputs`
    arrays; the eval layout ``chunk_blocks``/``n_chunks`` are its call-time
    statics).  ``lane_devices=D > 1`` lane-shards the blocked runner's E
    gradient lanes across D devices (requires E a multiple of D; composes
    with ``vmap_streams`` into the scenario × lane layout).  ``donate=True``
    donates the per-run event-stream buffers to the compiled program
    (callers passing freshly built arrays — the `_run_scan` / `run_matrix`
    drivers — save one device-side copy of the stream; don't enable it when
    re-calling with the same arrays).
    """
    import jax

    cache, func = _runner_cache(grad_fn)
    key = (
        "host", func, C, fedbuff_Z, eval_fn, update_fn, unroll, vmap_streams,
        block_size, kernel, snapshot_dtype, donate, lane_devices,
        None if guard is None else guard.cache_key(),
    )
    if block_size > 1 and eval_every:
        raise ValueError(
            "block_size > 1: the eval cadence is encoded in the blocked "
            "layout — pass chunk_blocks/n_chunks from blocked_inputs(..., "
            "eval_every=...) at call time instead of eval_every"
        )
    _check_lane_devices(lane_devices, block_size)
    if key in cache:
        jitted = cache[key]
        return jitted if block_size > 1 else partial(jitted, eval_every=eval_every)
    obs.counters["runner_builds"] += 1
    if block_size > 1:
        # the factory owns the scenario vmap and (if any) the lane shard_map
        run = _make_host_block_runner(
            grad_fn, C, block_size, fedbuff_Z=fedbuff_Z, eval_fn=eval_fn,
            update_fn=update_fn, unroll=unroll, kernel=kernel,
            snapshot_dtype=snapshot_dtype, lane_devices=lane_devices,
            vmap_streams=vmap_streams, guard=guard,
        )
        cache[key] = jax.jit(
            run,
            static_argnames=("chunk_blocks", "n_chunks"),
            donate_argnums=(1, 2, 3, 4, 5) if donate else (),
        )
        return cache[key]
    base = _make_host_runner(
        grad_fn, C, fedbuff_Z=fedbuff_Z, eval_fn=eval_fn, eval_every=0,
        update_fn=update_fn, unroll=unroll, snapshot_dtype=snapshot_dtype,
        guard=guard,
    )
    if vmap_streams:
        def run(w0, J, slot, scale, eval_every=0):
            return jax.vmap(
                lambda w, a, b, c: base(w, a, b, c, eval_every),
                in_axes=(None, 0, 0, 0),
            )(w0, J, slot, scale)
    else:
        run = base
    cache[key] = jax.jit(
        run,
        static_argnames=("eval_every",),
        donate_argnums=(1, 2, 3) if donate else (),
    )
    return partial(cache[key], eval_every=eval_every)


def jit_fused_runner(
    grad_fn,
    n: int,
    C: int,
    T: int,
    *,
    vmap_scenarios: bool = False,
    shard_devices: int = 1,
    lane_devices: int = 1,
    **kw,
):
    """Jitted, memoized fused (device-stream) runner.

    Memoized on the gradient source like `jit_runner`; ``vmap_scenarios``
    maps over stacked (mu, p0, key) with shared (w0, eta) — the zero-host-
    presimulation scenario matrix.  ``shard_devices > 1`` additionally
    `pmap`s the batched runner over that many devices (inputs carry an extra
    leading device axis) — the scenario matrix then runs data-parallel
    across the host platform's cores/accelerators, which the serial
    host-export path cannot.

    ``lane_devices > 1`` lane-shards each micro-block's gradient batch
    (requires ``block_size`` a >1 multiple of it).  Combined with
    ``vmap_scenarios`` it builds one `shard_map` over a scenario × lane 2-D
    mesh — ``shard_devices`` scenario shards on the first axis,
    ``lane_devices`` lane shards on the second — and unlike the pmap path
    the caller passes flat ``(B, ...)`` scenario batches (B divisible by
    ``shard_devices``); no extra leading device axis.

    Extra keywords (``block_size``, ``collect_extras``, ``snapshot_dtype``,
    ...) forward to `make_fused_runner` and participate in the memo key.
    """
    import jax

    cache, func = _runner_cache(grad_fn)

    def _kw_entry(k, v):
        if k == "bound":
            return (k, None if v is None else (v.A, v.L, v.B, v.C, v.T, v.rho))
        if k in ("fault", "guard", "serving", "scenario"):
            return (k, None if v is None else v.cache_key())
        if k == "classes":
            return (k, None if v is None else v.cache_key())
        return (k, v)

    kw_key = tuple(_kw_entry(k, v) for k, v in sorted(kw.items()))
    key = (
        "device", func, n, C, T, vmap_scenarios, shard_devices, lane_devices,
        kw_key,
    )
    if key not in cache:
        obs.counters["runner_builds"] += 1
        if lane_devices > 1 and vmap_scenarios:
            from jax.sharding import Mesh, PartitionSpec as P

            n_dev = shard_devices * lane_devices
            avail = jax.device_count()
            if n_dev > avail:
                raise ValueError(
                    f"scenario × lane mesh needs {n_dev} devices, "
                    f"{avail} visible"
                )
            run = make_fused_runner(
                grad_fn, n, C, T, lane_devices=lane_devices,
                lane_axis="lanes", **kw,
            )
            batched = jax.vmap(run, in_axes=(None, 0, 0, 0, None))
            mesh = Mesh(
                np.array(jax.devices()[:n_dev]).reshape(
                    shard_devices, lane_devices
                ),
                ("scen", "lanes"),
            )
            sharded = jax.shard_map(
                batched,
                mesh=mesh,
                in_specs=(P(), P("scen"), P("scen"), P("scen"), P()),
                out_specs=P("scen"),
                check_vma=False,
            )
            cache[key] = jax.jit(sharded)
        elif lane_devices > 1:
            run = make_fused_runner(
                grad_fn, n, C, T, lane_devices=lane_devices, **kw
            )
            cache[key] = jax.jit(run)
        else:
            run = make_fused_runner(grad_fn, n, C, T, **kw)
            if vmap_scenarios:
                batched = jax.vmap(run, in_axes=(None, 0, 0, 0, None))
                if shard_devices > 1:
                    cache[key] = jax.pmap(batched, in_axes=(None, 0, 0, 0, None))
                else:
                    cache[key] = jax.jit(batched)
            else:
                cache[key] = jax.jit(run)
    return cache[key]
