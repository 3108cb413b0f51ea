"""JAX-native closed-Jackson-network simulator + adaptive sampling control.

`queue_sim.ClosedNetworkSim` is the host-side oracle: exact, per-event
Python.  This module is the same closed network as a *device-resident* scan
step, so the event stream can be generated inside the compiled training
program (`engine_scan.make_runner(stream="device")`) instead of being
pre-simulated on the host and replayed:

  * `StreamState` carries per-node queue occupancy, fixed-shape ``(n, C)``
    FIFO ring buffers of slot ids and per-node head/tail counters — the
    device analogue of the host simulator's deques;
  * `stream_step` advances one CS step: the exponential completion race is
    sampled by inverse-CDF over the busy-rate vector (the same distribution
    as ``categorical(mu * busy_mask)``, but driven by pre-drawn uniform
    blocks — per-step Gumbel/threefry sampling costs ~10x more on CPU), the
    dispatch draw comes from ``p`` the same way, and the step emits the same
    ``(J, K, t, slot)`` tuple `queue_sim.EventStream` carries;
  * `StatsState`/`stats_step` accumulate running occupancy, busy time,
    completion counts and FIFO delays on device — the observables the
    adaptive control loop (and the parity tests) consume;
  * the control-plane section ports the exact Jackson analysis to ``jnp``:
    `mva_throughput_delays` (Mean Value Analysis — mathematically identical
    to Buzen-based `jackson.JacksonNetwork.expected_delays`, but a C-length
    scan of O(n) vectorized ops), `optimal_eta_jnp` (cubic stationary point
    by guarded Newton + the Theorem-1 cap) and `make_bound_value_and_grad`
    (the Theorem-1 objective G(p, eta*(p)) with exact simplex gradients via
    AD through the MVA recurrence — the `jnp` port of
    `sampling.bound_value_and_grad`);
  * `ctrl_refresh` is one control-loop update: re-estimate per-node service
    rates from observed (completions, busy time), then take a few
    exponentiated-gradient steps on the bound — `sampling.optimize_general`
    running *inside* the compiled program on *measured* rates.

The race relies on memorylessness, which is less restrictive than it
sounds: phase-type service (Erlang-k / hyperexponential chains of
exponential stages) and Markov-modulated on/off availability are both
memoryless at every instant, and `scenario_stream_step` /
`sparse_scenario_stream_step` fold them into the same inverse-CDF race
(see `core.scenario` for the law definitions).  Only ``service="det"``
stays host-only.  The stream is deterministic given the PRNG key but
does **not** reproduce the host simulator's realization — law-level
parity is locked in tests/test_stream_device.py and
tests/test_scenarios.py (chi-square, Little's law, delay means).
"""
from __future__ import annotations

from functools import lru_cache
from typing import Any, NamedTuple

import numpy as np

from .. import obs
from .queue_sim import (
    KIND_COMPLETE,
    KIND_CRASH,
    KIND_FLIP,
    KIND_SERVE,
    KIND_STAGE,
    KIND_TIMEOUT,
    N_KINDS,
    EventBlocks,
    EventStream,
    FaultConfig,
)
from .scenario import ModulationConfig, ScenarioConfig
from .theory import BoundConstants

__all__ = [
    "StreamState",
    "StatsState",
    "Event",
    "stream_init",
    "stream_step",
    "fault_stream_step",
    "merged_stream_step",
    "resolve_fault_rates",
    "ScenarioRates",
    "resolve_scenario",
    "resolve_scenario_classes",
    "scenario_stream_init",
    "scenario_stream_step",
    "scenario_stats_step",
    "stats_init",
    "stats_step",
    "fault_stats_step",
    "stats_stream_fn",
    "generate_stream",
    "generate_blocks",
    "tree_build",
    "tree_sample",
    "tree_update",
    "kahan_add",
    "kahan_value",
    "ClassSpec",
    "build_class_spec",
    "resolve_fault_rates_classes",
    "SparseStreamState",
    "sparse_stream_init",
    "sparse_stream_step",
    "sparse_fault_stream_step",
    "sparse_scenario_stream_init",
    "sparse_scenario_stream_step",
    "sparse_scenario_class_stats",
    "sparse_stats_init",
    "sparse_stats_step",
    "sparse_fault_stats_step",
    "sparse_stats_stream_fn",
    "class_occupancy",
    "sample_dispatch_classes",
    "mva_throughput_delays",
    "optimal_eta_jnp",
    "generalized_bound_jnp",
    "make_bound_value_and_grad",
    "ctrl_refresh",
    "estimate_mu",
]


# ---------------------------------------------------------------------- #
# segment-tree CDF sampler: hierarchical sums, descent never lands on a
# zero-weight leaf — the unbiased replacement for cumsum+searchsorted
# ---------------------------------------------------------------------- #
def _next_pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length() if n > 1 else 1


def tree_build(w):
    """Flattened-heap sum tree over the weight vector ``w``.

    Returns a ``(2N,)`` array (N = next power of two >= len(w)) with
    ``tree[1]`` the root total, children of node i at ``2i`` / ``2i+1``
    and the (zero-padded) leaves at ``tree[N:]``.  The root is a pairwise
    (hierarchical) sum, so its rounding error is O(log n) ulps — the
    float32 ``cumsum`` it replaces accumulates O(n) ulps, which visibly
    biases the inverse-CDF race at n >= 1e5.
    """
    import jax.numpy as jnp

    w = jnp.asarray(w)
    n = w.shape[0]
    N = _next_pow2(n)
    level = jnp.zeros(N, w.dtype).at[:n].set(w) if N != n else w
    levels = [level]
    while levels[-1].shape[0] > 1:
        lv = levels[-1]
        levels.append(lv.reshape(-1, 2).sum(axis=1))
    return jnp.concatenate([jnp.zeros(1, w.dtype)] + levels[::-1])


def tree_sample(tree, u):
    """Inverse-CDF draw from a `tree_build` tree; returns a leaf index.

    Descends ``x = u * total`` through the heap: go right iff the left
    mass is exhausted *and* the right subtree has positive mass.  A leaf
    with zero weight is therefore never selected — the clamped
    ``searchsorted`` this replaces rounds ``u * total`` past the last
    cumsum entry and over-selects the final (possibly idle, rate-0)
    index; the descent re-routes that mass to the last positive leaf,
    which is exactly the conditional law given the rounding event.
    """
    import jax.numpy as jnp

    N = tree.shape[0] // 2
    depth = max(N.bit_length() - 1, 0)
    x = u * tree[1]
    idx = jnp.int32(1)
    for _ in range(depth):
        left = tree[2 * idx]
        right = tree[2 * idx + 1]
        go_right = (x >= left) & (right > 0)
        x = jnp.where(go_right, x - left, x)
        idx = 2 * idx + go_right.astype(jnp.int32)
    return idx - N


def tree_update(tree, idx, value):
    """Set leaf ``idx`` to ``value`` and refresh its root path (O(log n))."""
    import jax.numpy as jnp

    N = tree.shape[0] // 2
    depth = max(N.bit_length() - 1, 0)
    pos = jnp.asarray(idx, jnp.int32) + N
    delta = value - tree[pos]
    path = jnp.stack([pos >> d for d in range(depth + 1)])
    return tree.at[path].add(delta)


# ---------------------------------------------------------------------- #
# Kahan-compensated accumulation: float32 time integrals stall once the
# accumulator passes ~2^24 (increments round to zero); a compensated
# (sum, c) pair keeps relative O(eps) accuracy on any backend, x64 or not
# ---------------------------------------------------------------------- #
def kahan_add(s, c, x):
    """One compensated add: returns the new ``(s, c)`` pair.

    The represented total is ``s - c``; ``s`` alone is the correctly
    rounded float32 running sum (safe to read in traced code).
    """
    y = x - c
    t = s + y
    return t, (t - s) - y


def kahan_value(s, c):
    """Host-side exact readout of a compensated pair (float64)."""
    return np.asarray(s, np.float64) - np.asarray(c, np.float64)


def _kahan_scatter_add(s, c, idx, x):
    """Compensated ``s.at[idx].add(x)`` for a single dynamic index."""
    sj, cj = s[idx], c[idx]
    y = x - cj
    t = sj + y
    return s.at[idx].set(t), c.at[idx].set((t - sj) - y)


class StreamState(NamedTuple):
    """Device state of the closed network (one scenario)."""

    occ: Any    # (n,) int32 — queue length per node (X_i)
    ring: Any   # (n, C) int32 — FIFO ring buffer of slot ids per node
    head: Any   # (n,) int32 — pop counter per node (ring index = head % C)
    tail: Any   # (n,) int32 — push counter per node
    t: Any      # () float32 — physical time (Kahan sum; see t_c)
    avail: Any = None  # (n,) float32 0/1 availability (fault mode; else None)
    t_c: Any = 0.0     # () float32 — Kahan compensation of t
    phase: Any = None  # (C,) int32 — service stage of each slot's task
                       # (scenario mode; else None)


class Event(NamedTuple):
    """One CS step, as emitted by `stream_step` (matches EventStream columns)."""

    j: Any      # completing client J_k
    k: Any      # newly sampled client K_{k+1}
    t: Any      # physical completion time
    slot: Any   # ring-buffer slot of the completing task (freed & reused)
    dt: Any     # time since the previous CS step
    kind: Any = KIND_COMPLETE  # KIND_* tag; constant 0 on fault-free streams


class StatsState(NamedTuple):
    """Running observables accumulated on device (one scenario).

    All float accumulators are Kahan pairs (``x`` plus compensation
    ``x_c``; host readout via `kahan_value`) — plain float32 integrals
    stall once they pass ~2^24, corrupting `estimate_mu`/`ctrl_refresh`
    on T ~ 1e8 runs.  In sparse (class-collapsed) mode the per-node
    vectors become per-class ``(m,)`` vectors; field names are shared so
    every consumer reads both layouts.
    """

    occ_sum: Any    # (n,) int32 — sum over steps of post-step X_{i,k} (Palm)
    occ_tw: Any     # (n,) float32 — time-weighted integral of X_i(t)
    busy_t: Any     # (n,) float32 — integral of 1{X_i > 0} dt (fault mode:
                    # gated on availability, so mu MLEs stay unbiased)
    comp: Any       # (n,) int32 — completions per node
    delay_sum: Any  # (n,) float32 — sum of CS-step delays per node
    slot_step: Any  # (C,) int32 — dispatch step of the task in each slot
    avail_tw: Any = None    # (n,) float32 — integral of availability (faults)
    kind_count: Any = None  # (4,) int32 — events per KIND_* tag (faults)
    occ_tw_c: Any = 0.0     # Kahan compensations of the float integrals
    busy_t_c: Any = 0.0
    delay_sum_c: Any = 0.0
    avail_tw_c: Any = None


def stream_init(key, n: int, C: int, p, init: str = "distinct",
                fault: bool = False):
    """Initial placement of the C tasks.  Returns (state, init_nodes).

    ``"distinct"`` places the tasks on C distinct clients (uniform random
    subset; round-robin when C > n), ``"sampled"`` draws C iid clients from
    ``p`` — the same two conventions as `queue_sim.SimConfig.initial`.
    """
    import jax
    import jax.numpy as jnp

    if init == "distinct":
        if C <= n:
            nodes = jax.random.permutation(key, n)[:C].astype(jnp.int32)
        else:
            nodes = (jnp.arange(C, dtype=jnp.int32) % n)
    elif init == "sampled":
        ptree = tree_build(jnp.asarray(p, jnp.float32))
        u = jax.random.uniform(key, (C,))
        nodes = jax.vmap(lambda uu: tree_sample(ptree, uu))(u).astype(jnp.int32)
    else:
        raise ValueError(init)
    # FIFO position of task s at its node = number of earlier tasks there
    eq = nodes[None, :] == nodes[:, None]
    pos = jnp.sum(jnp.tril(eq, -1), axis=1).astype(jnp.int32)
    occ = jnp.zeros(n, jnp.int32).at[nodes].add(1)
    ring = jnp.zeros((n, C), jnp.int32).at[nodes, pos].set(
        jnp.arange(C, dtype=jnp.int32)
    )
    state = StreamState(
        occ=occ,
        ring=ring,
        head=jnp.zeros(n, jnp.int32),
        tail=occ,
        t=jnp.float32(0.0),
        avail=jnp.ones(n, jnp.float32) if fault else None,
    )
    return state, nodes


def stream_step(state: StreamState, mu, xs) -> tuple[StreamState, Event]:
    """One CS step of the closed network.

    ``xs = (u_race, u_exp, k_new)``: two pre-drawn uniforms (completion race
    and holding time) plus the pre-sampled dispatch target K_{k+1} ~ p.  With
    exponential service the network is a CTMC, so given the occupancy the
    next completion is at node j w.p. mu_j 1{X_j>0} / sum(...) after an
    Exp(sum) holding time — no per-node residual clocks needed.  The race is
    sampled by segment-tree descent (`tree_build`/`tree_sample`): pairwise
    sums keep the CDF unbiased at large n and the descent never selects an
    idle (rate-0) node, unlike the clamped float32 cumsum+searchsorted.
    """
    import jax.numpy as jnp

    u_race, u_exp, k_new = xs
    occ, ring, head, tail, t = (
        state.occ, state.ring, state.head, state.tail, state.t,
    )
    n, C = ring.shape
    rates = jnp.where(occ > 0, mu, 0.0)
    rtree = tree_build(rates)
    tot = rtree[1]
    dt = -jnp.log1p(-u_exp) / tot
    t, t_c = kahan_add(t, state.t_c, dt)
    j = tree_sample(rtree, u_race).astype(jnp.int32)
    # pop the oldest in-flight task at j; its freed slot hosts the dispatch
    s = ring[j, head[j] % C]
    head = head.at[j].add(1)
    occ = occ.at[j].add(-1)
    ring = ring.at[k_new, tail[k_new] % C].set(s)
    tail = tail.at[k_new].add(1)
    occ = occ.at[k_new].add(1)
    return (
        StreamState(occ=occ, ring=ring, head=head, tail=tail, t=t, t_c=t_c),
        Event(j=j, k=k_new, t=t, slot=s, dt=dt),
    )


def resolve_fault_rates(fault: FaultConfig, n: int):
    """`FaultConfig` -> jnp ``(kappa, theta, q_off, q_on)`` float32 arrays,
    the operand order `fault_stream_step` races over."""
    import jax.numpy as jnp

    q_off, q_on, kappa, theta = fault.resolve(n)
    return (jnp.asarray(kappa, jnp.float32), jnp.asarray(theta, jnp.float32),
            jnp.asarray(q_off, jnp.float32), jnp.asarray(q_on, jnp.float32))


def fault_stream_step(state: StreamState, mu, fr, xs):
    """One merged-CTMC event of the faulty closed network.

    Identical machinery to `stream_step`, but the inverse-CDF race runs over
    ``4n`` competing exponential clocks instead of ``n``:

      ``[ mu_i a_i 1{X_i>0} | kappa_i a_i 1{X_i>0} | theta_i 1{X_i>0} |
         q_off_i a_i + q_on_i (1 - a_i) ]``

    — completions, crashes, straggler timeouts (deadlines are server-side,
    so they fire even while the node is off) and availability flips, with
    ``a`` the 0/1 availability vector.  Everything stays memoryless, so one
    pre-drawn uniform pair per step still suffices.  The winning index
    decodes as ``kind = idx // n``, ``node = idx % n``.

    Task movements (kind < 3) pop the head-of-line slot at ``node`` and
    re-dispatch it at the pre-sampled ``k_new``; flips toggle availability,
    emit ``slot = C`` (the trash row — every consumer scatter drops it) and
    leave the queues untouched.  ``fr = resolve_fault_rates(...)``.
    """
    import jax.numpy as jnp

    kappa, theta, q_off, q_on = fr
    u_race, u_exp, k_new = xs
    occ, ring, head, tail, t, avail = (
        state.occ, state.ring, state.head, state.tail, state.t, state.avail,
    )
    n, C = ring.shape
    busy = occ > 0
    r_comp = jnp.where(busy, mu * avail, 0.0)
    r_crash = jnp.where(busy, kappa * avail, 0.0)
    r_tmo = jnp.where(busy, theta, 0.0)
    r_flip = jnp.where(avail > 0, q_off, q_on)
    rates = jnp.concatenate([r_comp, r_crash, r_tmo, r_flip])
    rtree = tree_build(rates)
    tot = jnp.maximum(rtree[1], 1e-30)  # all-off + no clocks: time still moves
    dt = -jnp.log1p(-u_exp) / tot
    t, t_c = kahan_add(t, state.t_c, dt)
    idx = tree_sample(rtree, u_race).astype(jnp.int32)
    kind = idx // n
    j = idx % n
    move = kind < KIND_FLIP
    # pop the oldest in-flight task at j (no-op on flips: slot -> trash C,
    # head/occ increments masked, push target row n is dropped out-of-bounds)
    s = jnp.where(move, ring[j, head[j] % C], C).astype(jnp.int32)
    mv = move.astype(jnp.int32)
    head = head.at[j].add(mv)
    occ = occ.at[j].add(-mv)
    push_row = jnp.where(move, k_new, n)
    ring = ring.at[push_row, tail[k_new] % C].set(s, mode="drop")
    tail = tail.at[k_new].add(mv)
    occ = occ.at[k_new].add(mv)
    flip = (kind == KIND_FLIP).astype(jnp.float32)
    avail = avail.at[j].add(flip * (1.0 - 2.0 * avail[j]))
    return (
        StreamState(occ=occ, ring=ring, head=head, tail=tail, t=t,
                    avail=avail, t_c=t_c),
        Event(j=j, k=k_new, t=t, slot=s, dt=dt, kind=kind),
    )


def merged_stream_step(state: StreamState, mu, ext_rate, xs, fr=None):
    """One event of the closed network merged with an external event class.

    ``ext_rate`` is the total rate of an independent open stream (the
    serving plane, `serving.serve_total_rate`) racing against the closed
    network's clocks.  With all clocks exponential the merged system is a
    CTMC: the holding time is ``Exp(r_train + ext_rate)`` and the winner is
    external w.p. ``ext_rate / (r_train + ext_rate)`` — one pre-drawn
    uniform pair still suffices, and the *conditional* uniforms handed to
    each side (``x / r_train`` resp. ``(x - r_train) / ext_rate`` with
    ``x = u_race * tot``) are again uniform, so both sub-races stay exact
    in law.

    External wins leave the queues untouched and emit ``Event(j=n,
    slot=C, kind=KIND_SERVE)`` — the KIND_FLIP masking pattern: training-
    side gathers clamp, scatters drop.  ``fr`` switches the closed side to
    the faulty 4n-clock race of `fault_stream_step`.  Returns
    ``(state', ev, is_ext, u_ext)`` where ``u_ext`` is the external side's
    conditional uniform (garbage unless ``is_ext``).
    """
    import jax.numpy as jnp

    u_race, u_exp, k_new = xs
    occ, ring, head, tail, t, avail = (
        state.occ, state.ring, state.head, state.tail, state.t, state.avail,
    )
    n, C = ring.shape
    faulty = fr is not None
    if faulty:
        kappa, theta, q_off, q_on = fr
        busy = occ > 0
        rates = jnp.concatenate([
            jnp.where(busy, mu * avail, 0.0),
            jnp.where(busy, kappa * avail, 0.0),
            jnp.where(busy, theta, 0.0),
            jnp.where(avail > 0, q_off, q_on),
        ])
    else:
        rates = jnp.where(occ > 0, mu, 0.0)
    rtree = tree_build(rates)
    r_train = jnp.maximum(rtree[1], 1e-30)
    ext = jnp.asarray(ext_rate, jnp.float32)
    tot = r_train + ext
    dt = -jnp.log1p(-u_exp) / tot
    t, t_c = kahan_add(t, state.t_c, dt)
    x = u_race * tot
    is_ext = x >= r_train
    # conditional uniforms: exact given the branch (clipped only against
    # the open boundary so the tree descent stays in range)
    u_train = jnp.clip(x / r_train, 0.0, 1.0 - 1e-7)
    u_ext = jnp.clip((x - r_train) / jnp.maximum(ext, 1e-30),
                     0.0, 1.0 - 1e-7)
    idx = tree_sample(rtree, u_train).astype(jnp.int32)
    if faulty:
        kind = jnp.where(is_ext, KIND_SERVE, idx // n)
        j = idx % n
    else:
        kind = jnp.where(is_ext, KIND_SERVE, KIND_COMPLETE)
        j = idx
    j = jnp.where(is_ext, n, j).astype(jnp.int32)
    move = kind < KIND_FLIP  # excludes flips AND external events
    s = jnp.where(move, ring[j % n, head[j % n] % C], C).astype(jnp.int32)
    mv = move.astype(jnp.int32)
    head = head.at[j].add(mv, mode="drop")
    occ = occ.at[j].add(-mv, mode="drop")
    push_row = jnp.where(move, k_new, n)
    ring = ring.at[push_row, tail[k_new] % C].set(s, mode="drop")
    tail = tail.at[k_new].add(mv)
    occ = occ.at[k_new].add(mv)
    if faulty:
        flip = ((kind == KIND_FLIP) & ~is_ext).astype(jnp.float32)
        avail = avail.at[j].add(flip * (1.0 - 2.0 * avail[j % n]),
                                mode="drop")
    return (
        StreamState(occ=occ, ring=ring, head=head, tail=tail, t=t,
                    avail=avail, t_c=t_c),
        Event(j=j, k=k_new, t=t, slot=s, dt=dt, kind=kind),
        is_ext,
        u_ext,
    )


class ScenarioRates(NamedTuple):
    """Device-resident tables of a resolved `ScenarioConfig`.

    ``acdf`` is the cumulative initial-stage distribution (inverse-CDF
    phase draws), ``srate``/``absorb``/``nxt`` the unit-mean stage chain
    of `ServiceLaw.chain`, ``q_off``/``q_on`` the per-node (dense) or
    per-class (sparse) modulation intensities and ``rate_scale`` the
    degraded-service multiplier while off.
    """

    acdf: Any        # (S,) float32 — cumsum of alpha, tail pinned to 1
    srate: Any       # (S,) float32 — stage clock rates (unit-mean chain)
    absorb: Any      # (S,) float32 — 1.0 where firing completes service
    nxt: Any         # (S,) int32 — successor stage otherwise
    q_off: Any       # (n,)/(m,) float32 — on->off flip intensity
    q_on: Any        # (n,)/(m,) float32 — off->on flip intensity
    rate_scale: Any  # () float32 — service-speed multiplier while off


def _scenario_tables(scenario: ScenarioConfig):
    alpha, srate, absorb, nxt = scenario.service.chain()
    acdf = np.cumsum(alpha)
    acdf[-1] = max(acdf[-1], 1.0)  # guard fp undershoot at the tail
    mod = scenario.modulation if scenario.modulation is not None else ModulationConfig()
    return acdf, srate, absorb, nxt, mod


def resolve_scenario(scenario: ScenarioConfig, n: int) -> ScenarioRates:
    """`ScenarioConfig` -> dense device `ScenarioRates` ((n,) modulation)."""
    import jax.numpy as jnp

    acdf, srate, absorb, nxt, mod = _scenario_tables(scenario)
    q_off, q_on = mod.resolve(n)
    return ScenarioRates(
        acdf=jnp.asarray(acdf, jnp.float32),
        srate=jnp.asarray(srate, jnp.float32),
        absorb=jnp.asarray(absorb, jnp.float32),
        nxt=jnp.asarray(nxt, jnp.int32),
        q_off=jnp.asarray(q_off, jnp.float32),
        q_on=jnp.asarray(q_on, jnp.float32),
        rate_scale=jnp.float32(mod.rate_scale),
    )


def resolve_scenario_classes(scenario: ScenarioConfig, spec: "ClassSpec") -> ScenarioRates:
    """Class-level `resolve_scenario`: modulation rates as ``(m,)`` arrays.

    Modulation must be constant within each speed class — the
    exchangeability the sparse idle pools rely on (same constraint as
    `resolve_fault_rates_classes`)."""
    import jax.numpy as jnp

    acdf, srate, absorb, nxt, mod = _scenario_tables(scenario)
    q_off, q_on = mod.resolve(spec.n)
    perm = np.asarray(spec.perm)
    offsets = np.asarray(spec.offsets)
    counts = np.asarray(spec.counts)
    out = []
    for name, r in (("off_rate", q_off), ("on_rate", q_on)):
        rc = np.asarray(r, np.float64)[perm]
        vals = rc[offsets]
        for c in range(counts.size):
            seg = rc[offsets[c]: offsets[c] + counts[c]]
            if not np.allclose(seg, vals[c]):
                raise ValueError(
                    f"ModulationConfig.{name} varies within speed class {c}; "
                    "the sparse stream requires class-constant modulation"
                )
        out.append(jnp.asarray(vals, jnp.float32))
    return ScenarioRates(
        acdf=jnp.asarray(acdf, jnp.float32),
        srate=jnp.asarray(srate, jnp.float32),
        absorb=jnp.asarray(absorb, jnp.float32),
        nxt=jnp.asarray(nxt, jnp.int32),
        q_off=out[0],
        q_on=out[1],
        rate_scale=jnp.float32(mod.rate_scale),
    )


def _phase_draw(acdf, u):
    """Inverse-CDF initial-stage draw from the (S,) cumulative alpha."""
    import jax.numpy as jnp

    S = acdf.shape[0]
    return jnp.minimum(
        jnp.searchsorted(acdf, u, side="right"), S - 1
    ).astype(jnp.int32)


def scenario_stream_init(key, n: int, C: int, p, sr: ScenarioRates,
                         init: str = "distinct"):
    """`stream_init` + per-slot initial phase draws.  Returns (state, nodes).

    The phase of each task is drawn at dispatch (here: at initial
    placement) — independence of the stage sequence from the queue
    process makes this law-identical to drawing at service start, and it
    matches the host oracle's convention.
    """
    import jax

    k_place, k_ph = jax.random.split(key)
    state, nodes = stream_init(k_place, n, C, p, init=init, fault=True)
    phase = _phase_draw(sr.acdf, jax.random.uniform(k_ph, (C,)))
    return state._replace(phase=phase), nodes


def scenario_stream_step(state: StreamState, mu, sr: ScenarioRates, xs):
    """One merged-CTMC event of the scenario closed network.

    The race runs over ``2n`` clocks:

      ``[ mu_i srate[phase_i] speed_i 1{X_i>0} | q_off_i a_i + q_on_i (1-a_i) ]``

    where ``phase_i`` is the stage of node i's head-of-line task and
    ``speed_i = a_i + (1-a_i) rate_scale`` the modulated service speed.
    A serve-clock win is decoded by ``absorb[phase]`` into either a task
    completion (KIND_COMPLETE — pop + re-dispatch exactly as in
    `fault_stream_step`) or a stage advance (KIND_STAGE — the head task
    steps to ``nxt[phase]``; no queue change, ``slot = C``).  Flips
    toggle availability like fault flips.  ``xs = (u_race, u_exp, k_new,
    u_ph)`` — one extra pre-drawn uniform feeds the dispatch-time phase
    draw of the re-dispatched task.
    """
    import jax.numpy as jnp

    u_race, u_exp, k_new, u_ph = xs
    occ, ring, head, tail, t, avail, phase = (
        state.occ, state.ring, state.head, state.tail, state.t,
        state.avail, state.phase,
    )
    n, C = ring.shape
    busy = occ > 0
    speed = avail + (1.0 - avail) * sr.rate_scale
    head_slots = ring[jnp.arange(n), head % C]
    r_serve = jnp.where(busy, mu * sr.srate[phase[head_slots]] * speed, 0.0)
    r_flip = jnp.where(avail > 0, sr.q_off, sr.q_on)
    rates = jnp.concatenate([r_serve, r_flip])
    rtree = tree_build(rates)
    tot = jnp.maximum(rtree[1], 1e-30)  # all-suspended: time still moves
    dt = -jnp.log1p(-u_exp) / tot
    t, t_c = kahan_add(t, state.t_c, dt)
    idx = tree_sample(rtree, u_race).astype(jnp.int32)
    is_serve = idx < n
    j = idx % n
    s_head = ring[j, head[j] % C]
    ph_j = phase[s_head]
    complete = is_serve & (sr.absorb[ph_j] > 0)
    kind = jnp.where(
        complete, KIND_COMPLETE, jnp.where(is_serve, KIND_STAGE, KIND_FLIP)
    ).astype(jnp.int32)
    # completions pop + re-dispatch; stages and flips emit slot = C (trash)
    move = complete
    s = jnp.where(move, s_head, C).astype(jnp.int32)
    mv = move.astype(jnp.int32)
    head = head.at[j].add(mv)
    occ = occ.at[j].add(-mv)
    push_row = jnp.where(move, k_new, n)
    ring = ring.at[push_row, tail[k_new] % C].set(s, mode="drop")
    tail = tail.at[k_new].add(mv)
    occ = occ.at[k_new].add(mv)
    # phase update: a completion's freed slot hosts the dispatched task
    # (fresh alpha draw); a stage advance steps the head task to nxt;
    # flips write to the trash index C (dropped)
    ph_w = jnp.where(complete, _phase_draw(sr.acdf, u_ph), sr.nxt[ph_j])
    phase = phase.at[jnp.where(is_serve, s_head, C)].set(
        ph_w.astype(jnp.int32), mode="drop"
    )
    flip = (kind == KIND_FLIP).astype(jnp.float32)
    avail = avail.at[j].add(flip * (1.0 - 2.0 * avail[j]))
    return (
        StreamState(occ=occ, ring=ring, head=head, tail=tail, t=t,
                    avail=avail, t_c=t_c, phase=phase),
        Event(j=j, k=k_new, t=t, slot=s, dt=dt, kind=kind),
    )


def stats_init(n: int, C: int, fault: bool = False,
               scenario: bool = False) -> StatsState:
    import jax.numpy as jnp

    tagged = fault or scenario
    return StatsState(
        occ_sum=jnp.zeros(n, jnp.int32),
        occ_tw=jnp.zeros(n, jnp.float32),
        busy_t=jnp.zeros(n, jnp.float32),
        comp=jnp.zeros(n, jnp.int32),
        delay_sum=jnp.zeros(n, jnp.float32),
        slot_step=jnp.zeros(C, jnp.int32),
        avail_tw=jnp.zeros(n, jnp.float32) if tagged else None,
        kind_count=(
            jnp.zeros(N_KINDS if scenario else 4, jnp.int32) if tagged else None
        ),
        occ_tw_c=jnp.zeros(n, jnp.float32),
        busy_t_c=jnp.zeros(n, jnp.float32),
        delay_sum_c=jnp.zeros(n, jnp.float32),
        avail_tw_c=jnp.zeros(n, jnp.float32) if tagged else None,
    )


def stats_step(stats: StatsState, ev: Event, occ_pre, occ_post, k) -> StatsState:
    """Accumulate observables for step k (0-based).

    ``occ_pre`` is the pre-step occupancy (the state that persisted over
    ``ev.dt`` — its time integral is the quantity product form predicts),
    ``occ_post`` the post-step occupancy (the X_{i,k} the Palm accumulators
    of the host simulator count).
    """
    import jax.numpy as jnp

    delay = (k - stats.slot_step[ev.slot]).astype(jnp.float32)
    occ_tw, occ_tw_c = kahan_add(
        stats.occ_tw, stats.occ_tw_c, occ_pre.astype(jnp.float32) * ev.dt
    )
    busy_t, busy_t_c = kahan_add(
        stats.busy_t, stats.busy_t_c, jnp.where(occ_pre > 0, ev.dt, 0.0)
    )
    delay_sum, delay_sum_c = _kahan_scatter_add(
        stats.delay_sum, stats.delay_sum_c, ev.j, delay
    )
    return StatsState(
        occ_sum=stats.occ_sum + occ_post,
        occ_tw=occ_tw,
        busy_t=busy_t,
        comp=stats.comp.at[ev.j].add(1),
        delay_sum=delay_sum,
        slot_step=stats.slot_step.at[ev.slot].set(k + 1),
        occ_tw_c=occ_tw_c,
        busy_t_c=busy_t_c,
        delay_sum_c=delay_sum_c,
    )


def fault_stats_step(
    stats: StatsState, ev: Event, occ_pre, avail_pre, occ_post, k
) -> StatsState:
    """Fault-aware `stats_step`.

    Differences: completions / delays only count ``KIND_COMPLETE`` events,
    ``busy_t`` integrates ``1{X_i > 0 and available}`` (the time a node was
    actually serving — dividing completions by it keeps `estimate_mu`
    unbiased under churn), and the availability integral plus per-kind event
    counts accumulate.  Slot bookkeeping is shared: any task movement
    refreshes ``slot_step`` (crash/timeout re-dispatches reset staleness);
    flips carry ``slot == C`` so their scatters drop out of bounds.
    """
    import jax.numpy as jnp

    comp = (ev.kind == KIND_COMPLETE).astype(jnp.int32)
    delay = (k - stats.slot_step[ev.slot]).astype(jnp.float32)
    occ_tw, occ_tw_c = kahan_add(
        stats.occ_tw, stats.occ_tw_c, occ_pre.astype(jnp.float32) * ev.dt
    )
    busy_t, busy_t_c = kahan_add(
        stats.busy_t, stats.busy_t_c,
        jnp.where((occ_pre > 0) & (avail_pre > 0), ev.dt, 0.0),
    )
    delay_sum, delay_sum_c = _kahan_scatter_add(
        stats.delay_sum, stats.delay_sum_c, ev.j,
        delay * comp.astype(jnp.float32),
    )
    avail_tw, avail_tw_c = kahan_add(
        stats.avail_tw, stats.avail_tw_c, avail_pre * ev.dt
    )
    return StatsState(
        occ_sum=stats.occ_sum + occ_post,
        occ_tw=occ_tw,
        busy_t=busy_t,
        comp=stats.comp.at[ev.j].add(comp),
        delay_sum=delay_sum,
        slot_step=stats.slot_step.at[ev.slot].set(k + 1, mode="drop"),
        avail_tw=avail_tw,
        kind_count=stats.kind_count.at[ev.kind].add(1),
        occ_tw_c=occ_tw_c,
        busy_t_c=busy_t_c,
        delay_sum_c=delay_sum_c,
        avail_tw_c=avail_tw_c,
    )


def scenario_stats_step(
    stats: StatsState, ev: Event, occ_pre, avail_pre, speed_pre, occ_post, k
) -> StatsState:
    """Scenario-aware `stats_step`.

    Like `fault_stats_step`, but ``busy_t`` integrates the *modulated*
    exposure ``speed_i 1{X_i > 0}`` instead of the 0/1 gate: in the
    time-change ``dtau = speed dt`` the head task's stage sequence is the
    unmodulated unit-mean phase chain at rate ``mu_i``, so long-run
    ``comp / busy_t -> mu`` exactly and `estimate_mu` / `ctrl_refresh`
    stay unbiased under modulation and non-exponential service alike.
    ``kind_count`` is the full (N_KINDS,) histogram (stage advances are
    tag 5).
    """
    import jax.numpy as jnp

    comp = (ev.kind == KIND_COMPLETE).astype(jnp.int32)
    delay = (k - stats.slot_step[ev.slot]).astype(jnp.float32)
    occ_tw, occ_tw_c = kahan_add(
        stats.occ_tw, stats.occ_tw_c, occ_pre.astype(jnp.float32) * ev.dt
    )
    busy_t, busy_t_c = kahan_add(
        stats.busy_t, stats.busy_t_c,
        jnp.where(occ_pre > 0, speed_pre, 0.0) * ev.dt,
    )
    delay_sum, delay_sum_c = _kahan_scatter_add(
        stats.delay_sum, stats.delay_sum_c, ev.j,
        delay * comp.astype(jnp.float32),
    )
    avail_tw, avail_tw_c = kahan_add(
        stats.avail_tw, stats.avail_tw_c, avail_pre * ev.dt
    )
    return StatsState(
        occ_sum=stats.occ_sum + occ_post,
        occ_tw=occ_tw,
        busy_t=busy_t,
        comp=stats.comp.at[ev.j].add(comp),
        delay_sum=delay_sum,
        slot_step=stats.slot_step.at[ev.slot].set(k + 1, mode="drop"),
        avail_tw=avail_tw,
        kind_count=stats.kind_count.at[ev.kind].add(1),
        occ_tw_c=occ_tw_c,
        busy_t_c=busy_t_c,
        delay_sum_c=delay_sum_c,
        avail_tw_c=avail_tw_c,
    )


def _network_scan(n: int, C: int, T: int, init: str, emit_events: bool,
                  fault: bool = False, scenario: bool = False):
    """Shared scan harness: T fused CS steps of stream_step + stats_step.

    Returns ``gen(key, mu, p) -> (init_nodes, events | None, stats)`` where
    ``events = (J, K, t, slot, delay)`` arrays when ``emit_events`` (the
    exportable stream) and None otherwise (the cheaper stats-only pass the
    adaptive control loop and the stream benchmarks consume).  With
    ``fault``, the generator signature grows a trailing
    ``fr = resolve_fault_rates(...)`` operand, the per-step machinery swaps
    to `fault_stream_step` / `fault_stats_step`, and the emitted events gain
    a trailing kind column.  With ``scenario``, ``fr`` is instead a
    `ScenarioRates` and the machinery swaps to `scenario_stream_step` /
    `scenario_stats_step` (events also gain the kind column).
    """
    import jax
    import jax.numpy as jnp

    if fault and scenario:
        raise ValueError("fault and scenario streams are mutually exclusive")

    def gen(key, mu, p, fr=None):
        if scenario:
            k_init, k_race, k_exp, k_disp, k_ph = jax.random.split(key, 5)
            state, init_nodes = scenario_stream_init(
                k_init, n, C, p, fr, init=init
            )
            u_ph = jax.random.uniform(k_ph, (T,))
        else:
            k_init, k_race, k_exp, k_disp = jax.random.split(key, 4)
            state, init_nodes = stream_init(
                k_init, n, C, p, init=init, fault=fault
            )
            u_ph = None
        u_race = jax.random.uniform(k_race, (T,))
        u_exp = jax.random.uniform(k_exp, (T,))
        # all T dispatch draws through one shared segment tree (hierarchical
        # CDF — O(log n) per draw, unbiased at large n, zero-p never drawn)
        ptree = tree_build(jnp.asarray(p, jnp.float32))
        K = jax.vmap(lambda u: tree_sample(ptree, u))(
            jax.random.uniform(k_disp, (T,))
        ).astype(jnp.int32)
        stats = stats_init(n, C, fault=fault, scenario=scenario)

        def body(carry, xs):
            state, stats, k = carry
            occ_pre = state.occ
            if scenario:
                avail_pre = state.avail
                speed_pre = avail_pre + (1.0 - avail_pre) * fr.rate_scale
                state, ev = scenario_stream_step(state, mu, fr, xs)
                delay = k - stats.slot_step[ev.slot]
                stats = scenario_stats_step(
                    stats, ev, occ_pre, avail_pre, speed_pre, state.occ, k
                )
                ys = (
                    (ev.j, ev.k, ev.t, ev.slot, delay, ev.kind)
                    if emit_events else None
                )
            elif fault:
                avail_pre = state.avail
                state, ev = fault_stream_step(state, mu, fr, xs)
                delay = k - stats.slot_step[ev.slot]
                stats = fault_stats_step(
                    stats, ev, occ_pre, avail_pre, state.occ, k
                )
                ys = (
                    (ev.j, ev.k, ev.t, ev.slot, delay, ev.kind)
                    if emit_events else None
                )
            else:
                state, ev = stream_step(state, mu, xs)
                delay = k - stats.slot_step[ev.slot]  # before stats_step moves it
                stats = stats_step(stats, ev, occ_pre, state.occ, k)
                ys = (ev.j, ev.k, ev.t, ev.slot, delay) if emit_events else None
            return (state, stats, k + 1), ys

        carry = (state, stats, jnp.int32(0))
        xs = (u_race, u_exp, K, u_ph) if scenario else (u_race, u_exp, K)
        (state, stats, _), events = jax.lax.scan(body, carry, xs)
        return init_nodes, events, stats

    return gen


@lru_cache(maxsize=32)
def _stream_generator(n: int, C: int, T: int, init: str, fault: bool = False,
                      scenario: bool = False):
    import jax

    return jax.jit(
        _network_scan(n, C, T, init, emit_events=True, fault=fault,
                      scenario=scenario)
    )


@lru_cache(maxsize=32)
def stats_stream_fn(n: int, C: int, T: int, init: str = "distinct",
                    fault: bool = False, scenario: bool = False):
    """Stats-only fused network scan: ``gen(key, mu, p[, fr]) -> StatsState``.

    No per-event outputs — just the running occupancy / busy-time /
    completion / delay accumulators.  Returned un-jitted so callers compose
    it with vmap/pmap over scenarios before compiling.  With ``fault`` the
    trailing operand is `resolve_fault_rates(...)`; with ``scenario`` it is
    a `ScenarioRates`.
    """
    base = _network_scan(n, C, T, init, emit_events=False, fault=fault,
                         scenario=scenario)
    if fault or scenario:
        return lambda key, mu, p, fr: base(key, mu, p, fr)[2]
    return lambda key, mu, p: base(key, mu, p)[2]


def generate_stream(
    mu,
    p,
    C: int,
    T: int,
    seed: int | Any = 0,
    init: str = "distinct",
    fault: FaultConfig | None = None,
    scenario: ScenarioConfig | None = None,
) -> EventStream:
    """Simulate T CS steps on device and export a host `EventStream`.

    Drop-in replacement for `queue_sim.export_stream` (exponential service
    only): same arrays, same invariants, different — but law-identical —
    realization.  ``seed`` may be an int or a PRNG key.  The jitted
    generator is cached per (n, C, T, init, faults-on), so sweeps over
    (mu, p, seed) and fault rates reuse one compiled program.  With
    ``fault`` the stream carries a kind column and T counts merged events
    (flips included) — same convention as `queue_sim.export_stream`.
    ``scenario`` (mutually exclusive with ``fault``) swaps the service law
    and availability process to a `ScenarioConfig`; T then counts merged
    events including stage advances and flips.
    """
    import jax
    import jax.numpy as jnp

    mu = np.asarray(mu, dtype=np.float64)
    p = np.asarray(p, dtype=np.float64)
    n = mu.size
    if abs(p.sum() - 1.0) > 1e-8:
        raise ValueError("p must sum to 1")
    key = jax.random.PRNGKey(seed) if np.ndim(seed) == 0 else seed
    faulty = fault is not None and fault.enabled
    scen = scenario is not None and scenario.enabled
    if faulty and scen:
        raise ValueError("fault= and scenario= are mutually exclusive")
    gen = _stream_generator(n, int(C), int(T), init, faulty, scen)
    if scen:
        init_nodes, (J, K, t, slot, delays, kind), stats = gen(
            key, jnp.asarray(mu, jnp.float32), jnp.asarray(p, jnp.float32),
            resolve_scenario(scenario, n),
        )
        kind_np = np.asarray(kind, np.int8)
    elif faulty:
        init_nodes, (J, K, t, slot, delays, kind), stats = gen(
            key, jnp.asarray(mu, jnp.float32), jnp.asarray(p, jnp.float32),
            resolve_fault_rates(fault, n),
        )
        kind_np = np.asarray(kind, np.int8)
    else:
        init_nodes, (J, K, t, slot, delays), stats = gen(
            key, jnp.asarray(mu, jnp.float32), jnp.asarray(p, jnp.float32)
        )
        kind_np = None
    return EventStream(
        J=np.asarray(J, np.int32),
        K=np.asarray(K, np.int32),
        t=np.asarray(t, np.float64),
        slot=np.asarray(slot, np.int32),
        init_nodes=np.asarray(init_nodes, np.int32),
        n=n,
        C=int(C),
        p=p.copy(),
        delay_steps=np.asarray(delays, np.int64),
        queue_len_sum=np.asarray(stats.occ_sum, np.float64),
        queue_len_tw=kahan_value(stats.occ_tw, stats.occ_tw_c),
        kind=kind_np,
    )


def generate_blocks(
    mu,
    p,
    C: int,
    T: int,
    block_size: int,
    seed: int | Any = 0,
    init: str = "distinct",
    cut_every: int = 0,
    method: str = "greedy",
    fault: FaultConfig | None = None,
    scenario: ScenarioConfig | None = None,
) -> EventBlocks:
    """Device-generated event stream, segmented into conflict-free blocks.

    The blocked analogue of `generate_stream`: the closed network is
    simulated on device (one compiled scan, cached per shape) and the
    resulting stream is cut into fixed-shape ``(B, E)`` index+mask
    micro-blocks by `queue_sim.segment_blocks` — the feed of the blocked
    scan engine when the events should come from the device generator
    rather than the host simulator.  ``method`` picks the cut placement
    ("greedy" | "dp" — see `queue_sim.segment_blocks`).
    """
    return EventBlocks.from_stream(
        generate_stream(mu, p, C, T, seed=seed, init=init, fault=fault,
                        scenario=scenario),
        block_size,
        cut_every,
        method,
    )


# ---------------------------------------------------------------------- #
# sparse O(C) closed network: state keyed by the C in-flight tasks
# ---------------------------------------------------------------------- #
class ClassSpec(NamedTuple):
    """Static speed-class structure of the client population.

    Clients with identical ``(mu, p)`` are exchangeable in the closed
    Jackson network (the paper's two-cluster structure, generalized to m
    classes), so the sparse stream only tracks *which class* each idle
    node belongs to and keeps per-node identity for the C in-flight
    tasks.  ``perm`` maps compact (class-sorted) positions to global
    client ids — clients of class c occupy ``perm[offsets[c] :
    offsets[c] + counts[c]]`` — and ``inv_cls`` inverts it per global id.
    Both tables are O(n) *memory* but only touched by O(1) gathers per
    event, so per-event cost stays flat in n.
    """

    counts: Any   # (m,) int32 — class sizes
    offsets: Any  # (m,) int32 — exclusive prefix sums of counts
    perm: Any     # (n,) int32 — compact position -> global client id
    inv_cls: Any  # (n,) int32 — global client id -> class index

    @property
    def n(self) -> int:
        return int(self.perm.shape[0])

    @property
    def m(self) -> int:
        return int(self.counts.shape[0])

    def device(self) -> "ClassSpec":
        import jax.numpy as jnp

        return ClassSpec(*(jnp.asarray(a, jnp.int32) for a in self))

    def cache_key(self) -> tuple:
        return (
            self.n,
            tuple(np.asarray(self.counts).tolist()),
            hash(np.asarray(self.perm, np.int32).tobytes()),
        )


def build_class_spec(mu, p=None, max_classes: int = 64):
    """Detect speed classes from per-node ``(mu, p)``.

    Returns ``(spec, mu_m, p_m)`` with class-level service rates and
    per-node dispatch probabilities.  Raises if more than ``max_classes``
    distinct ``(mu, p)`` pairs exist — the sparse path is for populations
    with cluster structure, not fully heterogeneous rates.
    """
    mu = np.asarray(mu, np.float64)
    n = mu.size
    p = np.full(n, 1.0 / n) if p is None else np.asarray(p, np.float64)
    vals, inv = np.unique(np.stack([mu, p], axis=1), axis=0, return_inverse=True)
    m = vals.shape[0]
    if m > max_classes:
        raise ValueError(
            f"{m} distinct (mu, p) classes exceed max_classes={max_classes}; "
            "the sparse stream needs cluster structure"
        )
    inv = inv.reshape(n)
    counts = np.bincount(inv, minlength=m)
    offsets = np.concatenate([[0], np.cumsum(counts)[:-1]])
    spec = ClassSpec(
        counts=counts.astype(np.int32),
        offsets=offsets.astype(np.int32),
        perm=np.argsort(inv, kind="stable").astype(np.int32),
        inv_cls=inv.astype(np.int32),
    )
    return spec, vals[:, 0].copy(), vals[:, 1].copy()


def resolve_fault_rates_classes(fault: FaultConfig, spec: ClassSpec):
    """Class-level `resolve_fault_rates`: ``(kappa, theta, q_off, q_on)``
    as ``(m,)`` arrays.  Fault rates must be constant within each speed
    class — the exchangeability the sparse idle pools rely on."""
    import jax.numpy as jnp

    q_off, q_on, kappa, theta = fault.resolve(spec.n)
    perm = np.asarray(spec.perm)
    offsets = np.asarray(spec.offsets)
    counts = np.asarray(spec.counts)
    out = []
    for name, r in (("crash_rate", kappa), ("timeout_rate", theta),
                    ("off_rate", q_off), ("on_rate", q_on)):
        rc = np.asarray(r, np.float64)[perm]
        vals = rc[offsets]
        for c in range(counts.size):
            seg = rc[offsets[c]: offsets[c] + counts[c]]
            if not np.allclose(seg, vals[c]):
                raise ValueError(
                    f"FaultConfig.{name} varies within speed class {c}; "
                    "the sparse stream requires class-constant fault rates"
                )
        out.append(jnp.asarray(vals, jnp.float32))
    return tuple(out)


class SparseStreamState(NamedTuple):
    """Sparse device state of the closed network: O(C + m), not O(n·C).

    Each of the C circulating tasks is one slot; FIFO order within a node
    is the monotone ``seq`` stamp and ``head`` marks the head-of-line
    task (exactly one per busy node).  Under faults, availability is
    carried per slot (consistent across slots of the same node) and idle
    nodes are tracked as per-class ``(idle_on, idle_off)`` counts —
    within-class identities are exchangeable, so the collapse is exact in
    law for every per-class observable.
    """

    node: Any   # (C,) int32 — global client id of each in-flight task
    cls: Any    # (C,) int32 — speed class of that node
    seq: Any    # (C,) int32 — dispatch stamp (FIFO: head = min seq per node)
    head: Any   # (C,) bool — head-of-line flag
    t: Any      # () float32 — physical time (Kahan; see t_c)
    t_c: Any    # () float32
    next_seq: Any  # () int32 — next dispatch stamp
    avail: Any = None     # (C,) float32 — availability bit of the slot's node
    idle_on: Any = None   # (m,) int32 — idle & available nodes per class
    idle_off: Any = None  # (m,) int32 — idle & unavailable nodes per class
    phase: Any = None     # (C,) int32 — service stage of each slot's task
                          # (scenario mode; else None)


def sample_dispatch_classes(p, spec: ClassSpec, u_cls, u_mem):
    """Vectorized dispatch draws K ~ p for a class-structured population.

    ``p`` is the (m,) *per-node* probability by class; a draw picks the
    class from the (m,) mass vector ``counts * p`` by segment-tree
    descent, then a uniform member — identical in law to the dense (n,)
    inverse-CDF draw, at O(log m) per event.  ``u_cls``/``u_mem`` are
    1-D uniform blocks; returns global client ids.
    """
    import jax
    import jax.numpy as jnp

    counts = jnp.asarray(spec.counts, jnp.int32)
    offsets = jnp.asarray(spec.offsets, jnp.int32)
    perm = jnp.asarray(spec.perm, jnp.int32)
    mass = counts.astype(jnp.float32) * jnp.asarray(p, jnp.float32)
    mtree = tree_build(mass)
    c = jax.vmap(lambda u: tree_sample(mtree, u))(u_cls).astype(jnp.int32)
    member = jnp.minimum(
        (u_mem * counts[c].astype(jnp.float32)).astype(jnp.int32),
        counts[c] - 1,
    )
    return perm[offsets[c] + member]


def sparse_stream_init(key, spec: ClassSpec, C: int, p=None,
                       init: str = "distinct", fault: bool = False):
    """Initial sparse placement of the C tasks.  Returns (state, nodes).

    Same two conventions as `stream_init`.  "distinct" draws a uniform
    C-subset by sequential rank-adjusted draws (O(C^2), exact — the r-th
    smallest unchosen id is recovered by bumping r past every chosen id
    at or below it) rather than an O(n) permutation, so even the one-time
    init stays flat in n; every event is O(C + m + log n).
    """
    import jax
    import jax.numpy as jnp

    n, m = spec.n, spec.m
    if init == "distinct":
        if C <= n:
            ranks = jax.vmap(
                lambda k, hi: jax.random.randint(k, (), 0, hi)
            )(jax.random.split(key, C), jnp.arange(n, n - C, -1))

            def draw(i, chosen):
                sort = jnp.sort(chosen)  # sentinels n sort last

                def bump(x, s):
                    return jnp.where(s <= x, x + 1, x), None

                r, _ = jax.lax.scan(bump, ranks[i], sort)
                return chosen.at[i].set(r)

            nodes = jax.lax.fori_loop(
                0, C, draw, jnp.full(C, n, jnp.int32)).astype(jnp.int32)
        else:
            nodes = jnp.arange(C, dtype=jnp.int32) % n
    elif init == "sampled":
        k1, k2 = jax.random.split(key)
        nodes = sample_dispatch_classes(
            p, spec, jax.random.uniform(k1, (C,)), jax.random.uniform(k2, (C,))
        )
    else:
        raise ValueError(init)
    # head-of-line = first occurrence of each node among the C slots
    eq = nodes[None, :] == nodes[:, None]
    head = jnp.sum(jnp.tril(eq, -1), axis=1) == 0
    cls = jnp.asarray(spec.inv_cls, jnp.int32)[nodes]
    if fault:
        busy_m = jnp.zeros(m, jnp.int32).at[cls].add(head.astype(jnp.int32))
        idle_on = jnp.asarray(spec.counts, jnp.int32) - busy_m
        avail = jnp.ones(C, jnp.float32)
        idle_off = jnp.zeros(m, jnp.int32)
    else:
        avail = idle_on = idle_off = None
    state = SparseStreamState(
        node=nodes,
        cls=cls,
        seq=jnp.arange(C, dtype=jnp.int32),
        head=head,
        t=jnp.float32(0.0),
        t_c=jnp.float32(0.0),
        next_seq=jnp.int32(C),
        avail=avail,
        idle_on=idle_on,
        idle_off=idle_off,
    )
    return state, nodes


def class_occupancy(cls, m: int):
    """(m,) int32 per-class task counts from the (C,) slot classes."""
    import jax.numpy as jnp

    return jnp.zeros(m, jnp.int32).at[cls].add(1)


def sparse_class_stats(state: SparseStreamState, m: int, fault: bool = False):
    """Per-class (occupancy, busy-node, available-node) counts.

    ``busy`` counts distinct busy nodes (one head per busy node); in
    fault mode it is gated on availability (the `estimate_mu` exposure)
    and ``avail`` counts available nodes (busy-available heads plus the
    idle-on pool).  Returns ``(occ, busy, avail-or-None)``.
    """
    import jax.numpy as jnp

    occ = class_occupancy(state.cls, m)
    h = state.head.astype(jnp.int32)
    if not fault:
        busy = jnp.zeros(m, jnp.int32).at[state.cls].add(h)
        return occ, busy, None
    ha = h * (state.avail > 0).astype(jnp.int32)
    busy = jnp.zeros(m, jnp.int32).at[state.cls].add(ha)
    avail = busy + state.idle_on
    return occ, busy, avail


def sparse_stream_step(state: SparseStreamState, mu, spec, xs):
    """One CS step of the sparse closed network — O(C + log n) work.

    ``xs = (u_race, u_exp, k_new)`` as in `stream_step`; ``mu`` is the
    (m,) class rate vector and ``spec`` a device `ClassSpec`.  The
    completion race runs over the <= C head-of-line tasks only (each busy
    node contributes exactly one head), so nothing scales with n.
    """
    import jax.numpy as jnp

    u_race, u_exp, k_new = xs
    node, cls, seq, head = state.node, state.cls, state.seq, state.head
    C = node.shape[0]
    ar = jnp.arange(C, dtype=jnp.int32)
    rates = jnp.where(head, mu[cls], 0.0)
    rtree = tree_build(rates)
    dt = -jnp.log1p(-u_exp) / rtree[1]
    t, t_c = kahan_add(state.t, state.t_c, dt)
    s = tree_sample(rtree, u_race).astype(jnp.int32)
    j = node[s]
    # promote j's next-oldest task (if any) to head-of-line
    others_j = (node == j) & (ar != s)
    has_succ = jnp.any(others_j)
    succ = jnp.argmin(jnp.where(others_j, seq, jnp.int32(2**31 - 1)))
    head = head & (ar != s)
    head = head | ((ar == succ) & has_succ)
    # the freed slot hosts the dispatch; join-or-fresh by O(C) membership
    exists_k = jnp.any((node == k_new) & (ar != s))
    node = node.at[s].set(k_new)
    cls = cls.at[s].set(jnp.asarray(spec.inv_cls, jnp.int32)[k_new])
    seq = seq.at[s].set(state.next_seq)
    head = jnp.where(ar == s, ~exists_k, head)
    return (
        SparseStreamState(node=node, cls=cls, seq=seq, head=head, t=t,
                          t_c=t_c, next_seq=state.next_seq + 1),
        Event(j=j, k=k_new, t=t, slot=s, dt=dt),
    )


def sparse_fault_stream_step(state: SparseStreamState, mu, spec, fr, xs):
    """One merged-CTMC event of the faulty sparse network — O(C + m).

    The race runs over ``4C + 2m`` clocks: per head slot [completion |
    crash | timeout | availability flip] plus per class [idle on->off |
    idle off->on] — the exact class-collapse of the dense ``4n`` race
    (each busy node has one head; idle nodes are exchangeable within a
    class).  ``xs = (u_race, u_exp, k_new, u_bit)``: ``u_bit`` resolves
    the availability bit when the dispatch lands on an idle node (drawn
    from the class's (idle_on, idle_off) composition — exact in law by
    exchangeability; within-class *identities* of idle nodes are not
    tracked).  ``fr = resolve_fault_rates_classes(...)``.  Idle-pool
    flips emit a representative id of the class with ``slot = C`` (the
    trash row), like dense flips.
    """
    import jax.numpy as jnp

    kappa, theta, q_off, q_on = fr
    u_race, u_exp, k_new, u_bit = xs
    node, cls, seq, head, a = (
        state.node, state.cls, state.seq, state.head, state.avail,
    )
    ion, ioff = state.idle_on, state.idle_off
    C = node.shape[0]
    m = ion.shape[0]
    ar = jnp.arange(C, dtype=jnp.int32)
    inv_cls = jnp.asarray(spec.inv_cls, jnp.int32)
    hf = head.astype(jnp.float32)
    rates = jnp.concatenate([
        mu[cls] * a * hf,
        kappa[cls] * a * hf,
        theta[cls] * hf,
        (q_off[cls] * a + q_on[cls] * (1.0 - a)) * hf,
        ion.astype(jnp.float32) * q_off,
        ioff.astype(jnp.float32) * q_on,
    ])
    rtree = tree_build(rates)
    tot = jnp.maximum(rtree[1], 1e-30)  # all-off + no clocks: time still moves
    dt = -jnp.log1p(-u_exp) / tot
    t, t_c = kahan_add(state.t, state.t_c, dt)
    idx = tree_sample(rtree, u_race).astype(jnp.int32)

    move = idx < 3 * C
    kind = jnp.where(move, idx // C, KIND_FLIP).astype(jnp.int32)
    s_mv = jnp.where(move, idx % C, 0)
    is_bf = (idx >= 3 * C) & (idx < 4 * C)
    s_bf = jnp.where(is_bf, idx - 3 * C, 0)
    is_if = idx >= 4 * C
    if_on2off = is_if & (idx < 4 * C + m)
    if_c = jnp.where(
        is_if, jnp.where(if_on2off, idx - 4 * C, idx - 4 * C - m), 0
    )

    j_mv = node[s_mv]
    cls_j = cls[s_mv]
    a_j = a[s_mv]
    j_bf = node[s_bf]
    perm = jnp.asarray(spec.perm, jnp.int32)
    offsets = jnp.asarray(spec.offsets, jnp.int32)
    j = jnp.where(move, j_mv, jnp.where(is_bf, j_bf, perm[offsets[if_c]]))
    s = jnp.where(move, s_mv, C).astype(jnp.int32)

    # movement (complete / crash / timeout): pop the head at j, redispatch
    others_j = move & (node == j_mv) & (ar != s_mv)
    has_succ = jnp.any(others_j)
    succ = jnp.argmin(jnp.where(others_j, seq, jnp.int32(2**31 - 1)))
    head = head & ~(move & (ar == s_mv))
    head = head | ((ar == succ) & has_succ)

    cls_k = inv_cls[k_new]
    k_is_j = move & (k_new == j_mv)
    exists_k = move & jnp.any((node == k_new) & (ar != s_mv))
    j_idles = move & ~has_succ & ~k_is_j
    # pool state the fresh draw sees: after j (possibly) went idle
    ion1 = ion.at[cls_j].add((j_idles & (a_j > 0)).astype(jnp.int32))
    ioff1 = ioff.at[cls_j].add((j_idles & (a_j == 0)).astype(jnp.int32))
    pool_on = ion1[cls_k].astype(jnp.float32)
    pool = pool_on + ioff1[cls_k].astype(jnp.float32)
    bit_pool = (u_bit * jnp.maximum(pool, 1.0) < pool_on).astype(jnp.float32)
    bit_join = jnp.max(jnp.where((node == k_new) & (ar != s_mv), a, 0.0))
    bit_new = jnp.where(exists_k, bit_join, jnp.where(k_is_j, a_j, bit_pool))
    fresh = move & ~exists_k & ~k_is_j
    ion2 = ion1.at[cls_k].add(-(fresh & (bit_new > 0)).astype(jnp.int32))
    ioff2 = ioff1.at[cls_k].add(-(fresh & (bit_new == 0)).astype(jnp.int32))

    # busy-node availability flip: toggle every slot of that node
    a = jnp.where(is_bf & (node == j_bf), 1.0 - a, a)
    # idle-pool flips: move one node between the (on, off) counts
    ion3 = ion2.at[if_c].add(jnp.where(is_if, jnp.where(if_on2off, -1, 1), 0))
    ioff3 = ioff2.at[if_c].add(jnp.where(is_if, jnp.where(if_on2off, 1, -1), 0))

    at_s = move & (ar == s_mv)
    node = jnp.where(at_s, k_new, node)
    cls = jnp.where(at_s, cls_k, cls)
    seq = jnp.where(at_s, state.next_seq, seq)
    head = jnp.where(at_s, ~exists_k, head)
    a = jnp.where(at_s, bit_new, a)
    return (
        SparseStreamState(
            node=node, cls=cls, seq=seq, head=head, t=t, t_c=t_c,
            next_seq=state.next_seq + move.astype(jnp.int32),
            avail=a, idle_on=ion3, idle_off=ioff3,
        ),
        Event(j=j, k=k_new, t=t, slot=s, dt=dt, kind=kind),
    )


def sparse_scenario_stream_init(key, spec: ClassSpec, C: int, p,
                                sr: ScenarioRates, init: str = "distinct"):
    """`sparse_stream_init` + per-slot initial phase draws."""
    import jax

    k_place, k_ph = jax.random.split(key)
    state, nodes = sparse_stream_init(k_place, spec, C, p, init=init,
                                      fault=True)
    phase = _phase_draw(sr.acdf, jax.random.uniform(k_ph, (C,)))
    return state._replace(phase=phase), nodes


def sparse_scenario_class_stats(state: SparseStreamState, m: int, rate_scale):
    """Per-class (occupancy, modulated busy exposure, available nodes).

    ``busy`` is the *speed-weighted* float exposure ``sum_heads speed`` —
    the denominator that keeps `estimate_mu` unbiased under modulation
    (see `scenario_stats_step`); ``avail`` counts available nodes
    (available heads + the idle-on pool), as in fault mode.
    """
    import jax.numpy as jnp

    occ = class_occupancy(state.cls, m)
    hf = state.head.astype(jnp.float32)
    speed = state.avail + (1.0 - state.avail) * rate_scale
    busy = jnp.zeros(m, jnp.float32).at[state.cls].add(hf * speed)
    ha = state.head.astype(jnp.int32) * (state.avail > 0).astype(jnp.int32)
    avail = jnp.zeros(m, jnp.int32).at[state.cls].add(ha) + state.idle_on
    return occ, busy, avail


def sparse_scenario_stream_step(state: SparseStreamState, mu, spec,
                                sr: ScenarioRates, xs):
    """One merged-CTMC event of the scenario sparse network — O(C + m).

    The race runs over ``2C + 2m`` clocks: per head slot [serve-or-stage |
    availability flip] plus per class [idle on->off | idle off->on] — the
    class-collapse of the dense ``2n`` race of `scenario_stream_step`.
    A serve win decodes into completion vs stage advance by
    ``absorb[phase]``; only completions move a task (the idle-pool /
    join-bit machinery is shared with `sparse_fault_stream_step`).
    ``xs = (u_race, u_exp, k_new, u_bit, u_ph)``;
    ``sr = resolve_scenario_classes(...)``.
    """
    import jax.numpy as jnp

    u_race, u_exp, k_new, u_bit, u_ph = xs
    node, cls, seq, head, a, phase = (
        state.node, state.cls, state.seq, state.head, state.avail, state.phase,
    )
    ion, ioff = state.idle_on, state.idle_off
    C = node.shape[0]
    m = ion.shape[0]
    ar = jnp.arange(C, dtype=jnp.int32)
    inv_cls = jnp.asarray(spec.inv_cls, jnp.int32)
    hf = head.astype(jnp.float32)
    speed = a + (1.0 - a) * sr.rate_scale
    rates = jnp.concatenate([
        mu[cls] * sr.srate[phase] * speed * hf,
        (sr.q_off[cls] * a + sr.q_on[cls] * (1.0 - a)) * hf,
        ion.astype(jnp.float32) * sr.q_off,
        ioff.astype(jnp.float32) * sr.q_on,
    ])
    rtree = tree_build(rates)
    tot = jnp.maximum(rtree[1], 1e-30)  # all-suspended: time still moves
    dt = -jnp.log1p(-u_exp) / tot
    t, t_c = kahan_add(state.t, state.t_c, dt)
    idx = tree_sample(rtree, u_race).astype(jnp.int32)

    is_sv = idx < C
    s_sv = jnp.where(is_sv, idx, 0)
    ph_sv = phase[s_sv]
    complete = is_sv & (sr.absorb[ph_sv] > 0)
    move = complete
    s_mv = s_sv
    is_bf = (idx >= C) & (idx < 2 * C)
    s_bf = jnp.where(is_bf, idx - C, 0)
    is_if = idx >= 2 * C
    if_on2off = is_if & (idx < 2 * C + m)
    if_c = jnp.where(
        is_if, jnp.where(if_on2off, idx - 2 * C, idx - 2 * C - m), 0
    )
    kind = jnp.where(
        complete, KIND_COMPLETE, jnp.where(is_sv, KIND_STAGE, KIND_FLIP)
    ).astype(jnp.int32)

    j_mv = node[s_mv]
    cls_j = cls[s_mv]
    a_j = a[s_mv]
    j_bf = node[s_bf]
    perm = jnp.asarray(spec.perm, jnp.int32)
    offsets = jnp.asarray(spec.offsets, jnp.int32)
    j = jnp.where(is_sv, j_mv, jnp.where(is_bf, j_bf, perm[offsets[if_c]]))
    s = jnp.where(move, s_mv, C).astype(jnp.int32)

    # completion: pop the head at j, redispatch (shared with the fault step)
    others_j = move & (node == j_mv) & (ar != s_mv)
    has_succ = jnp.any(others_j)
    succ = jnp.argmin(jnp.where(others_j, seq, jnp.int32(2**31 - 1)))
    head = head & ~(move & (ar == s_mv))
    head = head | ((ar == succ) & has_succ)

    cls_k = inv_cls[k_new]
    k_is_j = move & (k_new == j_mv)
    exists_k = move & jnp.any((node == k_new) & (ar != s_mv))
    j_idles = move & ~has_succ & ~k_is_j
    # pool state the fresh draw sees: after j (possibly) went idle
    ion1 = ion.at[cls_j].add((j_idles & (a_j > 0)).astype(jnp.int32))
    ioff1 = ioff.at[cls_j].add((j_idles & (a_j == 0)).astype(jnp.int32))
    pool_on = ion1[cls_k].astype(jnp.float32)
    pool = pool_on + ioff1[cls_k].astype(jnp.float32)
    bit_pool = (u_bit * jnp.maximum(pool, 1.0) < pool_on).astype(jnp.float32)
    bit_join = jnp.max(jnp.where((node == k_new) & (ar != s_mv), a, 0.0))
    bit_new = jnp.where(exists_k, bit_join, jnp.where(k_is_j, a_j, bit_pool))
    fresh = move & ~exists_k & ~k_is_j
    ion2 = ion1.at[cls_k].add(-(fresh & (bit_new > 0)).astype(jnp.int32))
    ioff2 = ioff1.at[cls_k].add(-(fresh & (bit_new == 0)).astype(jnp.int32))

    # busy-node availability flip: toggle every slot of that node
    a = jnp.where(is_bf & (node == j_bf), 1.0 - a, a)
    # idle-pool flips: move one node between the (on, off) counts
    ion3 = ion2.at[if_c].add(jnp.where(is_if, jnp.where(if_on2off, -1, 1), 0))
    ioff3 = ioff2.at[if_c].add(jnp.where(is_if, jnp.where(if_on2off, 1, -1), 0))

    at_s = move & (ar == s_mv)
    node = jnp.where(at_s, k_new, node)
    cls = jnp.where(at_s, cls_k, cls)
    seq = jnp.where(at_s, state.next_seq, seq)
    head = jnp.where(at_s, ~exists_k, head)
    a = jnp.where(at_s, bit_new, a)
    # phase update: completion -> fresh alpha draw for the dispatched task;
    # stage advance -> nxt; flips write to the trash index C (dropped)
    ph_w = jnp.where(complete, _phase_draw(sr.acdf, u_ph), sr.nxt[ph_sv])
    phase = phase.at[jnp.where(is_sv, s_sv, C)].set(
        ph_w.astype(jnp.int32), mode="drop"
    )
    return (
        SparseStreamState(
            node=node, cls=cls, seq=seq, head=head, t=t, t_c=t_c,
            next_seq=state.next_seq + move.astype(jnp.int32),
            avail=a, idle_on=ion3, idle_off=ioff3, phase=phase,
        ),
        Event(j=j, k=k_new, t=t, slot=s, dt=dt, kind=kind),
    )


def sparse_stats_init(m: int, C: int, fault: bool = False,
                      scenario: bool = False) -> StatsState:
    """Per-class `StatsState`: same fields, (m,) instead of (n,)."""
    import jax.numpy as jnp

    tagged = fault or scenario
    return StatsState(
        occ_sum=jnp.zeros(m, jnp.int32),
        occ_tw=jnp.zeros(m, jnp.float32),
        busy_t=jnp.zeros(m, jnp.float32),
        comp=jnp.zeros(m, jnp.int32),
        delay_sum=jnp.zeros(m, jnp.float32),
        slot_step=jnp.zeros(C, jnp.int32),
        avail_tw=jnp.zeros(m, jnp.float32) if tagged else None,
        kind_count=(
            jnp.zeros(N_KINDS if scenario else 4, jnp.int32) if tagged else None
        ),
        occ_tw_c=jnp.zeros(m, jnp.float32),
        busy_t_c=jnp.zeros(m, jnp.float32),
        delay_sum_c=jnp.zeros(m, jnp.float32),
        avail_tw_c=jnp.zeros(m, jnp.float32) if tagged else None,
    )


def sparse_stats_step(stats: StatsState, ev: Event, cls_j, occ_pre, busy_pre,
                      occ_post, k) -> StatsState:
    """Per-class `stats_step`: ``occ_pre``/``busy_pre``/``occ_post`` are the
    (m,) counts from `sparse_class_stats` and ``cls_j`` the class of the
    completing node."""
    import jax.numpy as jnp

    delay = (k - stats.slot_step[ev.slot]).astype(jnp.float32)
    occ_tw, occ_tw_c = kahan_add(
        stats.occ_tw, stats.occ_tw_c, occ_pre.astype(jnp.float32) * ev.dt
    )
    busy_t, busy_t_c = kahan_add(
        stats.busy_t, stats.busy_t_c, busy_pre.astype(jnp.float32) * ev.dt
    )
    delay_sum, delay_sum_c = _kahan_scatter_add(
        stats.delay_sum, stats.delay_sum_c, cls_j, delay
    )
    return StatsState(
        occ_sum=stats.occ_sum + occ_post,
        occ_tw=occ_tw,
        busy_t=busy_t,
        comp=stats.comp.at[cls_j].add(1),
        delay_sum=delay_sum,
        slot_step=stats.slot_step.at[ev.slot].set(k + 1),
        occ_tw_c=occ_tw_c,
        busy_t_c=busy_t_c,
        delay_sum_c=delay_sum_c,
    )


def sparse_fault_stats_step(stats: StatsState, ev: Event, cls_j, occ_pre,
                            busy_pre, avail_pre, occ_post, k) -> StatsState:
    """Fault-aware per-class stats: mirrors `fault_stats_step` with (m,)
    vectors (``avail_pre`` = available nodes per class, busy + idle-on)."""
    import jax.numpy as jnp

    comp = (ev.kind == KIND_COMPLETE).astype(jnp.int32)
    delay = (k - stats.slot_step[ev.slot]).astype(jnp.float32)
    occ_tw, occ_tw_c = kahan_add(
        stats.occ_tw, stats.occ_tw_c, occ_pre.astype(jnp.float32) * ev.dt
    )
    busy_t, busy_t_c = kahan_add(
        stats.busy_t, stats.busy_t_c, busy_pre.astype(jnp.float32) * ev.dt
    )
    delay_sum, delay_sum_c = _kahan_scatter_add(
        stats.delay_sum, stats.delay_sum_c, cls_j,
        delay * comp.astype(jnp.float32),
    )
    avail_tw, avail_tw_c = kahan_add(
        stats.avail_tw, stats.avail_tw_c, avail_pre.astype(jnp.float32) * ev.dt
    )
    return StatsState(
        occ_sum=stats.occ_sum + occ_post,
        occ_tw=occ_tw,
        busy_t=busy_t,
        comp=stats.comp.at[cls_j].add(comp),
        delay_sum=delay_sum,
        slot_step=stats.slot_step.at[ev.slot].set(k + 1, mode="drop"),
        avail_tw=avail_tw,
        kind_count=stats.kind_count.at[ev.kind].add(1),
        occ_tw_c=occ_tw_c,
        busy_t_c=busy_t_c,
        delay_sum_c=delay_sum_c,
        avail_tw_c=avail_tw_c,
    )


def _sparse_network_scan(m: int, C: int, T: int, init: str,
                         fault: bool = False, scenario: bool = False):
    """Sparse analogue of `_network_scan`: T fused sparse CS steps.

    Returns ``gen(key, mu, p, spec[, fr]) -> (init_nodes, stats, state)``
    with (m,) class-level ``mu``/``p`` and a device `ClassSpec`.
    """
    import jax
    import jax.numpy as jnp

    if fault and scenario:
        raise ValueError("fault and scenario streams are mutually exclusive")

    def gen(key, mu, p, spec, fr=None):
        keys = jax.random.split(key, 7 if scenario else 6)
        if scenario:
            state, init_nodes = sparse_scenario_stream_init(
                keys[0], spec, C, p, fr, init=init
            )
        else:
            state, init_nodes = sparse_stream_init(
                keys[0], spec, C, p, init=init, fault=fault
            )
        u_race = jax.random.uniform(keys[1], (T,))
        u_exp = jax.random.uniform(keys[2], (T,))
        K = sample_dispatch_classes(
            p, spec,
            jax.random.uniform(keys[3], (T,)),
            jax.random.uniform(keys[4], (T,)),
        )
        tagged = fault or scenario
        u_bit = jax.random.uniform(keys[5], (T,)) if tagged else None
        u_ph = jax.random.uniform(keys[6], (T,)) if scenario else None
        stats = sparse_stats_init(m, C, fault=fault, scenario=scenario)

        def body(carry, xs):
            state, stats, k = carry
            if scenario:
                occ_pre, busy_pre, avail_pre = sparse_scenario_class_stats(
                    state, m, fr.rate_scale
                )
                ur, ue, kn, ub, uph = xs
                state, ev = sparse_scenario_stream_step(
                    state, mu, spec, fr, (ur, ue, kn, ub, uph)
                )
                cls_j = jnp.asarray(spec.inv_cls, jnp.int32)[ev.j]
                stats = sparse_fault_stats_step(
                    stats, ev, cls_j, occ_pre, busy_pre, avail_pre,
                    class_occupancy(state.cls, m), k,
                )
            elif fault:
                occ_pre, busy_pre, avail_pre = sparse_class_stats(
                    state, m, fault=True
                )
                ur, ue, kn, ub = xs
                state, ev = sparse_fault_stream_step(
                    state, mu, spec, fr, (ur, ue, kn, ub)
                )
                cls_j = jnp.asarray(spec.inv_cls, jnp.int32)[ev.j]
                stats = sparse_fault_stats_step(
                    stats, ev, cls_j, occ_pre, busy_pre, avail_pre,
                    class_occupancy(state.cls, m), k,
                )
            else:
                occ_pre, busy_pre, avail_pre = sparse_class_stats(
                    state, m, fault=False
                )
                ur, ue, kn = xs
                state, ev = sparse_stream_step(state, mu, spec, (ur, ue, kn))
                cls_j = jnp.asarray(spec.inv_cls, jnp.int32)[ev.j]
                stats = sparse_stats_step(
                    stats, ev, cls_j, occ_pre, busy_pre,
                    class_occupancy(state.cls, m), k,
                )
            return (state, stats, k + 1), None

        if scenario:
            xs = (u_race, u_exp, K, u_bit, u_ph)
        elif fault:
            xs = (u_race, u_exp, K, u_bit)
        else:
            xs = (u_race, u_exp, K)
        (state, stats, _), _ = jax.lax.scan(
            body, (state, stats, jnp.int32(0)), xs
        )
        return init_nodes, stats, state

    return gen


@lru_cache(maxsize=32)
def sparse_stats_stream_fn(m: int, C: int, T: int, init: str = "distinct",
                           fault: bool = False, scenario: bool = False):
    """Stats-only sparse network scan, cached per shape.

    ``gen(key, mu, p, spec[, fr]) -> (StatsState, SparseStreamState)``
    with (m,) class-level inputs; per-event cost is flat in n (the
    benchmark surface of ``benchmarks/engine.py --scale``).  Un-jitted so
    callers compose with vmap before compiling; ``spec`` must be a
    device `ClassSpec` (``spec.device()``).  With ``scenario=True``,
    ``fr`` must be the `ScenarioRates` from `resolve_scenario_classes`.
    """
    base = _sparse_network_scan(m, C, T, init, fault=fault, scenario=scenario)
    if fault or scenario:
        return lambda key, mu, p, spec, fr: base(key, mu, p, spec, fr)[1:]
    return lambda key, mu, p, spec: base(key, mu, p, spec)[1:]


# ---------------------------------------------------------------------- #
# jnp control plane: exact Jackson analysis + Theorem-1 bound, traceable
# ---------------------------------------------------------------------- #
def mva_throughput_delays(mu, p, C: int, normalized: bool = True,
                          counts=None):
    """Exact (m, lam) of the closed network via Mean Value Analysis.

    The MVA recurrence over populations M = 1..C

        W = (1 + Q_{M-1}) / mu,   lam_M = M / (p . W),   Q_M = lam_M p W

    computes the arrival-theorem queue lengths Q_{C-1} and throughput
    Lambda(C) without normalizing constants — identical values to the
    Buzen pipeline in `jackson.JacksonNetwork` (locked in tests), but a
    C-step scan of O(n) vectorized ops that AD flows through cheaply.
    Returns ``(m, lam)``: delays in CS steps (Prop. 3 estimate, with the
    (C-1)/C Little's-law normalization by default) and the throughput.

    ``counts`` collapses identical nodes to speed classes: ``mu``/``p``
    are then (m,) class-level values (per-*node* probabilities) with
    ``counts[c]`` nodes per class, and the recurrence runs in O(m·C) —
    numerically identical to the dense recurrence on the expanded
    vectors, so n = 1e6 costs the same as n = m.
    """
    import jax
    import jax.numpy as jnp

    mu = jnp.asarray(mu)
    p = jnp.asarray(p)
    n = p.shape[0]
    w = jnp.ones(n, p.dtype) if counts is None else jnp.asarray(counts, p.dtype)

    def body(Q, M):
        W = (1.0 + Q) / mu
        lam = M / jnp.dot(w * p, W)
        return lam * p * W, lam

    Ms = jnp.arange(1, C + 1, dtype=p.dtype)
    Q_C, lams = jax.lax.scan(body, jnp.zeros(n, p.dtype), Ms)
    lam_C = lams[-1]
    if C == 1:
        Q_prev = jnp.zeros(n, p.dtype)
    else:
        # invert the last MVA step: Q_C = lam_C p (1 + Q_{C-1}) / mu
        Q_prev = mu * Q_C / (lam_C * p) - 1.0
    m = lam_C * (Q_prev + 1.0) / mu
    if normalized:
        m = m * (C - 1.0) / C
    return m, lam_C


def _class_weights(p, counts):
    """(weights, total n) for dense (counts=None) or class-collapsed sums."""
    import jax.numpy as jnp

    if counts is None:
        return jnp.ones_like(p), float(p.shape[0])
    w = jnp.asarray(counts, p.dtype)
    return w, float(int(np.sum(np.asarray(counts))))


def generalized_bound_jnp(eta, p, m, k: BoundConstants, counts=None):
    """G(p, eta) of Eq. (3) — jnp port of `theory.generalized_bound`.

    With ``counts``, p/m are (m,) class-level values and every sum over
    nodes becomes a counts-weighted sum over classes (O(m))."""
    import jax.numpy as jnp

    w, n = _class_weights(p, counts)
    n2 = n**2
    t1 = k.A / (eta * (k.T + 1))
    t2 = eta * k.L * k.B * jnp.sum(w / (n2 * p))
    t3 = eta**2 * k.L**2 * k.B * k.C * jnp.sum(w * m / (n2 * p**2))
    return t1 + t2 + t3


def optimal_eta_jnp(p, m, k: BoundConstants, newton_iters: int = 20,
                    counts=None):
    """argmin_eta G(p, eta) s.t. eta <= eta_max, traceable.

    The stationary point solves 2c eta^3 + b eta^2 = D (unique positive
    root); Newton from eta0 = cbrt(D / 2c) >= root converges monotonically
    (f is convex increasing on eta > 0).  The Theorem-1 cap
    min(a, b) mirrors `theory.eta_max_components`.  ``counts`` collapses
    the node sums to counts-weighted class sums.
    """
    import jax
    import jax.numpy as jnp

    w, n = _class_weights(p, counts)
    n2 = n**2
    D = k.A / (k.T + 1)
    b = k.L * k.B * jnp.sum(w / (n2 * p))
    c = k.L**2 * k.B * k.C * jnp.sum(w * m / (n2 * p**2))

    eta0 = jnp.cbrt(D / (2.0 * c))

    def newton(eta, _):
        f = 2.0 * c * eta**3 + b * eta**2 - D
        fp = 6.0 * c * eta**2 + 2.0 * b * eta
        return eta - f / fp, None

    eta, _ = jax.lax.scan(newton, eta0, None, length=newton_iters)
    growth = 1.0 + k.rho**2
    m_k = jnp.sum(w * m / (n2 * p**2))
    a_cap = 1.0 / jnp.sqrt(16.0 * k.L**2 * k.C * m_k * growth)
    b_cap = n2 / (8.0 * k.L * growth * jnp.sum(w / p))
    return jnp.minimum(eta, jnp.minimum(a_cap, b_cap))


@lru_cache(maxsize=32)
def _bound_value_and_grad(k_tuple, counts=None):
    import jax

    k = BoundConstants(*k_tuple)

    def objective(p, mu):
        m, _ = mva_throughput_delays(mu, p, k.C, counts=counts)
        eta = optimal_eta_jnp(p, m, k, counts=counts)
        return generalized_bound_jnp(eta, p, m, k, counts=counts)

    return jax.value_and_grad(objective)


def make_bound_value_and_grad(k: BoundConstants, counts=None):
    """(value, grad) of f(p) = G(p, eta*(p)) with delays from MVA — the jnp
    port of `sampling.bound_value_and_grad`.

    The gradient is exact AD through the MVA recurrence (the delay channel),
    the explicit 1/p terms, and eta*(p): when the cubic stationary point is
    interior the eta channel vanishes by the envelope theorem (dG/deta = 0
    there, so the Newton iterates' sensitivity is multiplied by ~0); when
    the cap is active, ``jnp.minimum`` routes the chain rule through the
    active branch — the same case split `sampling.bound_value_and_grad`
    does by hand.  Cached per (BoundConstants, counts); ``counts`` (a
    tuple of class sizes) switches everything to the O(m·C)
    class-collapsed form with (m,) per-node class probabilities.
    """
    if counts is not None:
        counts = tuple(int(c) for c in counts)
    return _bound_value_and_grad(
        (k.A, k.L, k.B, int(k.C), int(k.T), k.rho), counts
    )


def estimate_mu(comp, busy_t, prior_weight: float = 1.0,
                floor_frac: float = 1e-3):
    """Per-node service-rate MLE from observed (completions, busy time).

    While a node is busy its completions are Poisson(mu_i), so
    mu_i ~ comp_i / busy_i.  Nodes with little observed busy time shrink
    toward the busy-time-weighted global mean rate (``prior_weight``
    pseudo-completions at the global rate).

    Dead-node safety: a node that recorded zero completions *and* zero busy
    time (unavailable the whole window — or, with ``prior_weight = 0``, any
    idle node) used to produce ``0/0`` or a hard zero, which the MVA delay
    recurrence turns into inf/NaN delays and the control loop into NaN
    sampling weights.  The estimate is therefore floored at ``floor_frac``
    of the global mean rate (itself floored away from zero), so a dark node
    reports as "very slow but finite" and mirror descent pushes its p
    toward the floor instead of diverging.
    """
    import jax.numpy as jnp

    comp = comp.astype(jnp.float32)
    mu_bar = jnp.sum(comp) / jnp.maximum(jnp.sum(busy_t), 1e-20)
    mu_bar = jnp.maximum(mu_bar, 1e-8)  # zero completions everywhere
    est = (comp + prior_weight) / jnp.maximum(
        busy_t + prior_weight / mu_bar, 1e-20
    )
    return jnp.maximum(est, floor_frac * mu_bar)


def ctrl_refresh(
    p,
    comp,
    busy_t,
    k: BoundConstants,
    lr: float = 0.3,
    iters: int = 4,
    floor_scale: float = 1e-5,
    counts=None,
):
    """One adaptive-sampling refresh: re-optimize p from running estimates.

    Estimates per-node rates from the observed stream, then takes ``iters``
    exponentiated-gradient steps on the Theorem-1 bound (the same projected
    mirror-descent update as `sampling.optimize_general`, with the analytic
    jnp gradient).  Pure function of device values — traceable, vmappable.

    Control-plane safeguards (faults): `estimate_mu` floors dead-node rate
    estimates, non-finite gradient components are scrubbed to 0 (a single
    poisoned coordinate must not NaN the whole simplex), and each iterate is
    re-floored/renormalized — so nodes that went dark keep a small positive
    sampling weight (bounded importance scales) instead of p collapsing to
    NaN or exact zeros.

    With ``counts`` (a static tuple of class sizes), everything runs in
    the O(m·C) class-collapsed form: ``p`` is the (m,) per-node
    probability by class, ``comp``/``busy_t`` the per-class aggregates
    (the pooled MLE is strictly better-conditioned than per-node), and
    the exponentiated-gradient step operates on the class *masses*
    ``z = counts · p`` (the simplex the collapsed problem lives on).
    Within a class the dense optimum is symmetric, so the collapsed
    optimum is exact — the adaptive loop at n = 1e6 costs the same as
    n = m.
    """
    import jax
    import jax.numpy as jnp

    vg = make_bound_value_and_grad(k, counts=counts)
    if counts is None:
        w = jnp.ones_like(p)
        n = p.shape[0]
    else:
        w = jnp.asarray(counts, p.dtype)
        n = int(np.sum(np.asarray(counts)))
    floor = floor_scale / n

    def one(p, _):
        _, g = vg(p, mu_hat)
        g = jnp.where(jnp.isfinite(g), g, 0.0)
        z = w * p
        gz = g / w
        gz = gz - jnp.dot(gz, z)
        z = z * jnp.exp(-lr * gz / (jnp.max(jnp.abs(gz)) + 1e-12))
        z = jnp.where(jnp.isfinite(z), z, w * floor)
        z = jnp.maximum(z, w * floor)
        return (z / jnp.sum(z)) / w, None

    with jax.named_scope(obs.CTRL):
        mu_hat = estimate_mu(comp, busy_t)
        p, _ = jax.lax.scan(one, p, None, length=iters)
    return p
