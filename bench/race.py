"""Reference for the event race: the closed Jackson network, step by step.

The program draws each call's events on the device from one key.  This
module reads the same uniforms from that key and plays the network in
float64 with plain per-node FIFO queues:

* the completing client J_k is the inverse-CDF draw of ``u_race[k]`` over
  the rates ``mu_i * 1{X_i > 0}``; the time advances by the exponential
  holding time ``-log1p(-u_exp[k]) / sum(rates)``;
* the oldest task at J_k completes; its ring slot is freed and re-used by the
  newly dispatched task, which goes to K_k, the inverse-CDF draw of
  ``u_disp[k]`` over p; its staleness is k minus the step that dispatched it;
* the importance scale of the update is ``eta / (n p_{J_k})``.

It checks the program's events of a call against that, one by one, and
counts every event the law does not allow (or, for the control, which runs no
program, makes the reference's own events).  Where a draw lies within
float32 rounding (``TIE``, relative to the total mass) of a boundary
between two clients, either client is right, and the replay follows the
program's.  The time of each step and the per-client completion counts and
mean staleness that the timed call itself returned are checked against the
replay too, which ties the checked events to that call.
"""
from __future__ import annotations

from collections import deque
from dataclasses import dataclass

import numpy as np

TIE = 1e-5        # relative width of a boundary within which both sides are right
# the program's clock adds float32 holding times -log1p(-u)/rate; on the TPU
# its log1p of a small argument was measured 2.5e-4 off (relative), so each
# step may add TIME_RTOL of its holding time plus TIME_ATOL / rate
TIME_RTOL = 1e-3
TIME_ATOL = 1e-6
MEAN_RTOL = 1e-5  # float32 delay_sum / comp against the exact ratio


@dataclass
class Events:
    """A checked call: what the reference replays the weights along."""

    J: np.ndarray       # (T,) completing client
    slot: np.ndarray    # (T,) ring slot of the completing task
    k: np.ndarray       # (T,) server step
    scale: np.ndarray   # (T,) importance-weighted step size
    faults: int         # events, times and per-client totals the law forbids
    ties: int           # draws within TIE of a boundary (program followed)
    where: list = None  # the first faults: (step, what, program's, reference's)


def uniforms(call_seed: int, n: int, C: int, T: int):
    """``(init_nodes, u_race, u_exp, u_disp)`` of the call keyed by ``call_seed``:
    the key splits four ways; C distinct initial clients are the head of a
    random permutation, then one uniform per step for each draw."""
    import jax

    k_init, k_race, k_exp, k_disp = jax.random.split(jax.random.PRNGKey(call_seed), 4)
    nodes = np.asarray(jax.random.permutation(k_init, n)[:C], np.int64)
    u = [np.asarray(jax.random.uniform(k, (T,)), np.float64) for k in (k_race, k_exp, k_disp)]
    return nodes, u[0], u[1], u[2]


def _draw(weights: np.ndarray, u: float):
    """Inverse-CDF index of ``u`` over ``weights`` and the relative distance
    of ``u * total`` to the nearest inner boundary between positive weights."""
    cum = np.cumsum(weights)
    tot = cum[-1]
    x = u * tot
    i = int(np.searchsorted(cum, x, side="right"))
    pos = np.flatnonzero(weights > 0)
    i = min(i, int(pos[-1]))
    inner = cum[pos[:-1]]
    margin = float(np.min(np.abs(inner - x)) / tot) if inner.size else 1.0
    return i, margin, tot


def check_call(prog, mu, p, C: int, eta: float, call_seed: int,
               times=None, comp=None, mean_delays=None, T: int | None = None) -> Events:
    """Check one call's events, or with ``prog`` None make the reference's own.

    ``prog`` holds the program's stream of the call (``J``, ``K``, ``slot``,
    ``delay_steps``, ``init_nodes``); ``times``, ``comp`` and ``mean_delays``
    are what the timed call returned (per-step clock, completions and mean
    staleness per client).  Without ``prog`` (the control, which runs no
    program) there is nothing to check or follow, and ``T`` gives the length.
    """
    mu = np.asarray(mu, np.float64)
    p = np.asarray(p, np.float64)
    n = mu.size
    T = int(np.asarray(prog.J).size) if prog is not None else int(T)
    nodes, u_race, u_exp, u_disp = uniforms(call_seed, n, C, T)
    ties = 0
    where = []

    def fault(k, what, got, want):
        where.append((int(k), what, float(got), float(want)))

    if prog is not None and not np.array_equal(np.asarray(prog.init_nodes), nodes):
        fault(-1, "init_nodes", 0, 0)
    queues = [deque() for _ in range(n)]
    for s, i in enumerate(nodes):
        queues[i].append(s)
    occ = np.array([len(q) for q in queues], np.int64)
    slot_step = np.zeros(C, np.int64)
    J = np.empty(T, np.int64)
    S = np.empty(T, np.int64)
    comp_ref = np.zeros(n, np.int64)
    delay_ref = np.zeros(n, np.int64)
    t = err = 0.0
    for k in range(T):
        rates = np.where(occ > 0, mu, 0.0)
        j, m_j, tot = _draw(rates, u_race[k])
        kn, m_k, _ = _draw(p, u_disp[k])
        if prog is not None:
            jp, kp = int(prog.J[k]), int(prog.K[k])
            if jp != j:
                if occ[jp] > 0 and m_j < TIE:
                    ties, j = ties + 1, jp
                else:
                    fault(k, "J", jp, j)
            if kp != kn:
                if p[kp] > 0 and m_k < TIE:
                    ties, kn = ties + 1, kp
                else:
                    fault(k, "K", kp, kn)
        s = queues[j].popleft()
        delay = k - slot_step[s]
        if prog is not None:
            if int(prog.slot[k]) != s:
                fault(k, "slot", prog.slot[k], s)
            if int(prog.delay_steps[k]) != delay:
                fault(k, "delay", prog.delay_steps[k], delay)
            dt = -np.log1p(-u_exp[k]) / tot
            t += dt
            err += TIME_RTOL * dt + TIME_ATOL / tot
            if abs(float(times[k]) - t) > err:
                fault(k, "time", times[k], t)
        comp_ref[j] += 1
        delay_ref[j] += delay
        slot_step[s] = k + 1
        occ[j] -= 1
        queues[kn].append(s)
        occ[kn] += 1
        J[k], S[k] = j, s
    if prog is not None:
        comp = np.asarray(comp, np.int64)
        for i in np.flatnonzero(comp != comp_ref):
            fault(T, f"comp[{i}]", comp[i], comp_ref[i])
        mean_ref = delay_ref / np.maximum(comp_ref, 1)
        mean = np.asarray(mean_delays, np.float64)
        for i in np.flatnonzero(np.abs(mean - mean_ref) > MEAN_RTOL * np.maximum(mean_ref, 1.0)):
            fault(T, f"mean_delay[{i}]", mean[i], mean_ref[i])
    return Events(J=J, slot=S, k=np.arange(T), scale=eta / (n * p[J]), faults=len(where),
                  ties=ties, where=where[:8])
