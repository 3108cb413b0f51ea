"""Resilient serving plane: an open inference queue coupled to the closed
training network.

The paper models *training* as a closed Jackson network; the production
system must also *serve* the model it trains.  This module models inference
requests as an **open** Poisson stream merged into the engine's CTMC event
race (`stream_device.merged_stream_step`): every competing clock — client
completions, faults, request arrivals, service completions, per-request
deadline timeouts, retry-backoff releases — is exponential, so the merged
system stays a CTMC and the engine's one-uniform-pair-per-event machinery
survives unchanged in law.

Robustness envelope (`ServingConfig`):

  * **token-bucket admission control** — ``bucket_rate``/``bucket_cap``
    tokens refill lazily at arrival epochs; an arrival without a token is
    shed.  Composes with **load shedding above a queue-depth threshold**
    (``queue_cap``): arrivals beyond it are shed, so queue depth — and
    therefore memory — is bounded no matter the overload factor.
  * **deadline timeouts with capped, jittered exponential backoff** — each
    queued request carries an ``Exp(1/deadline)`` timeout clock (the
    memoryless deadline keeps the CTMC exact); on firing, the request
    either retries (attempt < ``max_retries``) after an
    ``Exp(1/delay)`` backoff with ``delay = min(backoff_base *
    2**attempt, backoff_cap)`` — the exponential holding time *is* the
    jitter — or is evicted and counted ``timed_out``.
  * **degraded-mode snapshot serving** — requests are answered from the
    engine's snapshot ring (one ``(C, *leaf.shape)`` buffer per parameter
    leaf) at the **known-good pointer**: the ring row written by the most recent *accepted* update.  Divergence-
    guard-rejected and fault-masked updates never advance the pointer (and
    never enter ``w`` at all), so a guard trip degrades the served model to
    the last known-good iterate instead of serving poison; the per-serve
    staleness ``k - kg_step`` is histogrammed so the degradation is
    observable.

Everything is fixed-shape and O(R) per serve event (R = ``table_cap``), so
the serve plane rides inside `lax.scan` and checkpoints bitwise as part of
the engine carry.  `simulate_serving_host` is the host-side oracle: the
serving *marginal* of the merged CTMC is independent of the training state
(superposition of independent exponential clocks), so a standalone
event-driven simulation of the same law locks parity for the device plane.
"""
from __future__ import annotations

import heapq
from dataclasses import dataclass
from typing import Any, NamedTuple

import numpy as np

__all__ = [
    "ServingConfig",
    "ServeState",
    "ServeStats",
    "serve_init",
    "serve_stats_init",
    "serve_total_rate",
    "serve_depth",
    "serve_time_step",
    "serve_apply",
    "backoff_delay",
    "hist_bucket",
    "hist_quantile",
    "serve_extras",
    "drain_counters",
    "simulate_serving_host",
]

#: number of log2 buckets in the sojourn / staleness histograms
HIST_BUCKETS = 24
#: sojourn histogram: bucket i covers [2**(i + HIST_LO), 2**(i + 1 + HIST_LO))
HIST_LO = -10


@dataclass(frozen=True)
class ServingConfig:
    """The serving plane's traffic and robustness envelope.

    ``arrival_rate`` (lambda) and ``serve_rate`` (nu) are in the same time
    unit as the training network's ``mu``; ``deadline`` is the *mean* of
    the exponential per-request deadline; ``backoff_base``/``backoff_cap``
    bound the mean retry delay ``min(base * 2**attempt, cap)``.
    ``queue_cap`` is the admission threshold on in-system depth;
    ``table_cap`` (>= queue_cap; 0 = auto ``queue_cap + max_retries + 1``)
    sizes the static request table, which also holds backoff parkers.
    ``bucket_rate <= 0`` disables the token bucket (depth-only admission).
    """

    arrival_rate: float = 0.0
    serve_rate: float = 1.0
    queue_cap: int = 8
    bucket_rate: float = 0.0
    bucket_cap: float = 8.0
    deadline: float = 0.0
    max_retries: int = 2
    backoff_base: float = 0.25
    backoff_cap: float = 2.0
    table_cap: int = 0

    @property
    def enabled(self) -> bool:
        return float(self.arrival_rate) > 0.0

    @property
    def R(self) -> int:
        """Static request-table capacity."""
        if int(self.table_cap) > 0:
            return int(self.table_cap)
        return int(self.queue_cap) + int(self.max_retries) + 1

    def validate(self) -> "ServingConfig":
        if self.enabled:
            if float(self.serve_rate) <= 0:
                raise ValueError("serve_rate must be > 0")
            if int(self.queue_cap) < 1:
                raise ValueError("queue_cap must be >= 1")
            if self.R < int(self.queue_cap):
                raise ValueError("table_cap must be >= queue_cap")
            if int(self.max_retries) and float(self.deadline) <= 0:
                # retries only fire off deadline timeouts
                pass
            if float(self.backoff_base) <= 0 or float(self.backoff_cap) <= 0:
                raise ValueError("backoff_base/backoff_cap must be > 0")
        return self

    def cache_key(self):
        return (
            float(self.arrival_rate), float(self.serve_rate),
            int(self.queue_cap), float(self.bucket_rate),
            float(self.bucket_cap), float(self.deadline),
            int(self.max_retries), float(self.backoff_base),
            float(self.backoff_cap), int(self.R),
        )


def backoff_delay(cfg: ServingConfig, attempt):
    """Mean backoff delay before retry number ``attempt`` (1-based):
    ``min(backoff_base * 2**(attempt - 1), backoff_cap)`` — capped
    exponential backoff.  Works on numpy or jnp operands."""
    import jax.numpy as jnp

    a = jnp.maximum(jnp.asarray(attempt, jnp.float32) - 1.0, 0.0)
    return jnp.minimum(
        jnp.float32(cfg.backoff_base) * jnp.exp2(a),
        jnp.float32(cfg.backoff_cap),
    )


# request states in ServeState.stt
_FREE, _QUEUED, _BACKOFF = 0, 1, 2
_SEQ_MAX = np.int32(2**31 - 1)


class ServeState(NamedTuple):
    """Device state of the open serving queue (one scenario).

    ``stt`` is the per-slot request state (0 free / 1 queued / 2 in
    backoff); ``seq`` the FIFO order stamp (service pops the minimum);
    ``kg_slot``/``kg_step`` the known-good snapshot pointer — the ring row
    and server step of the most recent *accepted* training update.

    ``depth`` and ``cdf`` are derived caches of the table — the in-system
    count and the cumulative sum of the (2R + 2,) competing-clock rate
    vector (`_rates`), so ``cdf[-1] == serve_total_rate``.  The table
    mutates only inside `serve_apply`, so both are recomputed once at its
    commit and nowhere else; the engine reads them every merged step (the
    race needs the total rate, the queue-depth time integral needs
    ``depth``) without rebuilding the rate vector on train events.
    """

    t_arr: Any     # (R,) float32 — first-arrival time of the request
    attempt: Any   # (R,) int32 — retries consumed (0 on first attempt)
    stt: Any       # (R,) int32 — _FREE / _QUEUED / _BACKOFF
    seq: Any       # (R,) int32 — FIFO stamp (re-stamped on retry release)
    next_seq: Any  # () int32
    tokens: Any    # () float32 — token bucket level
    t_tok: Any     # () float32 — last lazy bucket refill time
    depth: Any     # () int32 — cached in-system count (== serve_depth)
    cdf: Any       # (2R+2,) float32 — cached cumsum of `_rates`
    kg_slot: Any   # () int32 — snapshot-ring row of the known-good iterate
    kg_step: Any   # () int32 — server step that wrote it


class ServeStats(NamedTuple):
    """Serving observables; float accumulators are Kahan pairs."""

    arrivals: Any    # () int32 — every Poisson arrival, admitted or not
    served: Any      # () int32
    shed: Any        # () int32 — rejected at admission (bucket or depth)
    timed_out: Any   # () int32 — evicted after exhausting the retry budget
    retried: Any     # () int32 — deadline hits that re-entered via backoff
    sojourn: Any     # () float32 — Kahan sum of served sojourn times
    sojourn_c: Any
    qdepth_tw: Any   # () float32 — time integral of in-system depth
    qdepth_tw_c: Any
    qdepth_max: Any  # () int32 — max in-system depth ever observed
    sojourn_hist: Any  # (HIST_BUCKETS,) int32 — log2 sojourn buckets
    stale_hist: Any    # (HIST_BUCKETS,) int32 — log2 served-staleness buckets
    checksum: Any    # () float32 — Kahan sum over serves of the served
    checksum_c: Any  # snapshot row's mean — the serving *read path*


def serve_init(cfg: ServingConfig) -> ServeState:
    import jax.numpy as jnp

    R = cfg.R
    return ServeState(
        t_arr=jnp.zeros(R, jnp.float32),
        attempt=jnp.zeros(R, jnp.int32),
        stt=jnp.zeros(R, jnp.int32),
        seq=jnp.zeros(R, jnp.int32),
        next_seq=jnp.int32(0),
        tokens=jnp.float32(cfg.bucket_cap),
        t_tok=jnp.float32(0.0),
        depth=jnp.int32(0),
        # empty table: only the arrival clock runs, so the cumulative rate
        # vector is flat at lambda — bitwise what cumsum(_rates) gives
        cdf=jnp.full(2 * R + 2, cfg.arrival_rate, jnp.float32),
        kg_slot=jnp.int32(0),
        kg_step=jnp.int32(0),
    )


def serve_stats_init() -> ServeStats:
    import jax.numpy as jnp

    z32 = jnp.int32(0)
    zf = jnp.float32(0.0)
    zh = jnp.zeros(HIST_BUCKETS, jnp.int32)
    return ServeStats(
        arrivals=z32, served=z32, shed=z32, timed_out=z32, retried=z32,
        sojourn=zf, sojourn_c=zf, qdepth_tw=zf, qdepth_tw_c=zf,
        qdepth_max=z32, sojourn_hist=zh, stale_hist=zh,
        checksum=zf, checksum_c=zf,
    )


def _rates(cfg: ServingConfig, sv: ServeState):
    """The serving side's (2R + 2,) competing-clock rate vector:
    ``[arrival | service | R deadline clocks | R backoff releases]``."""
    import jax.numpy as jnp

    queued = sv.stt == _QUEUED
    r_arr = jnp.float32(cfg.arrival_rate)[None]
    r_srv = jnp.where(jnp.any(queued), jnp.float32(cfg.serve_rate), 0.0)[None]
    if float(cfg.deadline) > 0:
        r_tmo = jnp.where(queued, jnp.float32(1.0 / cfg.deadline), 0.0)
    else:
        r_tmo = jnp.zeros(cfg.R, jnp.float32)
    r_rel = jnp.where(
        sv.stt == _BACKOFF, 1.0 / backoff_delay(cfg, sv.attempt), 0.0
    )
    return jnp.concatenate([r_arr, r_srv, r_tmo, r_rel])


def serve_total_rate(cfg: ServingConfig, sv: ServeState):
    """Total serving-side event rate — the open stream's share of the
    merged race (`stream_device.merged_stream_step`'s ``ext_rate``)."""
    return _rates(cfg, sv).sum()


def serve_depth(sv: ServeState):
    """In-system request count (queued + backoff)."""
    import jax.numpy as jnp

    return jnp.sum((sv.stt != _FREE).astype(jnp.int32))


def serve_time_step(stats: ServeStats, sv: ServeState, dt) -> ServeStats:
    """Time-integral accumulation over one merged event of duration ``dt``
    (training *and* serving events both advance the clock, so this runs
    unconditionally — outside the serve/train branch).  Uses the cached
    ``sv.depth`` so train events pay only scalar ops, no O(R) reduction."""
    import jax.numpy as jnp

    from .stream_device import kahan_add

    d = sv.depth
    qtw, qtw_c = kahan_add(
        stats.qdepth_tw, stats.qdepth_tw_c, d.astype(jnp.float32) * dt
    )
    return stats._replace(
        qdepth_tw=qtw, qdepth_tw_c=qtw_c,
        qdepth_max=jnp.maximum(stats.qdepth_max, d),
    )


def hist_bucket(x, lo: int = HIST_LO):
    """log2 bucket index of a positive float32 (clipped into range)."""
    import jax.numpy as jnp

    xb = jnp.maximum(jnp.asarray(x, jnp.float32), 1e-30)
    b = jnp.floor(jnp.log2(xb)).astype(jnp.int32) - lo
    return jnp.clip(b, 0, HIST_BUCKETS - 1)


def _row_mean(snaps, s):
    """Mean of ring row ``s`` over every parameter, in float32: the sum
    over the ring's leaves divided by their total size P."""
    import jax
    import jax.numpy as jnp

    leaves = jax.tree_util.tree_leaves(snaps)
    total = sum(
        jnp.sum(jax.lax.dynamic_index_in_dim(b, s, 0, keepdims=False)
                .astype(jnp.float32))
        for b in leaves
    )
    return total / sum(int(np.prod(b.shape[1:])) for b in leaves)


def serve_apply(cfg: ServingConfig, sv: ServeState, stats: ServeStats,
                u, t, k, snaps, live=True):
    """Resolve one serving event: which clock fired, and its transition.

    ``u`` is the conditional uniform from the merged race (exact in law),
    ``t`` the post-event clock, ``k`` the server step, ``snaps`` the
    snapshot ring (a pytree of ``(C, ...)`` buffers).  Everything is masked scatters — no data-
    dependent control flow — so the engine runs it *unconditionally* every
    merged step with ``live = is_ext``: when ``live`` is False every
    transition flag masks off and the call is an exact no-op on ``sv`` /
    ``stats`` (a `lax.cond` would skip the ~30 small ops on train events,
    but marshaling the ~25 serve-state buffers through the conditional
    costs more than the ops themselves at hot-loop event rates).
    Returns ``(sv', stats')``.

    The clock draw is an inverse-CDF ``searchsorted`` over the (2R + 2,)
    rate vector: ``side='right'`` skips zero-rate categories (their cdf
    step is flat), and the one rounding hazard — ``u * total`` landing
    past the last positive entry — is caught by the per-category state
    guards below, which turn any impossible draw into a no-op self-loop.
    Exactly one transition fires per event, so the request table commits
    through a single fused scatter per column instead of one per clock
    class (at the hot-loop event rate, scatter thunks are the cost).
    """
    import jax
    import jax.numpy as jnp

    from .stream_device import kahan_add

    R = cfg.R
    cdf = sv.cdf  # cached cumsum of `_rates` over the pre-event table
    idx = jnp.searchsorted(cdf, u * cdf[-1], side="right").astype(jnp.int32)

    lv = jnp.asarray(live, bool)
    stt0 = sv.stt
    queued = stt0 == _QUEUED
    it_raw = jnp.clip(idx - 2, 0, R - 1)
    ir_raw = jnp.clip(idx - (R + 2), 0, R - 1)
    is_arr = lv & (idx == 0)
    is_srv = lv & (idx == 1) & jnp.any(queued)
    is_tmo = lv & (idx >= 2) & (idx < R + 2) & (stt0[it_raw] == _QUEUED)
    is_rel = (lv & (idx >= R + 2) & (idx < 2 * R + 2)
              & (stt0[ir_raw] == _BACKOFF))

    # ---------------- arrival: token bucket + depth admission ----------
    # lazy refill at arrival epochs only (the bucket is read nowhere else)
    tok_ref = jnp.minimum(
        jnp.float32(cfg.bucket_cap),
        sv.tokens + jnp.float32(cfg.bucket_rate) * (t - sv.t_tok),
    )
    has_free = jnp.any(stt0 == _FREE)
    has_token = (tok_ref >= 1.0) | (float(cfg.bucket_rate) <= 0.0)
    admit = is_arr & has_free & (sv.depth < cfg.queue_cap) & has_token
    i_free = jnp.argmax(stt0 == _FREE).astype(jnp.int32)
    tokens = jnp.where(
        is_arr, tok_ref - jnp.where(admit, 1.0, 0.0), sv.tokens
    )
    t_tok = jnp.where(is_arr, t, sv.t_tok)

    # ---------------- service completion: FIFO head, known-good read ---
    i_head = jnp.argmin(
        jnp.where(queued, sv.seq, _SEQ_MAX)
    ).astype(jnp.int32)
    sj = t - sv.t_arr[i_head]
    # the read path: answer from the known-good snapshot row.  The row
    # mean (over all P parameters, in float32) is the servable observable — a guard-rejected (non-finite /
    # exploded) update reaching it would poison this checksum, which the
    # never-served property test asserts stays finite.  Scalar-output
    # cond: the O(P) row reduction runs only when a serve completes, and
    # `snaps` stays a read-only closure capture (no buffer marshaling).
    row_mean = jax.lax.cond(
        is_srv,
        lambda: _row_mean(snaps, sv.kg_slot),
        lambda: jnp.float32(0.0),
    )
    staleness = (k - sv.kg_step).astype(jnp.float32)

    # ---------------- deadline timeout: retry via backoff, or evict ----
    exhausted = sv.attempt[it_raw] >= cfg.max_retries
    evict = is_tmo & exhausted
    retry = is_tmo & ~exhausted

    # ---------------- commit: one fused scatter per table column -------
    # index R is out of bounds -> drop (shed arrivals and guarded
    # impossible draws leave the table untouched)
    i_upd = jnp.where(
        admit, i_free,
        jnp.where(is_srv, i_head,
                  jnp.where(is_tmo, it_raw,
                            jnp.where(is_rel, ir_raw, R))),
    )
    new_stt = jnp.where(
        admit | is_rel, jnp.int32(_QUEUED),
        jnp.where(retry, jnp.int32(_BACKOFF), jnp.int32(_FREE)),
    )
    stt = stt0.at[i_upd].set(new_stt, mode="drop")
    i_seq = jnp.where(admit | is_rel, i_upd, R)
    seq = sv.seq.at[i_seq].set(sv.next_seq, mode="drop")
    i_att = jnp.where(admit | retry, i_upd, R)
    att_val = jnp.where(admit, jnp.int32(0), sv.attempt[it_raw] + 1)
    attempt = sv.attempt.at[i_att].set(att_val, mode="drop")
    ia = jnp.where(admit, i_free, R)
    t_arr = sv.t_arr.at[ia].set(t, mode="drop")

    next_seq = sv.next_seq + (admit | is_rel).astype(jnp.int32)
    depth = (sv.depth + admit.astype(jnp.int32)
             - (is_srv | evict).astype(jnp.int32))
    sv = ServeState(
        t_arr=t_arr, attempt=attempt, stt=stt, seq=seq, next_seq=next_seq,
        tokens=tokens, t_tok=t_tok, depth=depth, cdf=sv.cdf,
        kg_slot=sv.kg_slot, kg_step=sv.kg_step,
    )
    # refresh the rate cache from the committed table — the one place the
    # table mutates.  On a masked (``~live``) call the table is unchanged
    # and the recompute reproduces the cache bitwise (same op sequence).
    sv = sv._replace(cdf=jnp.cumsum(_rates(cfg, sv)))

    srv_i = is_srv.astype(jnp.int32)
    sojourn, sojourn_c = kahan_add(
        stats.sojourn, stats.sojourn_c, jnp.where(is_srv, sj, 0.0)
    )
    checksum, checksum_c = kahan_add(
        stats.checksum, stats.checksum_c, jnp.where(is_srv, row_mean, 0.0)
    )
    hb = jnp.where(is_srv, hist_bucket(sj), HIST_BUCKETS)
    sb = jnp.where(is_srv, hist_bucket(jnp.maximum(staleness, 1.0), lo=0),
                   HIST_BUCKETS)
    stats = stats._replace(
        arrivals=stats.arrivals + is_arr.astype(jnp.int32),
        served=stats.served + srv_i,
        shed=stats.shed + (is_arr & ~admit).astype(jnp.int32),
        timed_out=stats.timed_out + (is_tmo & exhausted).astype(jnp.int32),
        retried=stats.retried + (is_tmo & ~exhausted).astype(jnp.int32),
        sojourn=sojourn, sojourn_c=sojourn_c,
        sojourn_hist=stats.sojourn_hist.at[hb].add(1, mode="drop"),
        stale_hist=stats.stale_hist.at[sb].add(1, mode="drop"),
        checksum=checksum, checksum_c=checksum_c,
    )
    return sv, stats


# ------------------------------------------------------------------ #
# host-side readout
# ------------------------------------------------------------------ #
def hist_quantile(hist, q: float, lo: int = HIST_LO) -> float:
    """Approximate quantile from a log2-bucket histogram (geometric
    midpoint of the bucket where the cumulative mass crosses ``q``)."""
    h = np.asarray(hist, np.float64)
    total = h.sum()
    if total <= 0:
        return float("nan")
    cum = np.cumsum(h)
    b = int(np.searchsorted(cum, q * total))
    b = min(b, len(h) - 1)
    return float(2.0 ** (b + lo + 0.5))


def drain_counters(sv: ServeState, stats: ServeStats) -> dict:
    """End-of-run drain: requests still in flight when the run stops are
    flushed into ``timed_out`` (server-shutdown semantics) and reported
    separately as ``pending``.  Conservation then holds *exactly*:
    ``served + shed + timed_out == arrivals``."""
    pending = int(np.sum(np.asarray(sv.stt) != _FREE))
    out = {
        "arrivals": int(stats.arrivals),
        "served": int(stats.served),
        "shed": int(stats.shed),
        "timed_out": int(stats.timed_out) + pending,
        "retried": int(stats.retried),
        "pending_drained": pending,
    }
    return out


def serve_extras(cfg: ServingConfig, sv: ServeState, stats: ServeStats,
                 t_final) -> dict:
    """Host-readable serving extras dict (counters, quantiles, SLO view)."""
    from .stream_device import kahan_value

    out = drain_counters(sv, stats)
    served = max(out["served"], 1)
    t = float(np.asarray(t_final, np.float64))
    out.update(
        sojourn_mean=float(kahan_value(stats.sojourn, stats.sojourn_c))
        / served,
        sojourn_p50=hist_quantile(stats.sojourn_hist, 0.50),
        sojourn_p99=hist_quantile(stats.sojourn_hist, 0.99),
        staleness_p50=hist_quantile(stats.stale_hist, 0.50, lo=0),
        staleness_p99=hist_quantile(stats.stale_hist, 0.99, lo=0),
        qdepth_mean=float(kahan_value(stats.qdepth_tw, stats.qdepth_tw_c))
        / max(t, 1e-30),
        qdepth_max=int(stats.qdepth_max),
        checksum=float(kahan_value(stats.checksum, stats.checksum_c)),
        kg_step=int(sv.kg_step),
        shed_frac=out["shed"] / max(out["arrivals"], 1),
    )
    return out


# ------------------------------------------------------------------ #
# host oracle: the serving marginal as a standalone event-driven sim
# ------------------------------------------------------------------ #
def simulate_serving_host(cfg: ServingConfig, horizon: float,
                          seed: int = 0) -> dict:
    """Exact event-driven simulation of the serving plane's marginal law.

    The merged CTMC's serving marginal is independent of the training
    state (independent exponential clocks superpose), so this standalone
    heap simulation follows the *same law* as the device plane inside the
    engine — the parity oracle for tests/test_serving.py.  Returns the
    same counters as `drain_counters` plus the served sojourn list.
    """
    cfg.validate()
    rng = np.random.default_rng(seed)
    R = cfg.R
    lam, nu = float(cfg.arrival_rate), float(cfg.serve_rate)
    arrivals = served = shed = timed_out = retried = 0
    sojourns: list[float] = []
    # request table mirrors ServeState; events live on one heap.  Each
    # queued request re-arms its own Exp(1/deadline) clock; stale heap
    # entries are invalidated by an epoch stamp per slot.
    stt = np.zeros(R, np.int64)
    t_arr = np.zeros(R)
    attempt = np.zeros(R, np.int64)
    seq = np.zeros(R, np.int64)
    epoch = np.zeros(R, np.int64)
    next_seq = 0
    heap: list[tuple[float, int, int, int]] = []  # (t, kind, slot, epoch)
    A_ARR, A_SRV, A_TMO, A_REL = 0, 1, 2, 3
    heapq.heappush(heap, (rng.exponential(1.0 / lam), A_ARR, -1, 0))
    srv_busy_since: float | None = None
    srv_slot = -1
    t = 0.0

    def arm_service():
        nonlocal srv_slot
        q = [i for i in range(R) if stt[i] == _QUEUED]
        if not q:
            srv_slot = -1
            return
        i = min(q, key=lambda i: seq[i])
        if srv_slot != i:
            srv_slot = i
            # memoryless: re-arming on a new head is law-preserving
            heapq.heappush(
                heap, (t + rng.exponential(1.0 / nu), A_SRV, i, epoch[i])
            )

    tokens = float(cfg.bucket_cap)
    t_tok = 0.0
    while heap:
        te, kind, i, ep = heapq.heappop(heap)
        if te > horizon:
            break
        t = te
        if kind == A_ARR:
            heapq.heappush(heap, (t + rng.exponential(1.0 / lam), A_ARR, -1, 0))
            arrivals += 1
            if cfg.bucket_rate > 0:
                tokens = min(cfg.bucket_cap, tokens
                             + cfg.bucket_rate * (t - t_tok))
                t_tok = t
            depth = int(np.sum(stt != _FREE))
            free = np.flatnonzero(stt == _FREE)
            ok = (len(free) > 0 and depth < cfg.queue_cap
                  and (cfg.bucket_rate <= 0 or tokens >= 1.0))
            if not ok:
                shed += 1
                continue
            if cfg.bucket_rate > 0:
                tokens -= 1.0
            s = int(free[0])
            stt[s], t_arr[s], attempt[s] = _QUEUED, t, 0
            seq[s] = next_seq
            next_seq += 1
            epoch[s] += 1
            if cfg.deadline > 0:
                heapq.heappush(
                    heap,
                    (t + rng.exponential(cfg.deadline), A_TMO, s, epoch[s]),
                )
            arm_service()
        elif kind == A_SRV:
            if i != srv_slot or stt[i] != _QUEUED or ep != epoch[i]:
                continue  # stale clock (head changed / request left)
            served += 1
            sojourns.append(t - t_arr[i])
            stt[i] = _FREE
            epoch[i] += 1
            srv_slot = -1
            arm_service()
        elif kind == A_TMO:
            if stt[i] != _QUEUED or ep != epoch[i]:
                continue
            epoch[i] += 1
            if attempt[i] >= cfg.max_retries:
                stt[i] = _FREE
                timed_out += 1
            else:
                retried += 1
                attempt[i] += 1
                stt[i] = _BACKOFF
                d = min(cfg.backoff_base * 2.0 ** (attempt[i] - 1),
                        cfg.backoff_cap)
                heapq.heappush(
                    heap, (t + rng.exponential(d), A_REL, i, epoch[i])
                )
            if i == srv_slot:
                srv_slot = -1
            arm_service()
        else:  # A_REL
            if stt[i] != _BACKOFF or ep != epoch[i]:
                continue
            epoch[i] += 1
            stt[i] = _QUEUED
            seq[i] = next_seq
            next_seq += 1
            if cfg.deadline > 0:
                heapq.heappush(
                    heap,
                    (t + rng.exponential(cfg.deadline), A_TMO, i, epoch[i]),
                )
            arm_service()
    pending = int(np.sum(stt != _FREE))
    return {
        "arrivals": arrivals,
        "served": served,
        "shed": shed,
        "timed_out": timed_out + pending,
        "retried": retried,
        "pending_drained": pending,
        "sojourns": sojourns,
    }
