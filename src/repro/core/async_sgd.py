"""Generalized AsyncSGD (Algorithm 1) and asynchronous/synchronous baselines.

The server algorithms are written against an abstract `GradientSource`
(anything that can produce a stochastic gradient for client i at parameters
w), so they run identically over:
  * toy quadratic problems (tests),
  * jitted JAX models on federated data shards (repro.fl),
  * sharded multi-pod train steps (repro.launch.train).

Faithfulness notes
------------------
* Line 10 of Algorithm 1:  w_{k+1} = w_k - eta/(n p_{J_k}) * g_{J_k}(w_{I_k})
  — the gradient is computed *at the dispatch-time parameters* w_{I_k}.  We
  snapshot parameters per in-flight task (C snapshots live at any time).
* Event timing follows the closed Jackson network (repro.core.queue_sim):
  completions J_k, sampling K_{k+1} ~ p, FIFO queues per client.
* The virtual-iterate sequence mu_k (Eq. 4) is tracked on demand to expose
  the Lemma-9 invariant |G_k| = C - 1 in tests.

Engines
-------
Each async server loop runs through one of two interchangeable engines
(`ServerConfig.engine`):
  * "python" — the per-event reference loop below (the parity oracle);
  * "scan"   — the compiled device-resident engine (repro.core.engine_scan):
    the event stream is pre-simulated on the host and Algorithm 1 replays
    as a single `jax.lax.scan` over a stacked snapshot ring buffer.
Identical (seed, block) => identical event stream => iterates agree to
float-associativity tolerance (locked in tests/test_engine.py).
"""
from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Protocol

import numpy as np

from .. import obs
from .queue_sim import (
    KIND_COMPLETE,
    KIND_FLIP,
    KIND_STAGE,
    ClosedNetworkSim,
    FaultConfig,
    SimConfig,
    export_stream,
)

__all__ = [
    "GradientSource",
    "ServerConfig",
    "TraceRecord",
    "run_generalized_async_sgd",
    "run_fedbuff",
    "run_fedavg",
    "run_favano",
]

Pytree = Any


class GradientSource(Protocol):
    def grad(self, client_id: int, params: Pytree, server_step: int) -> Pytree:
        """Stochastic gradient of client `client_id`'s local loss at `params`."""
        ...


def _tree_map(f, *trees):
    import jax

    return jax.tree_util.tree_map(f, *trees)


def _axpy(w: Pytree, g: Pytree, a: float) -> Pytree:
    """w + a*g elementwise over the pytree."""
    return _tree_map(lambda x, y: x + a * y, w, g)


@dataclass
class ServerConfig:
    """One server-loop run of {Generalized AsyncSGD, AsyncSGD, FedBuff, ...}.

    The queueing knobs (``n``, ``C``, ``T``, ``p``, ``mu``, ``service``)
    define the closed Jackson network of §2; the engine knobs pick how
    Algorithm 1 executes (reference Python loop vs one compiled scan, host
    replay vs fused on-device stream, per-event vs micro-blocked, single- vs
    multi-device).  See ``docs/architecture.md`` for the decision matrix.
    """

    n: int                      # number of clients
    C: int                      # concurrency (in-flight tasks)
    T: int                      # CS steps
    eta: float                  # learning rate
    p: np.ndarray | None = None  # sampling probabilities (None = uniform)
    mu: np.ndarray | None = None  # client speeds for the event clock (None = 1)
    service: str = "exp"
    seed: int = 0
    weighting: str = "importance"  # "importance" (Alg. 1) | "plain" (AsyncSGD)
    eval_every: int = 0
    track_virtual: bool = False
    apply_update: Callable[[Pytree, Pytree, float], Pytree] | None = None
    # apply_update(w, g, scale) -> new w.  Defaults to w - scale*g; override to
    # route through an optimizer or the Pallas weighted_update kernel.
    engine: str = "python"      # "python" (reference loop) | "scan" (compiled)
    update: str = "jnp"         # scan engine update path: "jnp" | "pallas"
    stream: str = "host"        # scan engine event source: "host" (pre-simulated
                                # EventStream replay, the parity oracle) |
                                # "device" (fused on-device generator, exp only)
    sparse: bool | str = "auto"  # device stream: sparse O(C) per-event state
                                 # keyed by the in-flight slots (flat in n).
                                 # "auto" switches on when n >= SPARSE_AUTO_N,
                                 # the speed profile collapses to few classes
                                 # and the run is per-event/unsharded/un-
                                 # checkpointed; True forces it (errors if
                                 # unsupported); False keeps the dense oracle
    adaptive: bool = False      # device stream: re-optimize p from running
                                # occupancy estimates every refresh_every steps
    refresh_every: int = 0      # control-loop cadence (CS steps)
    ctrl_lr: float = 0.3        # control-loop mirror-descent step size
    ctrl_iters: int = 4         # mirror-descent steps per refresh
    block_size: int | str = 1   # scan engine: events per micro-block (1 =
                                # per-event replay; E > 1 batches gathers /
                                # gradients / scatters over conflict-free
                                # blocks — exact, see engine_scan; "auto"
                                # selects E from the measured conflict rates
                                # via queue_sim.select_block_size)
    devices: int = 1            # blocked scan engine: lane-shard device count
                                # — each micro-block's E gradient lanes split
                                # across this many devices (requires
                                # block_size a >1 multiple of it; 1 = off)
    segmentation: str = "greedy"  # blocked cut placement: "greedy" (maximal
                                  # extension) | "dp" (exact minimum-padding
                                  # DP) — queue_sim.segment_blocks
    snapshot_dtype: str | None = None  # scan engine: ring-buffer storage dtype
                                       # (e.g. "bfloat16"; None = param dtype)
    collect_extras: bool = True  # scan engine: accumulate queue statistics /
                                 # p-trajectory extras (False prunes them from
                                 # the compiled program — benchmark runs)
    faults: "FaultConfig | None" = None  # client churn / crash / straggler
                                 # injection (queue_sim.FaultConfig); both
                                 # engines and both streams honor it — non-
                                 # completion events apply no update and
                                 # re-dispatch with the current weights
    guard: Any | None = None     # engine_scan.GuardConfig: reject non-finite /
                                 # norm-exploding gradients and (optionally)
                                 # updates staler than stale_cutoff CS steps
    ckpt_dir: str | None = None  # scan engine: checkpoint directory — routes
                                 # the run through repro.core.engine_ckpt,
                                 # snapshotting the full engine carry every
                                 # ckpt_every CS steps (kill-and-resume safe)
    ckpt_every: int = 0          # checkpoint cadence in CS steps
    resume: bool = False         # resume from the latest checkpoint in
                                 # ckpt_dir (config-fingerprint validated)
    serving: Any | None = None   # serving.ServingConfig: merge an open Poisson
                                 # inference stream into the device-stream
                                 # event race — requests are served from the
                                 # snapshot ring (last known-good iterate)
                                 # without blocking the update scan; serve_*
                                 # counters land in TraceRecord.extras
    scenario: Any | None = None  # scenario.ScenarioConfig (or a registry
                                 # name): phase-type service + Markov-
                                 # modulated availability.  Mutually
                                 # exclusive with `faults`; both engines
                                 # honor it — stage advances / flips apply
                                 # no update, exactly like fault events


@dataclass
class TraceRecord:
    steps: np.ndarray
    times: np.ndarray
    eval_steps: list[int] = field(default_factory=list)
    eval_values: list[float] = field(default_factory=list)
    delays: list[list[int]] | None = None
    mean_queue_lengths: np.ndarray | None = None
    virtual_gap_sq: list[float] = field(default_factory=list)
    inflight_cardinality: list[int] = field(default_factory=list)
    extras: dict = field(default_factory=dict)  # device stream: p_final,
                                                # p_traj, delay_sum, comp, ...


def _resolve(cfg: ServerConfig) -> tuple[np.ndarray, np.ndarray]:
    p = np.full(cfg.n, 1.0 / cfg.n) if cfg.p is None else np.asarray(cfg.p, float)
    mu = np.ones(cfg.n) if cfg.mu is None else np.asarray(cfg.mu, float)
    return p, mu


def _resolve_scenario_cfg(cfg: ServerConfig):
    """``cfg.scenario`` (name | ScenarioConfig | None) -> enabled config or
    None.  A disabled scenario (exponential + always-on) resolves to None so
    every engine takes its unmodified — bitwise-identical — path."""
    if cfg.scenario is None:
        return None
    from .scenario import get_scenario

    sc = get_scenario(cfg.scenario)
    return sc if sc.enabled else None


# sparse="auto" switches the device stream to the O(C) class-collapsed
# representation at and above this population size (below it the dense
# (n, C) path is already fast and stays the oracle)
SPARSE_AUTO_N = 50_000


def _resolve_sparse(cfg: ServerConfig, mu, p, block_size, ckpt_on):
    """Decide whether the device stream runs sparse; collapse to classes.

    Returns ``(ClassSpec, mu_m, p_m)`` — class-level rates and per-node
    sampling probabilities — or ``(None, None, None)`` to keep the dense
    path.  ``sparse=True`` raises on any unsupported combination;
    ``sparse="auto"`` silently falls back to dense (small n, too many
    distinct speed classes, blocked/sharded/checkpointed runs, or fault
    rates that vary within a class).
    """
    forced = cfg.sparse is True
    blockers = []
    if block_size != "auto" and int(block_size) > 1:
        blockers.append("block_size > 1")
    if cfg.devices > 1:
        blockers.append("devices > 1")
    if ckpt_on:
        blockers.append("checkpointing")
    if blockers:
        if forced:
            raise ValueError(
                "sparse=True does not compose with " + ", ".join(blockers)
            )
        return None, None, None
    if not forced and cfg.n < SPARSE_AUTO_N:
        return None, None, None
    from .stream_device import build_class_spec, resolve_fault_rates_classes

    try:
        spec, mu_m, p_m = build_class_spec(mu, p)
        if cfg.faults is not None and cfg.faults.enabled:
            resolve_fault_rates_classes(cfg.faults, spec)  # class-constant?
    except ValueError:
        if forced:
            raise
        return None, None, None
    return spec, np.asarray(mu_m, np.float64), np.asarray(p_m, np.float64)


def _expand_class_extras(extras: dict, classes) -> dict:
    """Expand (m,) class-level runner extras back to per-client (n,) arrays.

    Per-node quantities (`p_final`, `p_traj`) gather through ``inv_cls``;
    class totals (`occ_mean`, `busy_time`, `comp`, ...) divide by the class
    size first — within a class clients are exchangeable, so the per-client
    expectation is the class total over the class count.  `mean_delays` is
    computed at class level (class totals keep the ratio exact) and
    gathered.
    """
    inv = np.asarray(classes.inv_cls)
    cnt = np.asarray(classes.counts, np.float64)
    out = {k: np.asarray(v) for k, v in extras.items()}
    if "p_final" in out:
        out["p_final"] = out["p_final"][inv]
    if "p_traj" in out:
        out["p_traj"] = out["p_traj"][..., inv]
    if "comp" in out and "delay_sum" in out:
        out["mean_delays"] = (
            np.asarray(out["delay_sum"], np.float64)
            / np.maximum(np.asarray(out["comp"], np.float64), 1.0)
        )[inv]
    for k in ("occ_mean", "occ_time_avg", "busy_time", "delay_sum", "comp",
              "avail_time"):
        if k in out:
            out[k] = (np.asarray(out[k], np.float64) / cnt)[inv]
    return out


def _device_grad_fn(source) -> Callable:
    """Resolve the traceable gradient fn for the scan engine."""
    fn = getattr(source, "device_grad", None)
    if fn is None and callable(source):
        fn = source
    if fn is None:
        raise TypeError(
            "engine='scan' needs a DeviceGradientSource (a `device_grad(j, w, "
            "k)` method traceable by JAX); got "
            f"{type(source).__name__} — use engine='python' for host sources."
        )
    return fn


#: cap for block_size="auto" selection (multiples of `devices` are tried)
DEFAULT_BLOCK_SIZE_MAX = 16
#: probe length for "auto" when no event stream is materialized (device path)
AUTO_PROBE_STEPS = 4000


def _probe_stream_slots(mu, p, C: int, T: int, seed, fault=None,
                        scenario=None) -> np.ndarray:
    """Short device-generated probe stream for block-size auto-selection.

    The fused engine never materializes its event stream, so ``"auto"`` on
    the device path measures conflict rates on a (law-identical) probe of at
    most `AUTO_PROBE_STEPS` CS steps from `stream_device.generate_stream`.
    Shared by `_run_scan` and `fl.run_matrix` so both resolve "auto"
    identically.  The probe must draw from the *configured* stream — a
    faultless/exponential probe under faults or a scenario would understate
    slot-conflict rates (stage/flip events carry the trash slot C, which
    never conflicts) and bias ``block_size="auto"``.
    """
    from .stream_device import generate_stream

    return generate_stream(
        mu, p, C, min(T, AUTO_PROBE_STEPS), seed=seed, fault=fault,
        scenario=scenario,
    ).slot


def _auto_block_size(slots, devices: int = 1, cut_every: int = 0) -> int:
    """Resolve ``block_size="auto"`` from measured conflict-free run lengths
    (`queue_sim.select_block_size`), scaled to multiples of ``devices``.
    ``slots`` is one measured (T,) slot array or a list of them."""
    from .queue_sim import select_block_size

    E, _ = select_block_size(
        slots,
        block_size_max=max(DEFAULT_BLOCK_SIZE_MAX, devices),
        devices=max(devices, 1),
        cut_every=cut_every,
        # greedy and DP cuts have identical block counts (hereditary
        # validity — locked by tests), so the cheaper single pass suffices
        # for the utilization measurements
        method="greedy",
    )
    return E


def _scan_update_fn(cfg: ServerConfig):
    if cfg.apply_update is not None:
        return cfg.apply_update
    if cfg.update == "pallas":
        # a module-level function: the jitted-runner memo keys on the
        # update_fn object, and the kernels' interpret mode is decided by
        # `repro.kernels.ops` from the platform
        from ..kernels.ops import weighted_update_tree

        return weighted_update_tree
    if cfg.update != "jnp":
        raise ValueError(cfg.update)
    return None  # engine default: w - scale*g


def _run_scan(
    w0: Pytree,
    source,
    cfg: ServerConfig,
    eval_fn,
    p: np.ndarray,
    mu: np.ndarray,
    *,
    fedbuff_Z: int = 0,
) -> tuple[Pytree, TraceRecord]:
    """Shared scan-engine driver for Generalized AsyncSGD and FedBuff.

    ``cfg.stream`` picks the event source: "host" pre-simulates the event
    stream with `queue_sim.export_stream` and replays it (the parity
    oracle); "device" generates it inside the compiled program
    (`engine_scan.make_fused_runner`) — zero host pre-simulation, and the
    only mode supporting ``cfg.adaptive`` sampling.

    The call records the host span `obs.CALL` with the children
    `obs.PREPARE` (validation, resolution, the block-size probe, the runner
    memo and argument transfer), `obs.LAUNCH` (the runner call returning),
    `obs.WAIT` (``block_until_ready``) and `obs.READBACK` (results to the
    host); a call that builds a fused runner records its scope table
    (`obs.record_scopes`) in `obs.SCOPE_TABLE` between the last two.
    """
    with obs.span(obs.CALL), obs.phases(obs.PREPARE) as phase:
        return _scan_call(w0, source, cfg, eval_fn, p, mu, phase,
                          fedbuff_Z=fedbuff_Z)


def _scan_call(w0, source, cfg, eval_fn, p, mu, phase, *, fedbuff_Z=0):
    """`_run_scan`'s body; ``phase(name)`` moves to its next host span."""
    import jax
    import jax.numpy as jnp

    from .engine_scan import (
        blocked_inputs,
        jit_fused_runner,
        jit_runner,
        step_scales,
        stream_arrays,
    )
    from .queue_sim import EventBlocks

    if cfg.track_virtual:
        raise NotImplementedError("track_virtual requires engine='python'")
    weighting = "plain" if fedbuff_Z else cfg.weighting
    faults = cfg.faults if (cfg.faults is not None and cfg.faults.enabled) else None
    scenario = _resolve_scenario_cfg(cfg)
    if scenario is not None:
        if faults is not None:
            raise ValueError(
                "scenario= and faults= are separate injection paths; model "
                "suspension via ScenarioConfig modulation (rate_scale)"
            )
        if fedbuff_Z:
            raise ValueError(
                "scenario= composes with Algorithm 1, not FedBuff"
            )
        if cfg.service != "exp":
            raise ValueError(
                "scenario= replaces the service law; leave service='exp'"
            )
    guard = cfg.guard
    guard_stale = guard is not None and int(guard.stale_cutoff) > 0
    ckpt_on = cfg.ckpt_dir is not None
    if ckpt_on and cfg.ckpt_every <= 0:
        raise ValueError("ckpt_dir requires ckpt_every > 0")
    serving = cfg.serving if (cfg.serving is not None and cfg.serving.enabled) else None
    if serving is not None and cfg.stream != "device":
        raise ValueError(
            "serving requires stream='device' (the open arrival stream is "
            "merged into the on-device event race)"
        )
    if fedbuff_Z and (faults is not None or guard_stale):
        raise ValueError(
            "fault injection / staleness cutoff compose with Algorithm 1, "
            "not FedBuff (the buffer flush has no per-event masking)"
        )
    w0_dev = _tree_map(jnp.asarray, w0)
    eval_every = cfg.eval_every if eval_fn is not None else 0
    # the event-stream arrays are freshly built per run, so hand their
    # buffers to the compiled program; CPU cannot donate them (warns), so
    # keep donation to accelerator backends
    donate = jax.default_backend() != "cpu"
    block_size = cfg.block_size
    if block_size != "auto" and int(block_size) > 1 and cfg.apply_update is not None:
        raise ValueError(
            "block_size > 1 requires the default update w - scale*g"
        )

    if cfg.stream == "device":
        if cfg.service != "exp":
            raise ValueError(
                "stream='device' supports exponential service only "
                "(the on-device race relies on memorylessness)"
            )
        classes = class_mu = class_p = None
        if cfg.sparse is True and serving is not None:
            raise ValueError(
                "serving composes with the dense stream only (the serve "
                "read path indexes the dense snapshot ring)"
            )
        if scenario is not None:
            if cfg.sparse is True:
                raise ValueError(
                    "the fused engine's scenario path is dense-only; use "
                    "sparse_stats_stream_fn(scenario=True) for class-level "
                    "laws"
                )
            if serving is not None:
                raise ValueError("scenario= does not compose with serving=")
            if ckpt_on:
                raise ValueError(
                    "scenario= does not compose with checkpointing yet"
                )
            if block_size == "auto":
                block_size = 1  # scenario stream is per-event
            elif int(block_size) > 1:
                raise ValueError("scenario= requires block_size=1")
        elif (cfg.sparse is True or cfg.sparse == "auto") and serving is None:
            classes, class_mu, class_p = _resolve_sparse(
                cfg, mu, p, block_size, ckpt_on
            )
        elif cfg.sparse not in (False, "auto"):
            raise ValueError(f"sparse={cfg.sparse!r} (expected bool or 'auto')")
        if classes is not None:
            block_size = 1  # sparse stream is per-event; skip the auto probe
        if block_size == "auto":
            block_size = _auto_block_size(
                _probe_stream_slots(mu, p, cfg.C, cfg.T, cfg.seed,
                                    fault=faults),
                cfg.devices,
            )
        if ckpt_on:
            from .engine_ckpt import run_checkpointed

            if cfg.devices > 1:
                raise ValueError(
                    "checkpointing does not compose with lane sharding — "
                    "checkpoint the unsharded run"
                )
            if fedbuff_Z or _scan_update_fn(cfg) is not None:
                raise ValueError(
                    "the checkpointed fused engine supports the default "
                    "update w - scale*g with fedbuff_Z=0"
                )
            phase(obs.LAUNCH)
            w, evals, ck_extras = run_checkpointed(
                _device_grad_fn(source), cfg.n, cfg.C, cfg.T,
                w0=w0_dev, mu=mu, p0=p, key=jax.random.PRNGKey(cfg.seed),
                eta=cfg.eta, ckpt_dir=cfg.ckpt_dir, ckpt_every=cfg.ckpt_every,
                weighting=weighting, eval_fn=eval_fn, eval_every=eval_every,
                adaptive=cfg.adaptive, refresh_every=cfg.refresh_every,
                ctrl_lr=cfg.ctrl_lr, ctrl_iters=cfg.ctrl_iters,
                block_size=int(block_size), snapshot_dtype=cfg.snapshot_dtype,
                fault=faults, guard=guard, serving=serving, resume=cfg.resume,
            )
            phase(obs.WAIT)
            w = jax.block_until_ready(w)
            phase(obs.READBACK)
            # the chunked driver keeps no per-step clock (only the final t)
            trace = TraceRecord(
                steps=np.arange(cfg.T), times=np.full(cfg.T, np.nan)
            )
            trace.extras = {
                k: np.asarray(v) for k, v in ck_extras.items()
            }
            if eval_fn is not None and cfg.eval_every:
                n_evals = np.asarray(evals).shape[0]
                trace.eval_steps = [
                    (i + 1) * cfg.eval_every for i in range(n_evals)
                ]
                trace.eval_values = [float(v) for v in np.asarray(evals)]
            return w, trace
        builds = obs.counters["runner_builds"]
        runner = jit_fused_runner(
            _device_grad_fn(source),
            cfg.n,
            cfg.C,
            cfg.T,
            weighting=weighting,
            fedbuff_Z=fedbuff_Z,
            eval_fn=eval_fn,
            eval_every=eval_every,
            adaptive=cfg.adaptive,
            refresh_every=cfg.refresh_every,
            ctrl_lr=cfg.ctrl_lr,
            ctrl_iters=cfg.ctrl_iters,
            update_fn=_scan_update_fn(cfg),
            block_size=block_size,
            snapshot_dtype=cfg.snapshot_dtype,
            collect_extras=cfg.collect_extras,
            lane_devices=cfg.devices,
            fault=faults,
            guard=guard,
            classes=classes,
            serving=serving,
            scenario=scenario,
        )
        run_mu = mu if classes is None else class_mu
        run_p = p if classes is None else class_p
        args = (w0_dev, jnp.asarray(run_mu), jnp.asarray(run_p),
                jax.random.PRNGKey(cfg.seed), cfg.eta)
        phase(obs.LAUNCH)
        w, evals, extras = runner(*args)
        phase(obs.WAIT)
        w = jax.block_until_ready(w)
        if obs.counters["runner_builds"] != builds:
            phase(obs.SCOPE_TABLE)
            obs.record_scopes(runner, *args)
        phase(obs.READBACK)
        if classes is not None:
            extras = _expand_class_extras(extras, classes)
        times = (
            np.asarray(extras["t"], np.float64)
            if "t" in extras
            # collect_extras=False prunes the per-step clock: make misuse
            # loud (NaN propagates) instead of fabricating t=0 timestamps
            else np.full(cfg.T, np.nan)
        )
        trace = TraceRecord(steps=np.arange(cfg.T), times=times)
        trace.extras = {"p_final": np.asarray(extras["p_final"], np.float64)}
        for name in ("guard_rejects", "stale_drops", "kind_count", "avail_time"):
            if name in extras:
                trace.extras[name] = np.asarray(extras[name])
        for name in extras:
            if name.startswith("serve_"):
                trace.extras[name] = np.asarray(extras[name])
        if "occ_mean" in extras:
            trace.mean_queue_lengths = np.asarray(extras["occ_mean"], np.float64)
            comp = np.asarray(extras["comp"], np.float64)
            mean_delays = (
                np.asarray(extras["mean_delays"], np.float64)
                if "mean_delays" in extras  # sparse: exact class-level ratio
                else np.asarray(extras["delay_sum"], np.float64)
                / np.maximum(comp, 1.0)
            )
            trace.extras.update(
                p_traj=np.asarray(extras["p_traj"], np.float64),
                mean_delays=mean_delays,
                comp=comp,
                busy_time=np.asarray(extras["busy_time"], np.float64),
            )
    else:
        if cfg.stream != "host":
            raise ValueError(cfg.stream)
        if cfg.adaptive:
            raise ValueError("adaptive sampling requires stream='device'")
        stream = export_stream(
            SimConfig(mu=mu, p=p, C=cfg.C, T=cfg.T, service=cfg.service,
                      seed=cfg.seed,
                      record_delays=cfg.collect_extras or guard_stale,
                      fault=faults, scenario=scenario)
        )
        scale = step_scales(stream, cfg.eta, p, weighting)
        host_stale_drops = 0
        if guard_stale:
            # the host replay enforces the staleness cutoff here, where the
            # exported per-event delays live — the in-scan counter (gcnt[1])
            # stays 0 on host paths and the drop count rides trace.extras
            stale = (stream.delay_steps > int(guard.stale_cutoff)) & (scale != 0)
            host_stale_drops = int(stale.sum())
            scale = np.where(stale, 0.0, scale).astype(scale.dtype)
        if cfg.update not in ("jnp", "pallas"):
            raise ValueError(cfg.update)
        kernel = cfg.update
        if block_size == "auto":
            block_size = _auto_block_size(
                stream.slot, cfg.devices, cut_every=eval_every
            )
        if block_size > 1 and cfg.apply_update is not None:
            # re-check after "auto" resolution: the blocked replay only
            # reconstructs iterates for the default update w - scale*g
            raise ValueError(
                "block_size > 1 requires the default update w - scale*g"
            )
        gcnt = None
        if block_size > 1:
            group_events = eval_every
            if ckpt_on:
                # checkpoint cursors need exact event counts per row group,
                # which only the grouped (cut_every) layout provides
                group_events = eval_every if eval_every else min(
                    cfg.ckpt_every, cfg.T
                )
            blocks = EventBlocks.from_stream(
                stream, block_size, cut_every=group_events,
                method=cfg.segmentation,
            )
            J, slot, sc, kb, mask, chunk_blocks, n_chunks = blocked_inputs(
                blocks, scale, group_events
            )
            if ckpt_on:
                from .engine_ckpt import run_checkpointed_host_blocked

                if cfg.devices > 1:
                    raise ValueError(
                        "checkpointing does not compose with lane sharding"
                    )
                phase(obs.LAUNCH)
                out = run_checkpointed_host_blocked(
                    _device_grad_fn(source), cfg.C, int(block_size),
                    w0_dev, J, slot, sc, kb, mask,
                    group_events=group_events, chunk_blocks=chunk_blocks,
                    n_chunks=n_chunks, ckpt_dir=cfg.ckpt_dir,
                    ckpt_every=cfg.ckpt_every, eval_fn=eval_fn,
                    kernel=kernel,
                    snapshot_dtype=cfg.snapshot_dtype, fedbuff_Z=fedbuff_Z,
                    guard=guard, resume=cfg.resume,
                )
            else:
                runner = jit_runner(
                    _device_grad_fn(source),
                    cfg.C,
                    fedbuff_Z=fedbuff_Z,
                    eval_fn=eval_fn,
                    block_size=block_size,
                    kernel=kernel,
                    snapshot_dtype=cfg.snapshot_dtype,
                    donate=donate,
                    lane_devices=cfg.devices,
                    guard=guard,
                )
                phase(obs.LAUNCH)
                out = runner(
                    w0_dev, jnp.asarray(J), jnp.asarray(slot), jnp.asarray(sc),
                    jnp.asarray(kb), jnp.asarray(mask),
                    chunk_blocks=chunk_blocks, n_chunks=n_chunks,
                )
        else:
            if cfg.devices > 1:
                raise ValueError(
                    "devices > 1 lane-shards micro-blocks and requires the "
                    "blocked engine (block_size > 1)"
                )
            if ckpt_on:
                from .engine_ckpt import run_checkpointed_host

                phase(obs.LAUNCH)
                out = run_checkpointed_host(
                    _device_grad_fn(source), cfg.C, w0_dev,
                    stream.J, stream.slot, scale,
                    ckpt_dir=cfg.ckpt_dir, ckpt_every=cfg.ckpt_every,
                    eval_fn=eval_fn, eval_every=eval_every,
                    fedbuff_Z=fedbuff_Z, update_fn=_scan_update_fn(cfg),
                    snapshot_dtype=cfg.snapshot_dtype, guard=guard,
                    resume=cfg.resume,
                )
            else:
                runner = jit_runner(
                    _device_grad_fn(source),
                    cfg.C,
                    fedbuff_Z=fedbuff_Z,
                    eval_fn=eval_fn,
                    eval_every=eval_every,
                    update_fn=_scan_update_fn(cfg),
                    donate=donate,
                    guard=guard,
                )
                J_dev, slot_dev = stream_arrays(stream)
                phase(obs.LAUNCH)
                out = runner(w0_dev, J_dev, slot_dev, jnp.asarray(scale))
        if guard is not None:
            w, evals, gcnt = out
        else:
            w, evals = out
        phase(obs.WAIT)
        w = jax.block_until_ready(w)
        phase(obs.READBACK)
        trace = TraceRecord(steps=np.arange(cfg.T), times=np.asarray(stream.t))
        trace.delays = stream.delays
        trace.mean_queue_lengths = (
            stream.queue_len_sum / cfg.T
            if stream.queue_len_sum is not None else None
        )
        if guard is not None:
            gcnt = np.asarray(gcnt)
            trace.extras["guard_rejects"] = int(gcnt[0])
            trace.extras["stale_drops"] = int(gcnt[1]) + host_stale_drops
        if stream.kind is not None and (faults is not None or scenario is not None):
            trace.extras["kind_count"] = np.bincount(
                stream.kind, minlength=6 if scenario is not None else 4
            )

    if eval_fn is not None and cfg.eval_every:
        n_evals = np.asarray(evals).shape[0]
        trace.eval_steps = [(i + 1) * cfg.eval_every for i in range(n_evals)]
        trace.eval_values = [float(v) for v in np.asarray(evals)]
    return w, trace


def run_generalized_async_sgd(
    w0: Pytree,
    source: GradientSource,
    cfg: ServerConfig,
    eval_fn: Callable[[Pytree], float] | None = None,
) -> tuple[Pytree, TraceRecord]:
    """Algorithm 1.  Returns final parameters and the execution trace.

    With ``cfg.engine == "scan"``, `source` must be a DeviceGradientSource
    and `eval_fn` (if given) must be traceable — pure JAX ops returning a
    device scalar, since it runs inside the compiled program.  The "python"
    engine accepts any host callable returning a float.
    """
    p, mu = _resolve(cfg)
    if cfg.engine == "scan":
        return _run_scan(w0, source, cfg, eval_fn, p, mu)
    if cfg.engine != "python":
        raise ValueError(cfg.engine)
    if cfg.stream == "device" or cfg.adaptive:
        raise ValueError("stream='device' / adaptive require engine='scan'")
    if cfg.ckpt_dir is not None:
        raise ValueError("checkpointing requires engine='scan'")
    scenario = _resolve_scenario_cfg(cfg)
    sim = ClosedNetworkSim(
        SimConfig(mu=mu, p=p, C=cfg.C, T=cfg.T, service=cfg.service,
                  seed=cfg.seed, record_delays=True, fault=cfg.faults,
                  scenario=scenario)
    )
    apply_update = cfg.apply_update or (lambda w, g, s: _axpy(w, g, -s))
    faults_on = cfg.faults is not None and cfg.faults.enabled
    if faults_on or cfg.guard is not None or scenario is not None:
        if cfg.track_virtual:
            raise NotImplementedError(
                "track_virtual does not compose with faults/guards/scenarios"
            )
        return _python_fault_loop(w0, source, cfg, eval_fn, p, sim,
                                  apply_update)

    w = w0
    mu_virtual = w0 if cfg.track_virtual else None
    # dispatch-time parameter snapshot per client FIFO queue (mirrors sim.queues)
    snaps: list[deque] = [deque(w0 for _ in q) for q in sim.queues]

    times = np.zeros(cfg.T)
    steps = np.arange(cfg.T)
    trace = TraceRecord(steps=steps, times=times)
    if cfg.track_virtual:
        import jax

    for k in range(cfg.T):
        j, k_new = sim.step()          # J_k completes; K_{k+1} sampled; task enqueued
        w_disp = snaps[j].popleft()    # FIFO: the completed task's dispatch params
        g = source.grad(j, w_disp, k)
        if cfg.weighting == "importance":
            scale = cfg.eta / (cfg.n * p[j])
        elif cfg.weighting == "plain":
            scale = cfg.eta
        else:
            raise ValueError(cfg.weighting)
        w = apply_update(w, g, scale)
        snaps[k_new].append(w)    # the new task departs with the *updated* model
        times[k] = sim.now

        if cfg.track_virtual:
            # mu_{k+1} = mu_k - eta/(n p_{K_k}) g_{K_k}(w_k): instantaneous
            # contribution of the *newly sampled* client at the current w.
            g_virt = source.grad(k_new, w, k)
            mu_virtual = _axpy(mu_virtual, g_virt, -cfg.eta / (cfg.n * p[k_new]))
            gap = _tree_map(lambda a, b: float(np.sum((np.asarray(a) - np.asarray(b)) ** 2)), w, mu_virtual)
            trace.virtual_gap_sq.append(sum(jax.tree_util.tree_leaves(gap)))
            trace.inflight_cardinality.append(sim.total_tasks())

        if eval_fn is not None and cfg.eval_every and (k + 1) % cfg.eval_every == 0:
            trace.eval_steps.append(k + 1)
            trace.eval_values.append(float(eval_fn(w)))

    trace.delays = sim.delays
    trace.mean_queue_lengths = sim.queue_len_sum / cfg.T
    return w, trace


def _python_fault_loop(
    w0: Pytree,
    source: GradientSource,
    cfg: ServerConfig,
    eval_fn,
    p: np.ndarray,
    sim: ClosedNetworkSim,
    apply_update,
) -> tuple[Pytree, TraceRecord]:
    """Fault/guard-aware reference loop — the parity oracle of the scan
    engines' fault semantics.

    One iteration consumes one *merged* CTMC event (`ClosedNetworkSim.
    step_event`): completions compute the gradient at the dispatch-time
    snapshot and (guards permitting) apply it; crashes and straggler
    timeouts discard the in-flight work and re-dispatch the freed slot with
    the *current* server weights; availability flips touch no task.  The
    guard ordering (staleness before divergence, rejects counted once)
    matches `engine_scan._make_guard` exactly, with the staleness clock
    being merged-event steps — the same counter the device stream scans
    over.
    """
    import jax

    guard = cfg.guard
    max_sq = float(guard.max_grad_norm) ** 2 if guard is not None else 0.0
    cutoff = int(guard.stale_cutoff) if guard is not None else 0
    gcnt = [0, 0]  # [guard_rejects, stale_drops]
    w = w0
    # per-node FIFO of (dispatch-time snapshot, dispatch step + 1)
    snaps: list[deque] = [deque((w0, 0) for _ in q) for q in sim.queues]
    times = np.zeros(cfg.T)
    trace = TraceRecord(steps=np.arange(cfg.T), times=times)
    for k in range(cfg.T):
        kind, j, k_new = sim.step_event()
        times[k] = sim.now
        if kind == KIND_FLIP or kind == KIND_STAGE:
            # no task moved: flips touch no queue, stage advances keep the
            # head task in service — no snapshot pop, no update
            continue
        w_disp, disp_k = snaps[j].popleft()
        if kind == KIND_COMPLETE:
            live = True
            if cutoff and (k - disp_k) > cutoff:
                gcnt[1] += 1
                live = False
            if live:
                g = source.grad(j, w_disp, k)
                if guard is not None:
                    sq = sum(
                        float(np.sum(np.square(np.asarray(x, np.float32))))
                        for x in jax.tree_util.tree_leaves(g)
                    )
                    if not np.isfinite(sq) or (max_sq > 0.0 and sq > max_sq):
                        gcnt[0] += 1
                        live = False
            if live:
                if cfg.weighting == "importance":
                    scale = cfg.eta / (cfg.n * p[j])
                elif cfg.weighting == "plain":
                    scale = cfg.eta
                else:
                    raise ValueError(cfg.weighting)
                w = apply_update(w, g, scale)
        # crash/timeout: work discarded; slot re-dispatched at current w
        snaps[k_new].append((w, k + 1))
        if eval_fn is not None and cfg.eval_every and (k + 1) % cfg.eval_every == 0:
            trace.eval_steps.append(k + 1)
            trace.eval_values.append(float(eval_fn(w)))
    trace.delays = sim.delays
    trace.mean_queue_lengths = sim.queue_len_sum / cfg.T
    trace.extras = {
        "guard_rejects": gcnt[0],
        "stale_drops": gcnt[1],
        "kind_count": np.asarray(sim.kind_counts)
        if getattr(sim, "_fault", False) or getattr(sim, "_scenario", False)
        else None,
    }
    return w, trace


def run_fedbuff(
    w0: Pytree,
    source: GradientSource,
    cfg: ServerConfig,
    Z: int = 10,
    eval_fn: Callable[[Pytree], float] | None = None,
) -> tuple[Pytree, TraceRecord]:
    """FedBuff (Nguyen et al. 2022): uniform sampling, server applies the
    *average* of a buffer of Z received gradients.  The buffer fill shares the
    same queueing clock; the CS performs T//Z buffered updates over T
    completions."""
    p, mu = _resolve(cfg)
    pu = np.full(cfg.n, 1.0 / cfg.n)  # FedBuff samples uniformly
    if cfg.engine == "scan":
        return _run_scan(w0, source, cfg, eval_fn, pu, mu, fedbuff_Z=Z)
    if cfg.engine != "python":
        raise ValueError(cfg.engine)
    if cfg.stream == "device" or cfg.adaptive:
        raise ValueError("stream='device' / adaptive require engine='scan'")
    if ((cfg.faults is not None and cfg.faults.enabled) or cfg.guard is not None
            or _resolve_scenario_cfg(cfg) is not None):
        raise ValueError(
            "faults/guards/scenarios compose with Algorithm 1 "
            "(run_generalized_async_sgd), not the FedBuff reference loop"
        )
    if cfg.ckpt_dir is not None:
        raise ValueError("checkpointing requires engine='scan'")
    sim = ClosedNetworkSim(
        SimConfig(mu=mu, p=pu, C=cfg.C, T=cfg.T, service=cfg.service,
                  seed=cfg.seed, record_delays=True)
    )
    apply_update = cfg.apply_update or (lambda w, g, s: _axpy(w, g, -s))
    w = w0
    snaps: list[deque] = [deque(w0 for _ in q) for q in sim.queues]
    buffer: list[Pytree] = []
    times = np.zeros(cfg.T)
    trace = TraceRecord(steps=np.arange(cfg.T), times=times)
    updates = 0
    for k in range(cfg.T):
        j, k_new = sim.step()
        w_disp = snaps[j].popleft()
        buffer.append(source.grad(j, w_disp, k))
        if len(buffer) >= Z:
            g_mean = buffer[0]
            for g in buffer[1:]:
                g_mean = _axpy(g_mean, g, 1.0)
            g_mean = _tree_map(lambda x: x / len(buffer), g_mean)
            w = apply_update(w, g_mean, cfg.eta)
            buffer = []
            updates += 1
        snaps[k_new].append(w)
        times[k] = sim.now
        if eval_fn is not None and cfg.eval_every and (k + 1) % cfg.eval_every == 0:
            trace.eval_steps.append(k + 1)
            trace.eval_values.append(float(eval_fn(w)))
    trace.delays = sim.delays
    trace.mean_queue_lengths = sim.queue_len_sum / cfg.T
    return w, trace


def run_fedavg(
    w0: Pytree,
    source: GradientSource,
    cfg: ServerConfig,
    clients_per_round: int = 10,
    local_steps: int = 1,
    eval_fn: Callable[[Pytree], float] | None = None,
) -> tuple[Pytree, TraceRecord]:
    """Synchronous FedAvg baseline.  Each round waits for the slowest sampled
    client (round time = max of their service draws); `cfg.T` counts rounds."""
    _, mu = _resolve(cfg)
    rng = np.random.default_rng(cfg.seed)
    apply_update = cfg.apply_update or (lambda w, g, s: _axpy(w, g, -s))
    w = w0
    now = 0.0
    times = np.zeros(cfg.T)
    trace = TraceRecord(steps=np.arange(cfg.T), times=times)
    for r in range(cfg.T):
        sel = rng.choice(cfg.n, size=clients_per_round, replace=False)
        # round wall time = slowest client's total local work
        if cfg.service == "exp":
            durs = rng.exponential(1.0 / mu[sel], size=sel.size) * local_steps
        else:
            durs = local_steps / mu[sel]
        now += float(np.max(durs))
        g_mean = None
        for i in sel:
            g = source.grad(int(i), w, r)
            for _ in range(local_steps - 1):
                g = _axpy(g, source.grad(int(i), _axpy(w, g, -cfg.eta), r), 1.0)
            g_mean = g if g_mean is None else _axpy(g_mean, g, 1.0)
        g_mean = _tree_map(lambda x: x / sel.size, g_mean)
        w = apply_update(w, g_mean, cfg.eta)
        times[r] = now
        if eval_fn is not None and cfg.eval_every and (r + 1) % cfg.eval_every == 0:
            trace.eval_steps.append(r + 1)
            trace.eval_values.append(float(eval_fn(w)))
    return w, trace


def run_favano(
    w0: Pytree,
    source: GradientSource,
    cfg: ServerConfig,
    period: float = 1.0,
    max_local_steps: int = 8,
    eval_fn: Callable[[Pytree], float] | None = None,
) -> tuple[Pytree, TraceRecord]:
    """FAVANO/QuAFL-style baseline (Leconte et al. 2023; Zakerinia et al. 2022).

    No queues: the CS ticks at a fixed cadence `period`; between ticks each
    client performs as many local SGD steps as its speed allows (capped at
    `max_local_steps`, interruptible), and the CS averages the client models.
    The CS step rate is bounded by the cadence — the contrast the paper draws
    against queue-driven AsyncSGD (§5).  `cfg.T` counts CS rounds.
    """
    p, mu = _resolve(cfg)
    rng = np.random.default_rng(cfg.seed)
    apply_update = cfg.apply_update or (lambda w, g, s: _axpy(w, g, -s))
    w = w0
    locals_ = [w0 for _ in range(cfg.n)]
    now = 0.0
    times = np.zeros(cfg.T)
    trace = TraceRecord(steps=np.arange(cfg.T), times=times)
    for r in range(cfg.T):
        now += period
        for i in range(cfg.n):
            # local steps completed within the window (speed-proportional)
            n_i = min(int(rng.poisson(mu[i] * period)), max_local_steps)
            wi = locals_[i]
            for _ in range(n_i):
                wi = apply_update(wi, source.grad(i, wi, r), cfg.eta)
            locals_[i] = wi
        # CS averages client models and broadcasts
        w = _tree_mean(locals_)
        locals_ = [w for _ in range(cfg.n)]
        times[r] = now
        if eval_fn is not None and cfg.eval_every and (r + 1) % cfg.eval_every == 0:
            trace.eval_steps.append(r + 1)
            trace.eval_values.append(float(eval_fn(w)))
    return w, trace


def _tree_mean(trees: list) -> Pytree:
    out = trees[0]
    for t in trees[1:]:
        out = _axpy(out, t, 1.0)
    return _tree_map(lambda x: x / len(trees), out)
