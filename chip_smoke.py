"""Bring-up check: the async-FL engine's main paths on a TPU, at full width.

    python chip_smoke.py               # one chip: phases A and B
    python chip_smoke.py --four-chips  # lane-sharded LM run on four chips only

Everything runs in this one process (a chip belongs to one process at a
time), through the functions ``python -m repro.launch.train`` calls:
`lm_config`, `LMTask`, `FLConfig` and `run_experiment`.

* Phase A, the paper's section 5 path: the fused device-stream engine on
  the default `ClassificationTask`.  The last eval accuracy must beat the
  first.
* Phase B, Mamba2-130M at its published widths (bf16, Pallas kernels on)
  through the fused engine (``--engine fused``).  The eval loss must be
  finite and fall from its value at the initial weights by about as much
  as a scan-engine run of as many events on the same seed.  Then three
  comparisons with the repo's own references:
  - the SSD scan alone at one layer's widths: the kernel against
    `ssd_chunked` at precision "highest";
  - one client gradient with the kernels against the jnp path, in bf16
    and in float32, each measured against the float32 jnp gradient;
  - a few events of the compiled engine against the per-event Python loop
    on the same event stream.  This one runs the float32 variant of the
    config at ``precision="highest"``: in bf16 one update at this step
    size is below half a bf16 ulp of most weights, and the Python loop,
    which updates bf16 leaves in place, drops it, while the engine keeps
    an fp32 master copy.
* ``--four-chips``: the Phase B task on the lane-sharded blocked engine
  (``block_size=4, devices=4``, one lane per device) against the per-event run
  (``block_size=1, devices=1``) on the same seed, in float32 as above, with
  the ring, the client shards and the final weights checked on every lane
  device.  No other phase runs.

Each phase prints its wall time, the time JAX spent tracing, lowering and
compiling inside it, and its checks with their tolerances.  The script
exits non-zero before any phase when JAX finds no TPU, when a phase
raises, and, after the last phase, when any check failed.  The last line
of stdout is one JSON object.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from dataclasses import replace

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

try:
    import jax
    import jax.numpy as jnp

    from repro import obs
    from repro.configs.base import FLConfig
    from repro.fl import LMTask, run_experiment
    from repro.fl.engine import _cached_fl_setup
    from repro.kernels import ops as kops
    from repro.launch import train
    from repro.launch.compile_cache import use_compile_cache
    from repro.models.mamba2 import ssd_chunked
except ImportError as e:
    sys.exit(f"chip_smoke: cannot import the repro package next to this script: {e}")

# Phase A: the paper's classification federation (FLConfig defaults:
# n = 100 clients, C = 10, two speed clusters 10x apart)
A_STEPS, A_EVAL_EVERY = 4000, 500
# Phase B: Mamba2-130M, n = 8 clients, C = 4, batch 4 x seq 2048
LM_BATCH, LM_SEQ = 4, 2048
LM_ARGV = [
    "--mode", "lm", "--arch", "mamba2-130m", "--preset", "full",
    "--engine", "fused", "--clients", "8", "--concurrency", "4",
    "--batch", str(LM_BATCH), "--seq", str(LM_SEQ), "--steps", "20",
    "--eval-every", "10", "--shard-size", "64",
]
LM_EVAL_BATCH = 4   # held-out eval rows (the eval runs inside the engine)
EVENTS = 4          # engine vs Python loop: events compared
FOUR_CHIP_EVENTS = 32
# one gradient lane per device: at two lanes per device (E = 8) the chip's
# compiler did not finish the lane program in 470 s, and a compile for a
# described v5e:2x2 took over 10 GB of host memory even at 6 of the 24
# layers, against 65 s at full depth for one lane per device
FOUR_CHIP_BLOCK = 4
# fused vs scan engine (bf16, 20 events each, own event streams): relative
# gap between their eval-loss drops from the initial weights
DROP_RTOL = 0.5
# SSD forward alone, float32 inputs, against ssd_chunked at precision
# "highest" (relative L2 error): the kernel at the default precision may be
# no farther off than ssd_chunked at the default precision, or than
# FP32_FWD_RTOL (float32 summation-order slack), whichever is larger
FP32_FWD_RTOL = 1e-5
# one client gradient against the float32 jnp gradient at precision
# "highest" (relative L2 error): the fp32 kernel path at "highest" within
# FP32_GRAD_RTOL; the bf16 kernel path within BF16_GRAD_RATIO times the
# error of the bf16 jnp path it replaces.  On a TPU v5e at seed 0 the two
# bf16 paths read 0.04612846300005913 and 0.04612577334046364: bf16
# rounding in the rest of the model, shared by both, sets the error, and
# the ratio is 1.0000583.  An error the kernel adds on top of that adds in
# quadrature, so a ratio of 1.01 fails once it reaches 0.14 of the jnp
# path's error.
FP32_GRAD_RTOL = 1e-3
BF16_GRAD_RATIO = 1.01
# float32 "highest" engine comparisons: relative L2 error of the update
# w_T - w_0 over the whole model, and absolute eval-loss error
UPDATE_RTOL = 1e-3
LOSS_ATOL = 1e-3


FAILED: list[str] = []


def check(ok: bool, what: str) -> None:
    """Print a check; a failure is recorded, the later phases still run and
    print their readings, and the script exits non-zero at the end."""
    print(f"  check {'pass' if ok else 'FAIL'}: {what}", flush=True)
    if not ok:
        FAILED.append(what)


def phase(name: str, fn, *args):
    print(f"[{name}]", flush=True)
    t0, c0 = time.perf_counter(), obs.compile_seconds()
    out = fn(*args)
    wall, comp = time.perf_counter() - t0, obs.compile_seconds() - c0
    print(f"[{name}] wall_s={wall} compile_s={comp}", flush=True)
    return out


def _flat(tree) -> np.ndarray:
    return np.concatenate([
        np.asarray(x, np.float32).ravel() for x in jax.tree_util.tree_leaves(tree)
    ])


def _rel(a, b) -> float:
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def lm_args(seed: int, argv=()):
    return train.parser().parse_args(LM_ARGV + ["--seed", str(seed), *argv])


def lm_task(args, **cfg_kw) -> LMTask:
    task = train.lm_task(args)
    return replace(task, cfg=task.cfg.replace(**cfg_kw), eval_batch=LM_EVAL_BATCH)


def lm_run(args, task, **flc_kw):
    return run_experiment(train.lm_flconfig(args).replace(**flc_kw), "gen_async",
                          eta=args.lr, eval_every=args.eval_every, task=task)


def phase_a(seed: int) -> None:
    flc = FLConfig(engine="scan", stream="device", server_steps=A_STEPS,
                   seed=seed)
    r = run_experiment(flc, "gen_async", eta=0.05, eval_every=A_EVAL_EVERY)
    acc = np.asarray(r.eval_acc)
    print(f"  eval steps {r.eval_steps.tolist()}")
    print(f"  eval accuracy {acc.tolist()}")
    check(bool(np.isfinite(acc).all()), "eval accuracy finite")
    check(acc[-1] > acc[0], f"last eval accuracy {acc[-1]} > first {acc[0]}")


def phase_b(seed: int) -> LMTask:
    """The fused bf16 run, checked against the initial weights and against
    the scan engine (host stream) on the same seed.  The two engines draw
    different event streams from one sampling law, so their eval losses are
    compared as the drop from the initial loss, not event by event."""
    args = lm_args(seed)
    task = lm_task(args)
    cfg = task.cfg
    print(f"  {cfg.name}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
          f"dtype {cfg.dtype}, use_pallas {cfg.use_pallas}")
    check(cfg.use_pallas, "the full preset runs the Pallas kernels on the TPU")
    setup = _cached_fl_setup(None, seed, task, args.clients)
    w0 = _flat(setup.params)
    loss0 = float(setup.eval_fn(setup.params))
    print(f"  parameters {w0.size}, eval loss at the initial weights {loss0}")
    r = lm_run(args, task)
    loss = np.asarray(r.eval_acc)
    print(f"  fused: eval steps {r.eval_steps.tolist()}, eval loss {loss.tolist()}")
    check(loss.size > 0 and bool(np.isfinite(loss).all()), "eval loss finite")
    check(bool(np.any(_flat(r.final_params) != w0)), "the fused run moved the weights")
    r_scan = lm_run(lm_args(seed, ["--engine", "scan"]), task)
    loss_scan = np.asarray(r_scan.eval_acc)
    print(f"  scan engine, host stream: eval loss {loss_scan.tolist()}")
    drop, drop_scan = loss0 - float(loss[-1]), loss0 - float(loss_scan[-1])
    gap = abs(drop - drop_scan) / drop_scan
    print(f"  eval-loss drop over {args.steps} events: fused {drop}, scan {drop_scan}, "
          f"|fused - scan| / scan = {gap} (tolerance {DROP_RTOL})")
    check(drop_scan > 0, f"the scan run lowered the eval loss ({drop_scan})")
    check(drop > 0, f"the fused run lowered the eval loss ({drop})")
    check(gap <= DROP_RTOL, f"fused loss drop within {DROP_RTOL} of the scan run's")
    return task


def phase_b_ssd(seed: int, task: LMTask) -> None:
    """The SSD scan alone at one Mamba2-130M layer's widths, float32 inputs:
    the kernel and `ssd_chunked` at the default matmul precision, each
    against `ssd_chunked` at precision "highest"."""
    cfg = task.cfg
    H = cfg.ssm_expand * cfg.d_model // cfg.ssm_head_dim
    Bsz, S, N, Pd = LM_BATCH, LM_SEQ, cfg.ssm_state, cfg.ssm_head_dim
    kx, kd, kb, kc, ka = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(kx, (Bsz, S, H, Pd), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(kd, (Bsz, S, H), jnp.float32) - 2.0)
    Bm = jax.random.normal(kb, (Bsz, S, N), jnp.float32) / np.sqrt(N)
    Cm = jax.random.normal(kc, (Bsz, S, N), jnp.float32) / np.sqrt(N)
    A = -jnp.exp(jax.random.uniform(ka, (H,), jnp.float32, 0.0, 2.7))
    chunked = jax.jit(ssd_chunked, static_argnums=5)
    kernel = jax.jit(kops.ssd_scan, static_argnums=5)
    with jax.default_matmul_precision("highest"):
        y_ref = np.asarray(chunked(x, dt, A, Bm, Cm, cfg.ssm_chunk)[0])
    y = {"kernel": kernel(x, dt, A, Bm, Cm, cfg.ssm_chunk)[0],
         "ssd_chunked": chunked(x, dt, A, Bm, Cm, cfg.ssm_chunk)[0]}
    err = {k: _rel(np.asarray(v), y_ref) for k, v in y.items()}
    for k, e in err.items():
        print(f"  SSD forward, {k} at default precision vs ssd_chunked at "
              f"highest: relative L2 error {e}")
    check(bool(np.isfinite(y_ref).all()), "SSD reference output finite")
    bound = max(err["ssd_chunked"], FP32_FWD_RTOL)
    check(err["kernel"] <= bound, f"SSD kernel forward error {err['kernel']} <= {bound}")


def phase_b_grad(seed: int, task: LMTask) -> None:
    """One client gradient at the published bf16 weights, four ways: kernels
    or jnp path, in bf16 at the default matmul precision or in float32 at
    precision "highest".  The float32 jnp gradient is the reference."""
    args = lm_args(seed)
    cfg = task.cfg
    w = _cached_fl_setup(None, seed, task, args.clients).params
    g = {}
    for name, c, precision in (
        ("kernels bf16", cfg, None),
        ("jnp bf16", cfg.replace(use_pallas=False), None),
        ("kernels fp32", cfg.replace(dtype="float32"), "highest"),
        ("jnp fp32", cfg.replace(dtype="float32", use_pallas=False), "highest"),
    ):
        s = _cached_fl_setup(None, seed, replace(task, cfg=c), args.clients)
        wc = jax.tree_util.tree_map(lambda a, b: a.astype(b.dtype), w, s.params)
        with jax.default_matmul_precision(precision):
            g[name] = _flat(s.clients.grad(0, wc, 0))
    err = {k: _rel(v, g["jnp fp32"]) for k, v in g.items() if k != "jnp fp32"}
    for k, e in err.items():
        print(f"  client-0 gradient, {k} vs jnp fp32: relative L2 error {e}")
    print(f"  client-0 gradient, kernels bf16 vs jnp bf16: relative L2 error "
          f"{_rel(g['kernels bf16'], g['jnp bf16'])}")
    check(all(bool(np.isfinite(v).all()) for v in g.values()), "gradients finite")
    check(err["kernels fp32"] <= FP32_GRAD_RTOL,
          f"fp32 kernel-path error {err['kernels fp32']} <= {FP32_GRAD_RTOL}")
    print(f"  client-0 gradient, bf16 error ratio kernels / jnp "
          f"{err['kernels bf16'] / max(err['jnp bf16'], 1e-30)} "
          f"(tolerance {BF16_GRAD_RATIO})")
    check(err["kernels bf16"] <= BF16_GRAD_RATIO * err["jnp bf16"],
          f"bf16 kernel-path error {err['kernels bf16']} <= {BF16_GRAD_RATIO} x "
          f"the bf16 jnp path's {err['jnp bf16']}")


def _compare(name, r, r_ref, w0) -> None:
    loss_err = float(np.max(np.abs(np.asarray(r.eval_acc) - np.asarray(r_ref.eval_acc))))
    w, w_ref = _flat(r.final_params), _flat(r_ref.final_params)
    upd_err = _rel(w - w0, w_ref - w0)
    print(f"  {name}: eval loss {np.asarray(r.eval_acc).tolist()} vs "
          f"{np.asarray(r_ref.eval_acc).tolist()}")
    print(f"  {name}: |dw - dw_ref| / |dw_ref| = {upd_err} (tolerance "
          f"{UPDATE_RTOL}), max |loss - loss_ref| = {loss_err} (tolerance "
          f"{LOSS_ATOL}), |dw_ref| = {float(np.linalg.norm(w_ref - w0))}")
    check(bool(np.any(w_ref != w0)), f"{name}: the reference run moved the weights")
    check(upd_err <= UPDATE_RTOL, f"{name}: update error {upd_err} <= {UPDATE_RTOL}")
    check(loss_err <= LOSS_ATOL, f"{name}: eval loss error {loss_err} <= {LOSS_ATOL}")


def _fp32_task(seed: int, argv) -> tuple:
    args = lm_args(seed, argv)
    task = lm_task(args, dtype="float32")
    w0 = _flat(_cached_fl_setup(None, seed, task, args.clients).params)
    return args, task, w0


def phase_b_events(seed: int) -> None:
    argv = ["--engine", "scan", "--steps", str(EVENTS), "--eval-every", "2"]
    with jax.default_matmul_precision("highest"):
        args, task, w0 = _fp32_task(seed, argv)
        r_scan = lm_run(args, task)
        r_py = lm_run(args, task, engine="python")
    _compare("scan engine vs Python loop", r_scan, r_py, w0)


def _peak_bytes(devices) -> list[int]:
    return [d.memory_stats()["peak_bytes_in_use"] for d in devices]


def phase_four_chips(seed: int) -> None:
    argv = ["--engine", "scan", "--steps", str(FOUR_CHIP_EVENTS),
            "--eval-every", str(FOUR_CHIP_EVENTS // 2)]
    lanes = jax.devices()[:4]
    with jax.default_matmul_precision("highest"):
        args, task, w0 = _fp32_task(seed, argv)
        r_lanes = lm_run(args, task, block_size=FOUR_CHIP_BLOCK, devices=4)
        # read before the per-event run, which uses device 0 only
        peaks = _peak_bytes(lanes)
        r_event = lm_run(args, task)
    shards = _cached_fl_setup(None, seed, task, args.clients).clients.shards
    shard_bytes = sum(int(a.nbytes) for a in shards.values())
    ring = (args.concurrency + 1) * w0.size * 4
    print(f"  peak bytes per device {peaks}; one fp32 ring {ring}, client "
          f"shards {shard_bytes}")
    held = {d for x in jax.tree_util.tree_leaves(r_lanes.final_params)
            for d in x.sharding.device_set}
    print(f"  devices holding the sharded run's final weights "
          f"{sorted(d.id for d in held)}")
    # the shards are made uncommitted, so the lane program copies them to
    # every lane device; a shard pinned to one device would keep them there
    check(not any(a.committed for a in shards.values()),
          "client shards are not pinned to one device")
    check(min(peaks) >= ring + shard_bytes,
          "every lane device held a snapshot ring and the client shards")
    check(held >= set(lanes), "the final weights are replicated on every lane device")
    _compare(f"lanes (E={FOUR_CHIP_BLOCK}, D=4) vs per-event", r_lanes, r_event, w0)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the lane-sharded path on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cache_dir = use_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX platform {dev.platform!r})")
    if args.four_chips and len(devices) < 4:
        sys.exit(f"chip_smoke: --four-chips needs 4 TPU chips, found {len(devices)}")
    print(f"device {dev.device_kind} x{len(devices)}, jax {jax.__version__}, "
          f"compile cache {cache_dir}", flush=True)
    obs.install()

    t0 = time.perf_counter()
    if args.four_chips:
        phase("four chips: lane-sharded LM vs per-event",
              phase_four_chips, args.seed)
    else:
        phase("A: classification, fused device stream", phase_a, args.seed)
        task = phase("B: Mamba2-130M, fused engine", phase_b, args.seed)
        phase("B: SSD kernel vs jnp forward", phase_b_ssd, args.seed, task)
        phase("B: kernel vs jnp gradient", phase_b_grad, args.seed, task)
        phase("B: scan engine vs Python loop", phase_b_events, args.seed)
    print(f"total wall_s={time.perf_counter() - t0}", flush=True)
    if FAILED:
        sys.exit(f"chip_smoke: {len(FAILED)} check(s) failed: " + "; ".join(FAILED))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
    }}))


if __name__ == "__main__":
    main()
