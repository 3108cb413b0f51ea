"""One run of one cell: set-up, the measured window, the check, the result.

Everything a run needs is found by name from ``BENCHMARK.json``:

* the cell (``workloads`` entry) names its configuration and traffic;
* the configuration's sizes file (``bench/configs/<config>.json``) and,
  beside it, ``bench/configs/<config>.py``: the program's model step for
  those sizes, the benchmark's weights and the plain reference;
* the traffic file ``bench/workloads/<cell>.json``: the federation, the data,
  the engine call (updates per call, checked calls, traced calls) and the
  limits of the compared numbers;
* each per-layer metric's reader ``bench/metrics/<metric>.py``.

So a later change adds a cell by adding a traffic file (and, for a new model,
a sizes file and its module) and the BENCHMARK.json entries; it adds a
metric by adding a reader and its entry.  No existing file changes.

The program is driven through its normal entry: the clients are the
program's ``DeviceTaskClients`` over the generated shards, and each engine
call is ``repro.core.run_generalized_async_sgd`` with the fused device
stream (``engine="scan"``, ``stream="device"``), importance weighting and
the sampling law from ``repro.fl.engine.sampling_for``.  A call runs a
fixed number of server updates with one eval at its end.  The weights one
call returns are the next call's start, the key advances per call, and the
compiled runner, memoized on the clients object, is reused.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass

from bench import traffic as gen

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Spec:
    """A cell as BENCHMARK.json and its files describe it."""

    chips: int
    sizes: dict                  # bench/configs/<config>.json
    traffic: dict                # bench/workloads/<cell>.json
    model_module: object
    per_layer: list              # BENCHMARK.json per_layer entries of this cell
    end_to_end: list             # BENCHMARK.json end_to_end entries of this cell


def load_spec(cell: str, root: str = ROOT) -> Spec:
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    cells = {w["name"]: w for w in bench["workloads"]}
    if cell not in cells:
        raise KeyError(f"no workload {cell!r} in BENCHMARK.json")
    w = cells[cell]
    config = {c["name"]: c for c in bench["configs"]}[w["config"]]
    sizes = json.load(open(os.path.join(root, config["file"])))
    mod_path = os.path.splitext(os.path.join(root, config["file"]))[0] + ".py"
    traffic = json.load(open(os.path.join(HERE, "workloads", f"{cell}.json")))
    e2e = [m for m in bench["end_to_end"] if cell in m.get("workloads", [cell])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if cell in m.get("workloads", [cell] if m["moves"] in reported else [])]
    return Spec(int(w["chips"]), sizes, traffic,
                _load_module(mod_path, f"bench_config_{w['config']}"), per_layer, e2e)


class CompileClock:
    """Counts and sums JAX's trace / lower / compile durations."""

    def __init__(self):
        self.seconds = 0.0
        self.events = 0
        self.by_event = {e: 0.0 for e in COMPILE_EVENTS}

    def __call__(self, event, secs, **_):
        if event in COMPILE_EVENTS:
            self.seconds += secs
            self.events += 1
            self.by_event[event] += secs


@dataclass
class Call:
    weights: object              # what the call returned (dropped once handed on)
    record: object               # the program's TraceRecord of the call
    start: float
    end: float
    norms: object = None         # per-leaf norm of the weight change since the run's start


class Cell:
    """The program set up for one cell: data, clients, eval and weights."""

    def __init__(self, spec: Spec, seed: int):
        import jax

        from repro.fl.engine import DeviceTaskClients

        self.spec = spec
        fed, data, eng = spec.traffic["federation"], spec.traffic["data"], spec.traffic["engine"]
        self.n, self.C = int(fed["n_clients"]), int(fed["concurrency"])
        self.T = int(eng["updates_per_call"])
        self.eta = float(fed["lr"])
        self.model = spec.model_module.Model(spec.sizes)
        self.shards, self.eval_batch = gen.make_data(
            data, self.n, getattr(self.model, "V", None))
        self.clients = DeviceTaskClients(self.model.program_loss, self.shards,
                                         batch_size=int(data["batch"]),
                                         seed=int(data["data_seed"]))
        ev = self.eval_batch
        self.eval_fn = jax.jit(lambda w: self.model.program_loss(w, ev))
        self.reseed(seed)

    def reseed(self, seed: int):
        """The seed's part of the set-up: speeds, sampling law and weights
        (the data, the clients and their compiled runner stay)."""
        import jax

        from repro.configs.base import FLConfig
        from repro.fl.engine import sampling_for

        fed = self.spec.traffic["federation"]
        self.seed = seed
        self.mu = gen.client_speeds(fed, seed)
        flc = FLConfig(n_clients=self.n, concurrency=self.C, server_steps=self.T,
                       sampling=fed["sampling"], frac_fast=fed["frac_fast"],
                       speed_ratio=fed["speed_ratio"], seed=seed)
        self.p = sampling_for(flc, self.mu)
        self.w0 = self.model.init_weights(jax.random.PRNGKey(gen.call_seed(seed, -1)))
        self.model.check_structure(self.w0)

    def call(self, w, i: int) -> Call:
        """Engine call ``i`` from weights ``w``."""
        import jax

        from repro.core import ServerConfig, run_generalized_async_sgd

        fed = self.spec.traffic["federation"]
        cfg = ServerConfig(n=self.n, C=self.C, T=self.T, eta=self.eta, mu=self.mu, p=self.p,
                           seed=gen.call_seed(self.seed, i), eval_every=self.T,
                           engine="scan", stream="device", weighting=fed["weighting"])
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.call"):
            w_next, rec = run_generalized_async_sgd(w, self.clients, cfg, eval_fn=self.eval_fn)
        t1 = time.perf_counter()
        return Call(w_next, rec, t0, t1)

    def release(self):
        """Free the program's state: clients, runner and compiled programs."""
        import jax

        self.clients = None
        self.eval_fn = None
        gc.collect()
        jax.clear_caches()


def first_calls(cell_: Cell, count: int):
    """The run's first ``count`` calls from the seed's weights, through the
    window's own call.  Each keeps its record and the norms of its weight
    change per leaf; the starting weights move to the host, so that the
    window's device memory holds only what the program itself needs.
    Returns the weights the window starts from and the checked calls."""
    import jax

    from bench import check as chk

    w, checked = cell_.w0, []
    for i in range(count):
        c = cell_.call(w, i)
        c.norms = chk.change_norms(c.weights, cell_.w0)
        w, c.weights = c.weights, None
        checked.append(c)
    cell_.w0 = jax.device_get(cell_.w0)
    return w, checked


@dataclass
class Context:
    """What a per-layer metric reader gets (`bench.metrics`)."""

    trace: object
    compile_s: float
    model: object
    traffic: dict
    traced_calls: int
    updates_per_call: int
    device_kind: str
    chips: int


def use_cache():
    """Cache every program a run compiles in the checkout's ``.jax_cache``,
    the program's own place for it (`repro.launch.compile_cache`), whatever
    its size or compile time, so that only a cell's first run there compiles.
    A cache directory named by the environment is not taken: it may lie
    outside the checkout and be shared with another checkout's runs."""
    import jax

    from repro.launch import compile_cache

    os.environ.pop(compile_cache.ENV, None)
    compile_cache.use_compile_cache()
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def _device_info(jax, used: int) -> dict:
    """The device as JAX reports it.  The peak memory of a chip is its peak of
    buffers in use plus its peak reserved for programs' temporaries (the
    TPU runtime holds a program's scratch apart from its buffers)."""
    d = jax.devices()[0]
    peak = 0
    for dev in jax.devices()[:used]:
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return {"platform": d.platform, "kind": d.device_kind, "count": used,
            "memory_peak_bytes": peak}


def run(cell: str, seed: int, seconds: float, trace: bool, *, t_process: float,
        require_chip: bool = True, spec: Spec | None = None, out=sys.stdout) -> int:
    """One run; prints the result line and returns the exit code."""
    spec = spec or load_spec(cell)
    import jax

    devs = jax.devices()
    if require_chip and (devs[0].platform == "cpu" or len(devs) < spec.chips):
        print(f"bench: cell {cell} needs {spec.chips} accelerator chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return 3
    use_cache()
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)

    eng = spec.traffic["engine"]
    check_calls = int(eng["check_calls"])
    with jax.profiler.TraceAnnotation("bench.setup"):
        cell_ = Cell(spec, seed)
        w, checked = first_calls(cell_, check_calls)
    setup_s = time.perf_counter() - t_process
    compile_s = clock.seconds
    compile_parts = {e.rsplit("/", 1)[-1]: v for e, v in clock.by_event.items()}

    # ------------------------------------------------------------ window
    compiles0 = clock.events
    trace_dir = None
    window = []
    i = check_calls
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(trace_dir)
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(int(eng["trace_calls"])):
                with jax.profiler.TraceAnnotation("bench.handover"):
                    w_in = w
                c = cell_.call(w_in, i)
                with jax.profiler.TraceAnnotation("bench.readback"):
                    w, c.weights = c.weights, None
                    window.append(c)
                    _ = [float(v) for v in c.record.eval_values]
                i += 1
        jax.profiler.stop_trace()
    else:
        t_start = time.perf_counter()
        while not window or time.perf_counter() - t_start < seconds:
            c = cell_.call(w, i)
            w, c.weights = c.weights, None
            window.append(c)
            i += 1
    window_compiles = clock.events - compiles0
    elapsed = window[-1].end - window[0].start
    updates = len(window) * cell_.T
    rate = updates / elapsed
    failed = cell_.T * sum(
        1 for c in window if not all(math.isfinite(v) for v in c.record.eval_values))
    device = _device_info(jax, spec.chips)

    # ------------------------------------------------------------ check
    from bench import check as chk

    values, notes = compare(cell_, checked)
    values["window_compiles"] = float(window_compiles)
    limits = {k: float(v) for k, v in spec.traffic["limits"].items()}
    correct = chk.verdict(values, limits)

    # ------------------------------------------------------------ metrics
    metrics, breakdown = {}, None
    if trace:
        from bench import trace as tr

        red = tr.reduce(tr.find_trace(trace_dir))
        shutil.rmtree(trace_dir)
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        ctx = Context(red, compile_s, cell_.model, spec.traffic, len(window), cell_.T,
                      device["kind"], spec.chips)
        for m in spec.per_layer:
            reader = _load_module(os.path.join(HERE, "metrics", f"{m['name']}.py"),
                                  f"bench_metric_{m['name']}")
            v = reader.read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        breakdown = {"device_ops": red.top_ops(10), "idle_gaps": red.idle_gaps(10)}
    else:
        e2e = {"updates_per_s": rate, "setup_s": setup_s}
        for m in spec.end_to_end:
            metrics[m["name"]] = {"value": float(e2e[m["name"]]), "unit": m["unit"]}
        d = spec.traffic["data"]
        if "seq_len" in d:
            print(f"tokens_per_s {rate * d['batch'] * d['seq_len']}", file=out)
    print(f"window: {len(window)} calls of {cell_.T} updates in {elapsed} s, "
          f"{window_compiles} compile events; set-up {setup_s} s, compile {compile_s} s",
          file=out)
    notes["numbers"] = values
    notes["compile_parts"] = compile_parts
    notes["memory_stats"] = jax.devices()[0].memory_stats()
    for k, v in notes.items():
        print(f"note {k} {v}", file=out)
    checks = {k: {"value": values[k], "limit": limits[k]} for k in limits}
    for k in limits:
        print(f"check {k} {values[k]} limit {limits[k]}", file=sys.stderr)
    result = {"correct": bool(correct), "attempted": updates, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    out.flush()
    print(json.dumps(result), file=out, flush=True)
    return 0


def compare(cell_: Cell, checked: list, release: bool = True) -> tuple[dict, dict]:
    """The numbers of the checked calls against the reference, and notes for
    the reader.  With ``release`` the program's state is freed first."""
    import jax

    from bench import check as chk
    from bench import race
    from repro.core.stream_device import generate_stream

    w0 = cell_.w0
    prog_loss = [float(c.record.eval_values[-1]) for c in checked]
    prog_n1, prog_nN = checked[0].norms, checked[-1].norms
    if release:
        cell_.release()
    events, faults, ties, where = [], 0, 0, []
    for i, c in enumerate(checked):
        cs = gen.call_seed(cell_.seed, i)
        stream = generate_stream(cell_.mu, cell_.p, cell_.C, cell_.T, seed=cs)
        ex = c.record.extras
        ev = race.check_call(stream, cell_.mu, cell_.p, cell_.C, cell_.eta, cs,
                             c.record.times, ex["comp"], ex["mean_delays"])
        events.append(ev)
        faults += ev.faults
        ties += ev.ties
        where += [(i, *w) for w in ev.where]
    d = cell_.spec.traffic["data"]
    starts = chk.batch_starts(int(d["data_seed"]), int(d["shard_rows"]), int(d["batch"]))
    ref = chk.Reference(cell_.model, cell_.shards, cell_.eval_batch, int(d["batch"]), starts,
                        cell_.C)
    t0 = time.perf_counter()
    ref_w, ref_loss = ref.replay(w0, events)
    ref_n1 = chk.change_norms(ref_w[0], w0)
    ref_nN = chk.change_norms(ref_w[-1], w0)
    units = chk.leaf_units(w0)
    values = chk.numbers(prog_n1, prog_nN, prog_loss, ref_n1, ref_nN, ref_loss, units)
    values["race_faults"] = float(faults)
    notes = {"reference_s": time.perf_counter() - t0, "reference_step_bytes": ref.step_bytes(),
             "ties_followed": ties,
             "race_faults_at": where[:8],
             "program_losses": prog_loss, "reference_losses": ref_loss,
             "leaf_norms": [[name, *map(float, v)] for name, *v in zip(
                 chk.leaf_names(w0), prog_n1, ref_n1, prog_nN, ref_nN, units)]}
    del ref_w
    if release:
        jax.clear_caches()
    return values, notes
