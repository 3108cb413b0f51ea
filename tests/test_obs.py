"""The engine's own tracing (`repro.obs`): the scope table of a fused runner,
the host spans of a scan-engine call, and the runner-build and compile
counters."""
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import ServerConfig, run_generalized_async_sgd
from repro.core.engine_scan import jit_fused_runner
from repro.fl.engine import DeviceTaskClients

N, C, T, D = 6, 3, 4, 8


def _loss(w, b):
    h = jnp.tanh(b["x"] @ w["a"])
    return jnp.mean((h @ w["b"] - b["y"]) ** 2)


def _clients(seed=0):
    rng = np.random.default_rng(seed)
    shards = {"x": rng.normal(size=(N, 16, D)).astype(np.float32),
              "y": rng.normal(size=(N, 16)).astype(np.float32)}
    ev = {"x": jnp.asarray(shards["x"][0, :4]), "y": jnp.asarray(shards["y"][0, :4])}
    return DeviceTaskClients(_loss, shards, batch_size=4, seed=seed), (lambda w: _loss(w, ev))


W0 = {"a": np.full((D, D), 0.1, np.float32), "b": np.full((D,), 0.1, np.float32)}
CFG = ServerConfig(n=N, C=C, T=T, eta=0.1, seed=1, eval_every=T, engine="scan",
                   stream="device")


def _calls_since(t0):
    return sorted((s for s in obs.spans if s[1] >= t0), key=lambda s: s[1])


# ------------------------------------------------------------------ #
# the scope table
# ------------------------------------------------------------------ #
def _instructions(text):
    """(computation, name, opcode, fused computation or None) of each line."""
    out, comp = [], None
    for line in text.splitlines():
        m = obs._INSTRUCTION.match(line)
        if m is None:
            head = obs._COMPUTATION.match(line)
            comp = head.group(1) if head else comp
            continue
        name, opcode, _ = m.groups()
        calls = obs._CALLS.search(line) if opcode == "fusion" else None
        out.append((comp, name, opcode, calls.group(1) if calls else None))
    return out


@pytest.mark.parametrize("adaptive", [False, True], ids=["static", "adaptive"])
def test_scope_table_maps_the_runner_layers(adaptive):
    clients, eval_fn = _clients()
    kw = dict(refresh_every=T // 2) if adaptive else {}
    runner = jit_fused_runner(clients.device_grad, N, C, T, eval_fn=eval_fn,
                              eval_every=T, adaptive=adaptive, **kw)
    p = np.full(N, 1.0 / N, np.float32)
    args = (jax.tree_util.tree_map(jnp.asarray, W0), jnp.ones(N), jnp.asarray(p),
            jax.random.PRNGKey(0), 0.1)
    jax.block_until_ready(runner(*args))
    table = obs.record_scopes(runner, *args)
    want = {obs.RACE, obs.RING, obs.GRAD, obs.EVAL} | ({obs.CTRL} if adaptive else set())
    assert set(table.values()) == want
    assert all(obs.scope_table[k] == v for k, v in table.items())

    text = obs.hlo_text(runner.lower(*args).compile())
    ins = _instructions(text)
    fused = {sub for *_, sub in ins if sub}
    has_dot = {comp for comp, _, op, _ in ins if op in ("dot", "custom-call")}
    loop_work = [name for comp, name, op, sub in ins
                 if comp not in fused and (op in ("dot", "custom-call") or sub in has_dot)]
    assert loop_work
    assert all(table.get(name) in obs.SCOPES for name in loop_work)


def test_scope_of_takes_the_innermost_scope():
    assert obs.scope_of("jit(run)/fl.race/while/body/fl.ring/fl.grad/jvp()/dot") == obs.GRAD
    assert obs.scope_of("jit(run)/fl.race/vmap(fl.ring)/add") == obs.RING
    assert obs.scope_of("jit(run)/while/body/fl.racer/add") is None
    assert obs.scope_of("transpose(jvp())/reduce_sum") is None


# ------------------------------------------------------------------ #
# host spans
# ------------------------------------------------------------------ #
def test_call_records_its_four_phases_in_order():
    clients, eval_fn = _clients(1)
    w, _ = run_generalized_async_sgd(W0, clients, CFG, eval_fn=eval_fn)
    t0 = time.perf_counter_ns()
    run_generalized_async_sgd(w, clients, CFG, eval_fn=eval_fn)
    spans = _calls_since(t0)
    (call,) = [s for s in spans if s[0] == obs.CALL]
    _, c0, c1, depth = call
    kids = [s for s in spans if s[3] == depth + 1 and c0 <= s[1] and s[2] <= c1]
    assert [s[0] for s in kids] == [obs.PREPARE, obs.LAUNCH, obs.WAIT, obs.READBACK]
    assert all(a[2] <= b[1] for a, b in zip(kids, kids[1:]))
    assert len(spans) == 5


def test_span_names_reach_the_profiler_host_plane(tmp_path):
    from jax.profiler import ProfileData

    clients, eval_fn = _clients(2)
    w, _ = run_generalized_async_sgd(W0, clients, CFG, eval_fn=eval_fn)
    jax.profiler.start_trace(str(tmp_path))
    try:
        run_generalized_async_sgd(w, clients, CFG, eval_fn=eval_fn)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    names = {ev.name for plane in ProfileData.from_file(str(path)).planes
             if plane.name.startswith("/host:") for ln in plane.lines for ev in ln.events}
    assert {obs.CALL, obs.PREPARE, obs.LAUNCH, obs.WAIT, obs.READBACK} <= names


def test_phases_end_in_order_on_error():
    t0 = time.perf_counter_ns()
    with pytest.raises(RuntimeError):
        with obs.span("fl.test_outer"), obs.phases("fl.test_a") as phase:
            phase("fl.test_b")
            raise RuntimeError
    spans = _calls_since(t0)
    assert [(s[0], s[3]) for s in spans] == [("fl.test_outer", 0), ("fl.test_a", 1),
                                            ("fl.test_b", 1)]
    assert obs._stack() == []


# ------------------------------------------------------------------ #
# counters
# ------------------------------------------------------------------ #
def _compile_events():
    return {k: v[0] for k, v in obs.compiles.items()}


def test_first_call_builds_and_compiles_second_does_not():
    clients, eval_fn = _clients(3)
    builds, events = obs.counters["runner_builds"], _compile_events()
    t0 = time.perf_counter_ns()
    w, _ = run_generalized_async_sgd(W0, clients, CFG, eval_fn=eval_fn)
    assert obs.counters["runner_builds"] == builds + 1
    new = {k: n - events.get(k, 0) for k, n in _compile_events().items()
           if n > events.get(k, 0)}
    assert new.get(obs.LAUNCH, 0) > 0 and new.get(obs.SCOPE_TABLE) == 1
    assert all(k.startswith("fl.") for k in new)
    assert obs.SCOPE_TABLE in {s[0] for s in _calls_since(t0)}

    builds, events = obs.counters["runner_builds"], _compile_events()
    t0 = time.perf_counter_ns()
    run_generalized_async_sgd(w, clients, CFG, eval_fn=eval_fn)
    assert obs.counters["runner_builds"] == builds
    assert _compile_events() == events
    assert obs.SCOPE_TABLE not in {s[0] for s in _calls_since(t0)}
    assert obs.compile_seconds() > 0
