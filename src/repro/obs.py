"""Spans, scopes and counters of the async-FL engine.

The engine names its own layers, so that a profile of it can be read by
layer and not by guessing from op shapes:

* device scopes: the fused runner wraps each layer of its compiled program
  in ``jax.named_scope`` -- `RACE` (dispatch sampling, the event race and
  its statistics), `RING` (the dispatch-snapshot gather, the update
  ``w - scale*g`` and the snapshot write), `GRAD` (the client gradient),
  `EVAL`, `CTRL` (``ctrl_refresh``) and `SERVE` (the serving plane).  Scopes
  are HLO metadata only and cost nothing at run time;
* the scope table `scope_table`: ``{HLO instruction name: scope}`` of each
  fused runner built, read from its compiled module (`record_scopes`), so
  that the device ops of a profiler trace, named by instruction, map to
  layers.  It holds no device memory and outlives ``jax.clear_caches()``;
* host spans (`span`): each opens a ``jax.profiler.TraceAnnotation`` of its
  name, on the profiler's host plane on the device trace's clock, and
  appends ``(name, start_ns, end_ns, depth)`` of ``time.perf_counter_ns``
  to the bounded buffer `spans` when it ends.  Every scan-engine call
  (``core.async_sgd._run_scan``) records `CALL` with the children `PREPARE`,
  `LAUNCH`, `WAIT` and `READBACK` (and `SCOPE_TABLE` on the call that builds
  a fused runner); checkpoint writes record `CKPT_SAVE`.  Spans are always
  on;
* counters: `counters` ``["runner_builds"]`` (misses of the jitted-runner
  memo) and `compiles` ``{innermost open span: [events, seconds]}`` of JAX's
  trace / lower / compile duration events (``"none"`` outside any span),
  from one ``jax.monitoring`` listener that `install` registers.

The state is per process.  Importing this module imports no JAX.
"""
from __future__ import annotations

import re
import threading
import time
from collections import Counter, deque

RACE = "fl.race"
RING = "fl.ring"
GRAD = "fl.grad"
EVAL = "fl.eval"
CTRL = "fl.ctrl"
SERVE = "fl.serve"
SCOPES = (RACE, RING, GRAD, EVAL, CTRL, SERVE)

CALL = "fl.call"
PREPARE = "fl.prepare"
LAUNCH = "fl.launch"
WAIT = "fl.wait"
READBACK = "fl.readback"
SCOPE_TABLE = "fl.scope_table"
CKPT_SAVE = "fl.ckpt_save"

#: spans kept: the newest, about 500 engine calls' worth
SPAN_BUFFER = 4096
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)

spans: deque = deque(maxlen=SPAN_BUFFER)
scope_table: dict[str, str] = {}
counters: dict[str, int] = {"runner_builds": 0}
compiles: dict[str, list] = {}

_local = threading.local()
_installed = False
_annotation = None


def _stack() -> list:
    try:
        return _local.stack
    except AttributeError:
        _local.stack = []
        return _local.stack


def _on_duration(event: str, secs: float, **_) -> None:
    if event in COMPILE_EVENTS:
        stack = _stack()
        c = compiles.setdefault(stack[-1] if stack else "none", [0, 0.0])
        c[0] += 1
        c[1] += secs


def install() -> None:
    """Register the compile listener (once per process)."""
    global _installed, _annotation
    if _installed:
        return
    import jax

    _annotation = jax.profiler.TraceAnnotation
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    _installed = True


def compile_seconds() -> float:
    """Seconds of trace / lower / compile events since `install`."""
    return sum(s for _, s in compiles.values())


class span:
    """A host span: ``with span(name): ...``.  `end` closes it early (the
    ``with`` exit is then a no-op); spans close in the order they opened."""

    __slots__ = ("name", "depth", "_ann", "_t0")

    def __init__(self, name: str):
        self.name = name
        self._t0 = None

    def __enter__(self) -> span:
        install()
        stack = _stack()
        self.depth = len(stack)
        stack.append(self.name)
        self._ann = _annotation(self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def end(self) -> None:
        if self._t0 is None:
            return
        t1 = time.perf_counter_ns()
        self._ann.__exit__(None, None, None)
        _stack().pop()
        spans.append((self.name, self._t0, t1, self.depth))
        self._t0 = None

    def __exit__(self, *exc) -> None:
        self.end()


class phases:
    """Sibling spans one after another: ``with phases(first) as phase: ...;
    phase(second); ...`` -- each call ends the open span and opens the next,
    and the ``with`` exit ends the last."""

    __slots__ = ("_first", "_open")

    def __init__(self, first: str):
        self._first = first

    def __enter__(self) -> phases:
        self._open = span(self._first).__enter__()
        return self

    def __call__(self, name: str) -> None:
        self._open.end()
        self._open = span(name).__enter__()

    def __exit__(self, *exc) -> None:
        self._open.end()


# ------------------------------------------------------------------ #
# the scope table
# ------------------------------------------------------------------ #
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = \S.*? ([\w\-]+)\((?:[^%)]*%([\w.\-]+))?")
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SCOPE = re.compile(r"(?<![\w.])(fl\.(?:race|ring|grad|eval|ctrl|serve))(?![\w.])")


def scope_of(op_name: str) -> str | None:
    """The innermost ``fl.*`` scope of an HLO ``op_name``, or None."""
    found = _SCOPE.findall(op_name)
    return found[-1] if found else None


def scope_map(hlo_text: str) -> dict[str, str]:
    """``{instruction name: scope}`` of an HLO module's text.

    Every instruction outside fused computations -- what a device trace
    names -- takes its own ``op_name``'s innermost scope (a fusion's is its
    root's); a fusion without one takes the most common scope of the
    instructions it fuses, and an instruction the compiler added without
    one (a copy, a bitcast) its first operand's.  Instructions with no scope
    are left out."""
    comps: dict[str, list] = {}
    fused: set[str] = set()
    body = None
    for line in hlo_text.splitlines():
        ins = _INSTRUCTION.match(line)
        if ins is None:
            head = _COMPUTATION.match(line)
            if head is not None:
                body = comps.setdefault(head.group(1), [])
            continue
        if body is None:
            continue
        name, opcode, operand = ins.groups()
        op = _OP_NAME.search(line)
        calls = _CALLS.search(line) if opcode == "fusion" else None
        if calls is not None:
            fused.add(calls.group(1))
        body.append((name, scope_of(op.group(1)) if op else None,
                     calls.group(1) if calls else None, operand))

    table = {}
    for comp, body in comps.items():
        if comp in fused:
            continue
        for name, sc, sub, operand in body:
            if sc is None and sub is not None:
                common = Counter(i[1] for i in comps.get(sub, ()) if i[1]).most_common(1)
                sc = common[0][0] if common else None
            if sc is None:
                sc = table.get(operand)
            if sc is not None:
                table[name] = sc
    return table


def hlo_text(compiled) -> str:
    """The optimized HLO of a ``jax.stages.Compiled``, with op metadata and
    without large constants (a runner may close over its clients' data)."""
    modules = compiled.runtime_executable().hlo_modules()
    return "\n\n".join(m.to_string() for m in modules)


def record_scopes(jitted, *args) -> dict[str, str]:
    """Add the scope table of ``jitted`` at ``args`` to `scope_table` and
    return it.  Call it after the runner's first call with the same
    arguments: lowering and compiling then hit JAX's caches."""
    table = scope_map(hlo_text(jitted.lower(*args).compile()))
    scope_table.update(table)
    return table
