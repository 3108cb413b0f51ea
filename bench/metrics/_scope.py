"""Shared part of the readers of the program's own layers.

The program (`repro.obs`) names the layers of its fused runner with
``jax.named_scope`` (``fl.race``, ``fl.ring``, ``fl.grad``, ``fl.eval``, ...)
and, when it builds a runner, records a scope table ``{HLO instruction name:
scope}`` from the compiled module.  The trace's device ops are named by HLO
instruction (`bench.trace.op_name`), so a scope's device time is the summed
duration, inside the traced window, of the leaf ops the table maps to it.
The program also keeps its host spans (``fl.call`` and its children) in
memory.  Both outlive the release of the program's state, so the readers run
after the check like the others.  A program without them (an older one)
gives nothing to read: the readers return None.
"""
from __future__ import annotations

import importlib

from bench import trace as tr


def program_obs():
    """The program's `repro.obs` module, or None where it has none."""
    try:
        return importlib.import_module("repro.obs")
    except ImportError:
        return None


def scope_seconds(ctx, scope: str) -> float | None:
    """Device seconds of the window's leaf ops in ``scope``; None where the
    program recorded no scope table or no op of the window maps to it."""
    obs = program_obs()
    table = getattr(obs, "scope_table", None)
    if ctx.trace is None or not table:
        return None
    t0, t1 = ctx.trace.window
    n, tot = 0, 0
    for s, e, text in ctx.trace.ops:
        if e > t0 and s < t1 and table.get(tr.op_name(text)) == scope:
            n += 1
            tot += min(e, t1) - max(s, t0)
    return tot * 1e-9 if n else None


def per_update(ctx, scope: str) -> float | None:
    """Device seconds of ``scope`` per server update of the traced calls."""
    secs = scope_seconds(ctx, scope)
    if secs is None or not ctx.traced_calls:
        return None
    return secs / (ctx.traced_calls * ctx.updates_per_call)
