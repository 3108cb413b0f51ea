"""Device time of the event race per server update, in us.

Layer: compiled engine.  The window's leaf device ops that the program's
scope table maps to ``fl.race``: the closed network's event race, dispatch
sampling, the queue statistics and the per-call draws, over the traced
updates (`bench.metrics._scope`).  Moves ``updates_per_s``."""

from bench.metrics import _scope


def read(ctx):
    v = _scope.per_update(ctx, "fl.race")
    return None if v is None else 1e6 * v
