"""Device time of the snapshot ring per server update, in ms.

Layer: compiled engine.  The window's leaf device ops that the program's
scope table maps to ``fl.ring``: the gather of the completing task's
dispatch snapshot, the update ``w - scale*g`` of the float32 master and the
write of the new dispatch's snapshot (and the ring's fill at each call's
start), over the traced updates (`bench.metrics._scope`).  Moves
``updates_per_s``."""

from bench.metrics import _scope


def read(ctx):
    v = _scope.per_update(ctx, "fl.ring")
    return None if v is None else 1e3 * v
