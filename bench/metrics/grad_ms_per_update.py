"""Device time of the client gradient per server update, in ms.

Layer: model step.  The window's leaf device ops that the program's scope
table maps to ``fl.grad`` (the gradient call: the client's batch slice, the
forward, its recompute and the backward, the Pallas SSD kernels under it;
see `bench.metrics._scope`), over the traced updates.  Moves
``updates_per_s``."""

from bench.metrics import _scope


def read(ctx):
    v = _scope.per_update(ctx, "fl.grad")
    return None if v is None else 1e3 * v
