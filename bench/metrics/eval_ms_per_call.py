"""Device time of the eval per engine call, in ms.

Layer: model step.  The window's leaf device ops that the program's scope
table maps to ``fl.eval`` (one forward of the held-out batch at each call's
end), over the traced calls (`bench.metrics._scope`).  Moves
``updates_per_s``."""

from bench.metrics import _scope


def read(ctx):
    secs = _scope.scope_seconds(ctx, "fl.eval")
    if secs is None or not ctx.traced_calls:
        return None
    return 1e3 * secs / ctx.traced_calls
