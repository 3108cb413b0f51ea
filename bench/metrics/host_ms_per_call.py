"""Host time of an engine call outside its wait on the device, in ms.

Layer: server loop.  From the program's own host spans (`repro.obs.spans`,
``time.perf_counter_ns``): for each of the last ``ctx.traced_calls``
``fl.call`` spans -- the traced calls -- its length less that of the
``fl.wait`` span inside it (``block_until_ready``), averaged.  That is the
host's work per call: resolving the call, the runner memo, moving the
arguments, launching, and reading the results back.  Moves
``updates_per_s``."""

from bench.metrics import _scope


def read(ctx):
    obs = _scope.program_obs()
    spans = list(getattr(obs, "spans", ()))
    calls = sorted((s for s in spans if s[0] == "fl.call"), key=lambda s: s[1])
    calls = calls[-ctx.traced_calls:] if ctx.traced_calls else []
    if not calls:
        return None
    host = []
    for _, c0, c1, depth in calls:
        wait = sum(e - s for name, s, e, d in spans
                   if name == "fl.wait" and d == depth + 1 and c0 <= s and e <= c1)
        host.append(c1 - c0 - wait)
    return 1e-6 * sum(host) / len(host)
