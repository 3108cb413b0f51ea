"""Model FLOP utilization of the whole step over the traced window, in %.

Layer: model step.  The model's FLOPs of the traced calls -- per update the
gradient's forward and backward (3x the forward's matmul work, recompute not
counted), per call one eval forward -- over the window's wall time, the
chips and the chip's bf16 peak (`bench.peaks`).  Moves ``updates_per_s``."""

from bench import peaks


def read(ctx):
    if ctx.trace is None or not ctx.traced_calls:
        return None
    peak = peaks.for_kind(ctx.device_kind)["bf16_flops_per_s"]
    d = ctx.traffic["data"]
    seq = d.get("seq_len", 1)
    upd = ctx.model.flops(d["batch"], seq)["update"]
    ev = ctx.model.flops(d["eval_rows"], seq)["forward"]
    work = ctx.traced_calls * (ctx.updates_per_call * upd + ev)
    return 100.0 * work / ctx.trace.window_s / (ctx.chips * peak)
