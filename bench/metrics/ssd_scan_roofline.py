"""Share of its roofline that the program's ``ssd_scan`` Pallas kernel
reaches in the traced window, in %.

Layer: kernels.  The kernel's device time is the summed duration of its
events (`bench.trace`, matched by the kernel's name); its least time is, per
call, the larger of its FLOPs over the bf16 peak and its bytes over the HBM
bandwidth (`bench.kernels.ssd_scan`, from the shapes of the cell's update
batch; the cell's eval batch has the same shape).  Moves ``updates_per_s``."""

from bench import peaks
from bench.kernels import ssd_scan

#: the kernel's events in the trace: its custom call, named after the kernel
PATTERN = r"^%?ssd_scan[.\w]* = .*custom-call"


def read(ctx):
    if ctx.trace is None:
        return None
    n, secs = ctx.trace.kernel(PATTERN)
    if n == 0 or secs <= 0:
        return None
    pk = peaks.for_kind(ctx.device_kind)
    d = ctx.traffic["data"]
    shape = ctx.model.ssd_call_shape(d["batch"], d["seq_len"])
    t_min, _ = ssd_scan.least_time(shape, pk["bf16_flops_per_s"], pk["hbm_bytes_per_s"])
    return 100.0 * n * t_min / secs
