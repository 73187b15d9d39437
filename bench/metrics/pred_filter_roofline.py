"""Share of the HBM roofline the ``pred_filter`` kernel reached, in %.

The least time is the bytes the window's launches needed
(``bench/roofline.py``: touched int32 columns over live blocks, plus one
output bit per row and binding) over the chip's HBM bandwidth
(``bench/peaks.py``); the kernel time is the summed device time of the
kernel's events in the trace.  Nothing to read without a trace, a kernel
event or a recorded launch.
"""

from bench.trace import kernel_time

# a TPU trace names a device op by its HLO instruction, e.g.
# "%pred_filter_batch.1 = s32[1,6001664]{...} custom-call(...)"
KERNEL = r"^%pred_filter\w*(\.\d+)? = .*custom-call"


def read(ctx):
    if not ctx.records or not ctx.launch_bytes or ctx.peaks is None:
        return None
    seconds, n = kernel_time(ctx.records, KERNEL)
    if not n or seconds <= 0:
        return None
    least = ctx.launch_bytes / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / seconds
