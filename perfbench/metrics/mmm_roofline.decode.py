"""The MMM kernel's roofline share inside the decode program: the least
time its calls need at their call shapes (per call the larger of
operations at peak and bytes at peak bandwidth) over the device time of the
MMM kernel's events in that program."""
import devtrace as trace

KERNEL = "_mmm_impl"


def read(ctx):
    s, calls = ctx.get("trace"), ctx.get("calls") or {}
    prog = "jit__decode_fn"
    if not s or prog not in calls:
        return None
    roof = calls[prog]["roof_s_mean"].get(KERNEL)
    t = trace.kernel_time(s, KERNEL, {prog})
    if roof is None or t <= 0:
        return None
    return 100.0 * roof * s["module_n"].get(prog, 0) / t
