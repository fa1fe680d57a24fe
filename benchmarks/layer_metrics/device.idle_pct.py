"""Device: 1 - (union of the intervals in which any operation runs on the
device) / traced window, on the busiest device, in %.  Profiler trace."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["window_s"] or not trace["busy_by_device"]:
        return None
    return 100.0 * (1.0 - max(trace["busy_by_device"]) / trace["window_s"])
