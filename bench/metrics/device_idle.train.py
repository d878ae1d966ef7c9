"""device_idle.train: percent of the traced window in which no operation
ran on the device, averaged over the chips (profiler trace)."""


def read(ctx):
    s = ctx.get("trace")
    if ctx["kind"] != "train" or s is None or s["n_ops"] == 0:
        return None
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])
