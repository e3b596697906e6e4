"""Share of the profiled stretch in which no operation ran on the device
(1 - union of op intervals / stretch), averaged over the chips."""


def read(ctx):
    if ctx["window_s"] <= 0 or not ctx["events"]["device"]:
        return None
    return 100.0 * (1.0 - ctx["busy_s"] / ctx["window_s"])
