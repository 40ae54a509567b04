"""Share of the traced window in which no operation ran on the device,
averaged over the devices used (device layer, batch cells)."""


def read(ctx):
    t = ctx["trace"]
    if not t["window_s"] or not t["busy_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
