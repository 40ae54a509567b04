"""Device time of host-to-device and device-to-host transfers in the
traced window, per batch the service ran (host-device transfer layer)."""


def read(ctx):
    batches = ctx["counts"]["batches"]
    transfer_s = ctx["trace"]["transfer_s"]
    if not batches or not transfer_s:
        return None
    return 1e3 * transfer_s / batches
