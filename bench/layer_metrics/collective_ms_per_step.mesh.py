"""Device time of the all-reduce operations per step in the traced
window, taken on the device that spent most (collectives layer)."""


def read(ctx):
    per_device = ctx["trace"]["collective_s"]
    steps = ctx["counts"]["steps"]
    if not steps or not per_device or not max(per_device):
        return None
    return 1e3 * max(per_device) / steps
