"""Device time of the Mosaic kernels in the traced window per image slot
run, padded slots included (kernels layer, serve cells)."""


def read(ctx):
    counts = ctx["counts"]
    slots = counts["requests"] + counts["padded_slots"]
    kernel_s = ctx["trace"]["kernel_s"]
    if not slots or not kernel_s:
        return None
    return 1e3 * kernel_s / slots
