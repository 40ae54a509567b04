"""Device time of the Mosaic kernels in the traced window, summed over
all devices, per image completed (kernels layer, batch cells)."""


def read(ctx):
    images = ctx["counts"]["images"]
    kernel_s = ctx["trace"]["kernel_s"]
    if not images or not kernel_s:
        return None
    return 1e3 * kernel_s / images
