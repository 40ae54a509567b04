"""The definition's operations for the images completed in the traced
window (``bench/work.py``), divided by the Mosaic kernels' device time
summed over all devices: an achieved rate in 1e9 operations a second
(kernels layer, batch cells)."""


def read(ctx):
    kernel_s = ctx["trace"]["kernel_s"]
    n = int(ctx["config"]["n"])
    ops = sum(count * ctx["work"].ops_per_image(kind, n)
              for kind, count in ctx["counts"]["ops"].items())
    if not ops or not kernel_s:
        return None
    return ops / kernel_s / 1e9
