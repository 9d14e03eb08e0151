"""Device self time of the named kernels over device busy time."""
from chipbench import xplane


def read(ctx, params):
    if ctx.device is None:
        return None
    return 100.0 * xplane.kernel_seconds(
        ctx.device, params["kernels"]) / ctx.device["busy_s"]
