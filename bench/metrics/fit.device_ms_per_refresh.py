"""fit.device_ms_per_refresh: device time of the synopsis-fit programs in
the window, per refresh the window inserted."""
from bench.metrics import layer_seconds


def read(ctx):
    sec = layer_seconds(ctx, "fit")
    if sec is None or ctx.refreshes <= 0:
        return None
    return sec * 1e3 / ctx.refreshes
