"""device_idle: share of the traced window in which no op ran on the
device (1 - union of device op intervals / window)."""


def read(ctx):
    tr = ctx["trace"]
    if not tr["n_ops"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
