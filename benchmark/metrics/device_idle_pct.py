"""The device's idle share of the traced window: 100 * (1 - busy / window),
busy being the union of the intervals in which an op ran on the device."""


def read(ctx):
    t = ctx["trace"]
    if not t["devices"] or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
