"""device_idle_share (%): the share of the traced window in which no
operation ran on the device, averaged over the cell's chips:
100 * (1 - busy_s / window_s), busy being the union of op intervals."""


def read(ctx):
    t = ctx["trace"]
    if t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
