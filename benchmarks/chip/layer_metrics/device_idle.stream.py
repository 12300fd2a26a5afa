"""Share of the traced window, in %, in which no operation ran on the
device (averaged over the chips used)."""


def read(o):
    s = o.layer.get("trace")
    if s is None or not s.n_devices or s.window_s <= 0:
        return None
    return 100.0 * (1.0 - s.busy_s / s.window_s)
