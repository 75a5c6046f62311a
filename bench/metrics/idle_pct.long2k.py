"""Share of the traced window in which no op ran on the device."""


def read(x):
    tr = x["trace"]
    return 100.0 * tr.idle_share if tr.n_devices else None
