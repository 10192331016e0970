"""Device idle share of the traced sweep window, in %: 1 - busy / window."""


def read(ctx):
    tr = ctx["trace"]
    if tr["window_s"] <= 0 or tr["n_device_events"] == 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
