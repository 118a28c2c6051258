"""Share of the traced window of an ingest cell in which no operation
ran on the device (1 - union of device-op intervals / window)."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or tr["window_s"] <= 0:
        return None
    return 1.0 - tr["busy_s"] / tr["window_s"]
