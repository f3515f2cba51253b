"""device_idle.dsm: the share of the traced window in which the device
ran no operation (1 - busy union / window), in a DSM cell."""


def read(ctx):
    tr = ctx.get("trace")
    if tr is None or not tr.devices() or tr.window_s <= 0:
        return None
    return (1.0 - tr.busy_s() / tr.window_s) * 100.0
