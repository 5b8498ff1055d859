"""Share of the traced stretch in which no operation ran on the device:
1 minus the union of the device's operation intervals over the stretch's
length on the host clock."""


def read(ctx):
    tr, dev = ctx["trace"], ctx["device"]
    if tr is None or not dev.get("window_s"):
        return None
    return 100.0 * (1.0 - dev["busy_s"] / dev["window_s"])
