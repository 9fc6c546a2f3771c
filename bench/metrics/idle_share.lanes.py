"""Share of the traced window in which no operation ran on the device, in
a lane cell: 1 - busy union / window (``bench/xplane.py``)."""


def read(ctx):
    tr = ctx["trace"]
    if ctx["driver"] != "lanes" or tr is None or not tr["devices"]:
        return None
    return 100.0 * tr["idle_share"]
