"""Device busy time in the traced window per lane cell evaluated in it.
All device work of a lane cell is the lane program."""


def read(ctx):
    tr = ctx["trace"]
    cells = ctx["counters"].get("cells")
    if ctx["driver"] != "lanes" or tr is None or not tr["devices"] or not cells:
        return None
    if tr["busy_s"] <= 0:
        return None
    return 1e3 * tr["busy_s"] / cells
