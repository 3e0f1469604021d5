"""The RNS chains' share of their roofline in the traced window.

The least time the chip could take for the verify items and CRT sign
rows the sidecar's ``launched`` counters say went to the device inside
the traced window (real items, not padded rows) over the device time of
the XLA modules in that window.  The bound that applies (FLOPs at these
shapes) is printed in the trace summary.  No module time, no items:
nothing returned.
"""

from benchmarks.reduce import rns_counts


def read(ctx: dict, args: dict):
    tr = ctx.get("trace")
    if not tr or tr["module_s"] <= 0:
        return None
    if tr["verify_items"] + tr["sign_rows"] <= 0:
        return None
    least = rns_counts.least_seconds(
        tr["verify_items"], tr["sign_rows"], ctx["device"]["kind"]
    )
    tr["roofline_bound"] = least["bound"]
    return 100.0 * least["seconds"] / tr["module_s"]
