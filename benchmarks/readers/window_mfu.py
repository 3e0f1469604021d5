"""The whole traced window's share of the chip's peak FLOP/s: the FLOPs
the launched verify items and sign rows need, over window x chips x
peak.  Bounds every kernel's roofline share from above once idle time
counts: a kernel taken off the path leaves its roofline silent, this
number stays.
"""

from benchmarks.reduce import rns_counts


def read(ctx: dict, args: dict):
    tr = ctx.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    if tr["verify_items"] + tr["sign_rows"] <= 0:
        return None
    peaks = rns_counts.load_peaks(ctx["device"]["kind"])
    flops = (tr["verify_items"] * rns_counts.verify_flops()
             + tr["sign_rows"] * rns_counts.sign_row_flops())
    chips = max(1, tr["devices_used"])
    return 100.0 * flops / (tr["window_s"] * chips * peaks["flops_per_s"])
