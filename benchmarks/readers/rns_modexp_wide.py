"""One exponent class of the WIDE pow chain in the traced window: its
share of its roofline, or the whole step's share of the chip's peak.

The same reading as ``rns_modexp_class.py`` — the same args
(``mod_bits``, ``exp_windows``, ``module``, ``share``) and the same
count of rows inside the traced window — with the row's operations and
bytes from ``reduce/rns_wide_counts.py``: the wide chain's channel
count, which the 12-bit rule of ``reduce/rns_counts.py`` has no answer
for.  No module of that name (a program without the wide chain), no
rows: nothing returned.
"""

from benchmarks.readers.rns_modexp_class import rows_in_trace
from benchmarks.reduce import rns_counts, rns_wide_counts


def read(ctx: dict, args: dict):
    tr = ctx.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    module_s = sum(s for name, s in tr["modules"] if args["module"] in name)
    rows = rows_in_trace(ctx)
    if module_s <= 0 or rows <= 0:
        return None
    bits, windows = int(args["mod_bits"]), int(args["exp_windows"])
    peaks = rns_counts.load_peaks(ctx["device"]["kind"])
    flops = rows * rns_wide_counts.row_flops(bits, windows)
    if args["share"] == "window":
        flops += (tr.get("verify_items", 0) * rns_counts.verify_flops()
                  + tr.get("sign_rows", 0) * rns_counts.sign_row_flops())
        chips = max(1, tr["devices_used"])
        return 100.0 * flops / (tr["window_s"] * chips * peaks["flops_per_s"])
    least = max(flops / peaks["flops_per_s"],
                rows * rns_wide_counts.row_bytes(bits, windows)
                / peaks["bytes_per_s"])
    return 100.0 * least / module_s
