"""One exponent class of the pow chain in the traced window: its share
of its roofline, or the whole step's share of the chip's peak.

args: ``mod_bits`` — the rows' modulus class (2048); ``exp_windows`` —
4-bit windows of a row's exponent (1026: a first-level threshold
fragment of a 2,048-bit key, 2 x 2,048 + up to ~5 bits; the program
scans the class's 1,040); ``module`` — the program's name
(``rns_pow_2048_e4160``); ``share`` — ``roofline`` (least time for the
rows over the device time of the modules of that name) or ``window``
(the rows' FLOPs PLUS those of the verify items and sign rows launched
inside the traced window, over window x chips x peak: the whole step's
share).  FLOPs follow ``reduce/rns_counts.py``'s rule — five products a
window and the 19 of table and framing, ``mont_flops(channels(
mod_bits))`` each — bytes ``rns_pow_width.row_bytes`` with the longer
exponent.

**Rows inside the traced window.**  ``run.py:trace_summary`` brackets
``verify.device`` and ``sign.device`` alone.  Where a later
``benchmark`` PR gives the summary ``modexp_rows`` (growth of
``modexp.device`` between the profiler's start and stop) that is the
count.  Until then: the signs launched inside the traced window
(``sign_rows`` / 2, exact, bracketed) times the window's
``sidecar:modexp.device`` / ``sidecar:sign.device`` — in a mix of one
kind of operation that ratio is the operation's own — and, where no
sign rode the device in the traced window, the window's rows by the
traced share of its seconds.  No module of that name (the parent of the
PR that brought the class), no rows: nothing returned.
"""

from benchmarks.reduce import rns_counts


def rows_in_trace(ctx: dict) -> float:
    tr = ctx["trace"]
    if "modexp_rows" in tr:
        return tr["modexp_rows"]
    rows = ctx["counters"].total("sidecar", "modexp.device")
    signs = ctx["counters"].total("sidecar", "sign.device")
    if tr.get("sign_rows", 0) > 0 and signs > 0:
        return tr["sign_rows"] / 2 * rows / signs
    if ctx["window_s"] <= 0:
        return 0.0
    return rows * tr["window_s"] / ctx["window_s"]


def row_flops(mod_bits: int, exp_windows: int) -> float:
    products = 5 * exp_windows + (rns_counts.SIGN_PRODUCTS - 5 * 256)
    return products * rns_counts.mont_flops(rns_counts.channels(mod_bits))


def row_bytes(mod_bits: int, exp_windows: int) -> float:
    """Base in (uint8 half digits), exponent windows in, key index in,
    residues out."""
    return mod_bits // 8 + exp_windows + 4 + mod_bits // 8


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
    flops = rows * row_flops(bits, windows)
    if args["share"] == "window":
        flops += (tr.get("verify_items", 0) * rns_counts.verify_flops()
                  + tr.get("sign_rows", 0) * rns_counts.sign_row_flops())
        chips = max(1, tr["devices_used"])
        return 100.0 * flops / (tr["window_s"] * chips * peaks["flops_per_s"])
    least = max(flops / peaks["flops_per_s"],
                rows * row_bytes(bits, windows) / peaks["bytes_per_s"])
    return 100.0 * least / module_s
