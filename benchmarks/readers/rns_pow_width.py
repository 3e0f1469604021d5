"""One width's pow chain in the traced window: its share of its
roofline, or its FLOPs' share of the chip's peak over the whole window.

args: ``row_bits`` — the width of the chain's rows (1536: the CRT
halves of RSA-3072 signs; the module is ``jit_rns_pow_<row_bits>``);
``share`` — ``roofline`` (least time for the rows over the device time
of that module) or ``window`` (the rows' FLOPs over window x chips x
peak).  The rows are the trace summary's ``sign_rows``: two a
signature launched inside the traced window, real rows and not padded
ones — so this reads a cell whose identities have ONE width, which a
configuration's ``key_bits`` says.  FLOPs and bytes follow
``reduce/rns_counts.py``: one Montgomery product costs
``mont_flops(channels(row_bits))``, a row takes five products per
4-bit window of its exponent and the 19 of table and framing that
``SIGN_PRODUCTS`` holds for 1024 bits; at ``row_bits`` 1024 a row is
``sign_row_flops()`` and ``sign_row_bytes()`` exactly.  No rows, no
module of that name (the parent of the PR that brought the width):
nothing returned.
"""

from benchmarks.reduce import rns_counts


def row_flops(row_bits: int) -> float:
    products = rns_counts.SIGN_PRODUCTS + 5 * (row_bits - 1024) // 4
    return products * rns_counts.mont_flops(rns_counts.channels(row_bits))


def row_bytes(row_bits: int) -> float:
    """Base in (uint8 half digits), exponent windows in, key index in,
    residues out."""
    return row_bits // 8 + row_bits // 4 + 4 + row_bits // 8


def read(ctx: dict, args: dict):
    tr = ctx.get("trace")
    if not tr or tr.get("sign_rows", 0) <= 0:
        return None
    bits = int(args["row_bits"])
    rows = tr["sign_rows"]
    module_s = sum(s for name, s in tr["modules"]
                   if f"rns_pow_{bits}" in name)
    if module_s <= 0:
        return None
    peaks = rns_counts.load_peaks(ctx["device"]["kind"])
    flops = rows * row_flops(bits)
    if args["share"] == "window":
        if tr["window_s"] <= 0:
            return None
        chips = max(1, tr["devices_used"])
        return 100.0 * flops / (tr["window_s"] * chips * peaks["flops_per_s"])
    least = max(flops / peaks["flops_per_s"],
                rows * row_bytes(bits) / peaks["bytes_per_s"])
    return 100.0 * least / module_s
