"""Growth of counters over the measured window, per second of the
window — a share of the window where the counter counts seconds — or,
with ``den``, per unit of other counters' growth.

args: ``num`` — list of ``scope:counter`` as in ``counter_ratio``;
``den`` — optional, the same kind of list (left out: the window's
seconds); ``scale`` multiplies the result (100 for a share in %).
Unlike ``counter_ratio`` this reader tells a counter that did not grow
from one the program does not have: where no scrape holds a key of any
``num`` name (the parent of the PR that adds the counter), nothing is
returned.  Nothing to divide by: nothing returned.
"""


def _growth(ctx: dict, names: list[str]) -> float | None:
    total, found = 0.0, False
    for spec in names:
        scope, _, counter = spec.partition(":")
        for key, growth in ctx["counters"].items(scope):
            if key == counter or key.startswith(counter + "{"):
                total, found = total + growth, True
    return total if found else None


def read(ctx: dict, args: dict):
    num = _growth(ctx, args["num"])
    den = _growth(ctx, args["den"]) if "den" in args else ctx["window_s"]
    if num is None or not den or den <= 0:
        return None
    return args.get("scale", 1.0) * num / den
