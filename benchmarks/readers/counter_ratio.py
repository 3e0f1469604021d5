"""A ratio of counter growth over the measured window.

args: ``num`` and ``den`` — lists of ``scope:counter`` (``scope`` is
``sidecar`` or ``daemons``, summed over label sets and, for daemons,
over the processes); ``den`` may instead be ``"ops"`` (operations
committed in the window).  ``scale`` multiplies the result (100 for a
share in %, 1000 for "per thousand operations").  Nothing to divide by:
nothing returned.
"""


def _total(ctx: dict, names: list[str]) -> float:
    total = 0.0
    for spec in names:
        scope, _, counter = spec.partition(":")
        total += ctx["counters"].total(scope, counter)
    return total


def read(ctx: dict, args: dict):
    den = ctx["ops"] if args["den"] == "ops" else _total(ctx, args["den"])
    if den <= 0:
        return None
    return args.get("scale", 1.0) * _total(ctx, args["num"]) / den
