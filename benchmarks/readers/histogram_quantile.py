"""A quantile of one of the sidecar's histograms over the window, from
the growth of its cumulative-style buckets (``name.bucket{...,le=X}``
counts the observations that fell in the bucket ending at X).

args: ``metric`` (e.g. ``admission.wait``), ``labels`` (e.g.
``resource=sidecar``), ``q``, ``scale``.  The quantile is read at the
upper edge of the bucket it falls in, interpolated from the lower edge:
a reading, not a sample.
"""


def read(ctx: dict, args: dict):
    prefix = f"{args['metric']}.bucket{{{args['labels']},le="
    buckets = []
    for key, growth in ctx["counters"].items("sidecar"):
        if key.startswith(prefix) and growth > 0:
            buckets.append((float(key[len(prefix):-1]), growth))
    if not buckets:
        return None
    buckets.sort()
    want = args["q"] * sum(n for _le, n in buckets)
    seen, lower = 0.0, 0.0
    for le, n in buckets:
        if seen + n >= want:
            return args.get("scale", 1.0) * (lower + (le - lower) * (want - seen) / n)
        seen, lower = seen + n, le
    return args.get("scale", 1.0) * buckets[-1][0]
