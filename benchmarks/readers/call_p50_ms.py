"""Median duration of the window's calls of one kind, in ms.

args: ``kind`` — ``insert``, ``update`` or ``read``.
"""

import statistics


def read(ctx: dict, args: dict):
    times = [c.t_done - c.t_send for c in ctx["calls"] if c.kind == args["kind"]]
    if not times:
        return None
    return 1000.0 * statistics.median(times)
