"""Growth of counters over the measured window, as a plain count.

args: ``counters`` — list of ``scope:counter`` as in ``counter_ratio``.
A counter that did not grow reads 0; where no scrape holds a key of any
of the names (a program without the counter), nothing is returned.
"""

from benchmarks.readers.counter_per_window import _growth


def read(ctx: dict, args: dict):
    return _growth(ctx, args["counters"])
