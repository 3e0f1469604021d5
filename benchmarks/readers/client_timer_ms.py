"""Mean duration of one timer of the client, in ms per observation.

The one client lives in the harness process, so its timers are in this
process's own registry and no scrape brackets them: the mean is over
the process's life, warm calls included (a few of some hundred).

args: ``timer`` — a name the client observes once per call, such as
``client.write_many.phase_verify``.  A program without the registry or
without the timer: nothing returned.
"""


def read(ctx: dict, args: dict):
    try:
        from bftkv_tpu.metrics import registry
    except ImportError:
        return None
    h = registry.histograms().get(args["timer"])
    if not h or not h["count"]:
        return None
    return 1000.0 * h["sum"] / h["count"]
