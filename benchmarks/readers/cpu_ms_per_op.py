"""CPU milliseconds a group of processes spent per committed operation
(user + system time from ``/proc``, over the measured window).

args: ``who`` — ``client`` (the harness process, callers included),
``daemons`` (all replica daemons) or ``sidecar``.
"""


def read(ctx: dict, args: dict):
    if ctx["ops"] <= 0:
        return None
    return 1000.0 * ctx["cpu_s"][args["who"]] / ctx["ops"]
