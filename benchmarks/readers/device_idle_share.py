"""1 - union of the device's operation intervals / traced window, in %
(mean over the chips used).  A trace with no device operation: nothing
returned (and the harness refuses the run)."""


def read(ctx: dict, args: dict):
    tr = ctx.get("trace")
    if not tr or tr["busy_s"] <= 0 or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
