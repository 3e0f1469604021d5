"""Share, in %, of the seconds of the trace's longest idle gaps whose
label (``host:<span>``, from ``reduce/xplane.py``) matches.

args: ``labels`` — prefixes a gap's label has to start with to count —
or ``not_labels`` — prefixes it must not start with.  No trace, or a
trace with no gap: nothing returned.
"""


def read(ctx: dict, args: dict):
    tr = ctx.get("trace")
    gaps = tr.get("idle_gaps") if tr else None
    total = sum(s for _label, s in gaps) if gaps else 0.0
    if total <= 0:
        return None
    if "labels" in args:
        hit = sum(s for label, s in gaps
                  if label.startswith(tuple(args["labels"])))
    else:
        hit = sum(s for label, s in gaps
                  if not label.startswith(tuple(args["not_labels"])))
    return 100.0 * hit / total
