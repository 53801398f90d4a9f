"""Host time on each verify call's critical path, in milliseconds: the mean,
over the program's ``sled.verify`` spans that start in the traced window,
of the span's duration (from the batch in hand to its results on the host)
minus the device time of the ``jit_paged_verify_step`` run that starts
inside it.  Spans without such a run are left out; nothing to read where
none has one."""

import bisect

SPAN = "sled.verify"
PROGRAM = "jit_paged_verify_step"


def read(ctx):
    tr = ctx.trace
    if tr is None:
        return None
    runs = sorted((a, b) for a, b, name in tr.runs if name == PROGRAM)
    starts = [a for a, _ in runs]
    lo, hi = tr.window
    host = []
    for name, a, b in tr.spans:
        if name != SPAN or not lo <= a < hi:
            continue
        i = bisect.bisect_left(starts, a)
        if i < len(runs) and runs[i][0] <= b:
            host.append((b - a) - (runs[i][1] - runs[i][0]))
    return 1e-6 * sum(host) / len(host) if host else None
