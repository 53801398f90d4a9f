"""What the server's spans leave unexplained of a round trip, in
milliseconds: the mean fleet round trip of the rounds completed in the
traced window, minus their mean admission wait (``queue_s``), minus the
request-weighted mean, over the window's verify calls, of ``sled.verify``
plus the ``sled.commit`` and ``sled.send`` that follow it.  Each call's
weight is its number of requests (``ctx.traced_rounds``).  A call is
matched to the ``sled.verify`` span that opened just before it: the one
whose start lies nearest the call's time on the profiler's clock, which
``ctx.t_open`` maps to the window's start.  Nearest, not containing: the
call starts microseconds into its span, and the profiler's host clock and
the monotonic clock can disagree by more than that, though by far less
than the milliseconds between two calls.  What remains is the frame's
wait in the socket and the loop, the wire and the fleet's own lag.  It is
a difference of means, not a mean of per-round differences: the rounds
and the calls are not matched one to one.  Nothing to read where the
program has no ``sled.verify`` span."""

import bisect


def _first_after(spans, t, until):
    """Duration of the first span that starts in [t, until), or None."""
    i = bisect.bisect_left(spans, (t,))
    if i < len(spans) and spans[i][0] < until:
        return spans[i][1] - spans[i][0]
    return None


def read(ctx):
    tr = ctx.trace
    if tr is None:
        return None
    by = {"sled.verify": [], "sled.commit": [], "sled.send": []}
    for name, a, b in tr.spans:
        if name in by:
            by[name].append((a, b))
    verify, commit, send = (sorted(by[k]) for k in ("sled.verify", "sled.commit", "sled.send"))
    done = [r for r in ctx.fleet["rounds"] if ctx.t_open <= r[1] <= ctx.t_close]
    if not verify or not done:
        return None
    starts = [a for a, _ in verify]
    weighted = weight = 0.0
    for r in ctx.traced_rounds:
        t = tr.window[0] + (r.t - ctx.t_open) * 1e9
        j = bisect.bisect_left(starts, t)
        i = min((k for k in (j - 1, j) if 0 <= k < len(starts)), key=lambda k: abs(starts[k] - t))
        a, b = verify[i]
        until = starts[i + 1] if i + 1 < len(verify) else float("inf")
        c, s = _first_after(commit, b, until), _first_after(send, b, until)
        if c is None or s is None:
            continue
        weighted += r.size * ((b - a) + c + s)
        weight += r.size
    if not weight:
        return None
    rtt = sum(r[1] - r[0] for r in done) / len(done)
    queue = sum(r[2] for r in done) / len(done)
    return 1e3 * (rtt - queue) - 1e-6 * weighted / weight
