"""Share of the traced window in which device 0 is idle between program
runs while the host is not waiting for work, in percent: the idle gaps of
``Trace.idle_gaps`` (same midpoint rule) whose innermost ``sled.*`` span is
anything but the program's ``sled.await_work`` (the server's loop waiting
on an empty queue).  This is the idle time the host's own work holds the
device back by; the rest of ``device_idle_share`` is demand (``sled.await_work``)
or waits inside a program run.  Nothing to read where the program has no
``sled.verify`` span."""

AWAIT = "sled.await_work"


def read(ctx):
    tr = ctx.trace
    if tr is None or tr.window_s <= 0 or not any(s[0] == "sled.verify" for s in tr.spans):
        return None
    host = 0.0
    for label, seconds in tr.idle_gaps(n=1 << 30):
        where = label.rsplit(" (", 1)[0]  # drop "(<n> gaps)"
        if not where.startswith("inside ") and where.split(" > ", 1)[0] != AWAIT:
            host += seconds
    return 100.0 * host / tr.window_s
