"""Mean time a round waited in the server's admission queue
(``Verdict.queue_s``, the server's own host-clock span), over the rounds
completed in the traced window, in milliseconds.  The mean, not a tail:
most rounds wait not at all, so a tail of the waits swings from seed to
seed."""


def read(ctx):
    waits = [r[2] * 1e3 for r in ctx.fleet["rounds"] if ctx.t_open <= r[1] <= ctx.t_close]
    return sum(waits) / len(waits) if waits else None
