"""Mean number of real requests per verify round dispatched in the window
(the engine's own batch sizes, before bucket padding)."""


def read(ctx):
    if not ctx.rounds:
        return None
    return sum(r.size for r in ctx.rounds) / len(ctx.rounds)
