"""Host time the serving loop spends around the verify calls, per call, in
milliseconds: the time in the traced window whose innermost program span
is ``sled.recv``, ``sled.plan``, ``sled.commit`` or ``sled.send``, over the
``sled.verify`` spans that start in the window.  Program spans are the
``sled.*`` spans but the harness's own (``run.Recorder``'s and the window).
Nothing to read where the program has no ``sled.verify`` span."""

LOOP = ("sled.recv", "sled.plan", "sled.commit", "sled.send")
HARNESS = ("sled.window", "sled.verify_dispatch", "sled.admit", "sled.step", "sled.retire")


def read(ctx):
    tr = ctx.trace
    if tr is None:
        return None
    lo, hi = tr.window
    spans = sorted(
        (max(a, lo), min(b, hi), name) for name, a, b in tr.spans
        if name not in HARNESS and b > lo and a < hi
    )
    calls = sum(1 for name, a, _ in tr.spans if name == "sled.verify" and lo <= a < hi)
    if not calls:
        return None
    # sweep the span edges: between two edges the innermost open span is
    # the one that opened last (of two that opened together, the shorter)
    edges = sorted({t for a, b, _ in spans for t in (a, b)})
    active, j, loop_ns = [], 0, 0.0
    for t0, t1 in zip(edges, edges[1:]):
        while j < len(spans) and spans[j][0] <= t0:
            active.append(spans[j])
            j += 1
        active = [s for s in active if s[1] > t0]
        if active and max(active, key=lambda s: (s[0], -s[1]))[2] in LOOP:
            loop_ns += t1 - t0
    return 1e-6 * loop_ns / calls
