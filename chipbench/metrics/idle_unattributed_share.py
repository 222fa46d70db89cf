"""Device: the share of the first device's idle time (the reduction's
gaps) during which no program span was open other than the containers,
which span a whole fleet step or a client's dispatch-to-resolve and so
name no work of their own. What the idle breakdown cannot explain."""

from chipbench.trace_reduce import _union

CONTAINERS = frozenset({"runtime/fleet_step", "runtime/step",
                        "runtime/distill", "runtime/supervised"})


def read(ctx):
    gaps = ctx.reduction.gaps
    idle = sum(t - s for s, t in gaps)
    if idle <= 0:
        return None
    covered, spans = 0.0, _union(
        [e for e in ctx.spans if e.name not in CONTAINERS])
    i = 0
    for s, t in sorted(gaps):
        while i < len(spans) and spans[i][1] <= s:
            i += 1
        j = i
        while j < len(spans) and spans[j][0] < t:
            covered += min(t, spans[j][1]) - max(s, spans[j][0])
            j += 1
    return 100.0 * (1.0 - covered / idle)
