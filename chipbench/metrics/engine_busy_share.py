"""Share of the window in which the engine ran a batch, in %: the union of
the engine's ``dispatch`` spans, clipped to the window, over the window's
length.  The engine, its tracer and the load generator all stamp
``time.monotonic``."""

from chipbench.trace import clip, union


def read(run):
    lg = run["log"]
    spans = [(s.t_start, s.t_end) for s in run["spans"]
             if s.name == "dispatch"]
    if not spans:
        return None
    busy = sum(e - s for s, e in union(clip(spans, lg.t0, lg.t_end)))
    return 100.0 * busy / (lg.t_end - lg.t0)
