"""Host time of query encryption per request, in ms: the mean of the
engine's ``encrypt`` spans (one per request, host clock)."""


def read(run):
    d = [s.duration_s for s in run["spans"] if s.name == "encrypt"]
    return 1e3 * sum(d) / len(d) if d else None
