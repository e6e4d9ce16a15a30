"""Encrypted re-rank per request, in ms: the engine's ``score`` spans of
each batch summed, over the lanes they served.  Under tracing the span
ends when the score ciphertexts are ready on the device, so it holds the
candidate gather and the re-rank kernels (dense pool), or the host packing
and the NTT kernels (per-request candidates)."""


def read(run):
    total = lanes = 0.0
    for s in run["spans"]:
        if s.name == "score":
            total += s.duration_s
            lanes += s.attrs.get("lanes", 0)
    return 1e3 * total / lanes if lanes else None
