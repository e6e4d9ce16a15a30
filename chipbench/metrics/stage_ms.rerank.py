"""Encrypted re-rank and decryption per request, in ms: the engine's
``score`` and ``decrypt`` spans of each batch summed, over the lanes they
served.  The two are read together: ``score`` returns device arrays without
waiting for them, so its device time lands in ``decrypt``."""


def read(run):
    total = lanes = 0.0
    for s in run["spans"]:
        if s.name in ("score", "decrypt"):
            total += s.duration_s
        if s.name == "score":
            lanes += s.attrs.get("lanes", 0)
    return 1e3 * total / lanes if lanes else None
