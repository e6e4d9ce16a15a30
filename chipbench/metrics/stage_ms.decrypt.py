"""Decryption per request, in ms: the engine's ``decrypt`` spans of each
batch summed, over the lanes their ``score`` spans served (as
``stage_ms.rerank`` counts them, so the two stages sum to it).  The
ciphertexts are ready when the span opens: it holds the users' decryption
and CRT on the host and the device work they call."""


def read(run):
    total = lanes = 0.0
    found = False
    for s in run["spans"]:
        if s.name == "decrypt":
            total += s.duration_s
            found = True
        elif s.name == "score":
            lanes += s.attrs.get("lanes", 0)
    return 1e3 * total / lanes if found and lanes else None
