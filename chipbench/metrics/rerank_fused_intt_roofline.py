"""The fused rotate -> Hadamard -> inverse-NTT kernel's share of its
roofline, in %: the HBM bytes of its calls in the window (one per RNS prime
per dispatch, from the shapes) at the chip's peak bandwidth, over its summed
device time in the trace.  Nothing to read where candidates are packed per
request: that path re-ranks with the separate NTT kernels."""

from chipbench import kernels


def read(run):
    t = run["trace"].kernel_s.get("rerank_fused_intt")
    if not t:
        return None
    cfg = run["config"]
    c = cfg["crypto"]
    g = kernels.rerank_geometry(cfg["dim"], run["kprime"], c["n_poly"],
                                c["chunk"])
    total = c["num_primes"] * sum(
        kernels.rerank_fused_intt_bytes(b, g["num_ct"], g["cands_per_ct"],
                                        g["chunks"], c["n_poly"])
        for b in run["batch_sizes"])
    return 100.0 * total / run["peaks"]["hbm_bytes_per_s"] / t
