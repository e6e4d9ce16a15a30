"""The score-top-k' kernel's share of its roofline, in %: the HBM bytes its
calls in the window must move (corpus read once per call, from the shapes),
at the chip's peak bandwidth, over its summed device time in the trace."""

from chipbench import kernels


def read(run):
    t = run["trace"].kernel_s.get("score_topk")
    if not t:
        return None
    cfg = run["config"]
    total = sum(kernels.score_topk_bytes(b, cfg["n_docs"], cfg["dim"],
                                         run["kprime"])
                for b in run["batch_sizes"])
    return 100.0 * total / run["peaks"]["hbm_bytes_per_s"] / t
