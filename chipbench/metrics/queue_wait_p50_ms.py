"""How long a request waits in the engine before its batch starts, in ms:
the median of the engine's ``queue_wait`` spans (enqueue to dispatch
start) of the requests enqueued inside the window.  The engine, its
tracer and the load generator all stamp ``time.monotonic``."""

import numpy as np


def read(run):
    lg = run["log"]
    waits = [s.duration_s for s in run["spans"]
             if s.name == "queue_wait" and lg.t0 <= s.t_start < lg.t_end]
    return 1e3 * float(np.median(waits)) if waits else None
