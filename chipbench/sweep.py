#!/usr/bin/env python3
"""Find a poisson cell's knee by the rule in `chipbench.loadgen` (``GROWTH``,
``SLACK``, ``LOAD``): the highest rate of an ascending sweep at which the
time-averaged backlog of the windows' second halves stays within GROWTH
times that of their first halves plus SLACK, pooled over repeats.

    python3 chipbench/sweep.py --workload dense100k.poisson --seed 1 \\
        --seconds 40 --repeats 2 --rates 12 14 16 18 20

Sets the cell's configuration up once, then plays its open mix at each rate
in turn, ``--repeats`` windows of ``--seconds`` each (a fresh engine and
fresh requests each time), and stops after the first rate that is not
sustained.  Prints one JSON line per window, one per rate with the rule's
verdict, and last the knee and LOAD times it, the rate the cell's traffic
file takes.  The benchmark's runs never call this.
"""

from __future__ import annotations

import argparse
import sys

from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench.run import log, play, set_up  # noqa: E402

import numpy as np  # noqa: E402

from chipbench import loadgen, reference  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--repeats", type=int, default=2)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    su = set_up(args.workload, args.seed)
    if su.mix["loop"] != loadgen.OPEN:
        print("sweep: the cell's mix is not an open loop", file=sys.stderr)
        return 2
    system = su.system
    rates = sorted(args.rates)
    held = []
    for rate in rates:
        halves = []
        for rep in range(args.repeats):
            engine = system.engine()
            stream = args.seed * 1000 + len(halves) + 100 * len(held)
            _s, queries, lg = play(system, dict(su.mix, rate_rps=rate),
                                   args.seconds, engine, stream_seed=stream)
            engine.close()
            idx = lg.attempted()
            lat = lg.latencies(idx)
            lag = (lg.submit[idx] - lg.due[idx]) * 1e3
            summary = loadgen.rate_summary(lg)
            halves.append((summary["backlog_first_half"],
                           summary["backlog_second_half"]))
            recs = {lg.rid[r]: v for r, v in engine.records.items()}
            radius = reference.noise_radius_dev(
                queries, recs, su.cfg["dp_eps"])
            log(rate=rate, repeat=rep, **summary,
                latency_p50_s=float(np.percentile(lat, 50)),
                latency_p95_s=float(np.percentile(lat, 95)),
                lag_p99_ms=float(np.nanpercentile(lag, 99)),
                failed=int(np.sum(~lg.ok[idx])),
                mean_batch=float(np.mean(engine.batch_sizes)),
                longest_step_s=lg.longest_step_s,
                noise_radius_dev=radius)
        held.append(loadgen.sustained(halves))
        log(rate=rate, sustained=held[-1],
            backlog_halves_mean=np.mean(halves, axis=0).tolist())
        if not held[-1]:
            break
    k = loadgen.knee(rates, held)
    log(knee_rps=k, bracketed=not all(held),
        cell_rate_rps=None if k is None else loadgen.LOAD * k)
    return 0


if __name__ == "__main__":
    sys.exit(main())
