#!/usr/bin/env python3
"""The control of the scan check: the plain reference put in the program's
place one precision down.  The configuration's scan is a float32 inner
product; the control computes it in bfloat16 (f32 accumulation), the step a
later change would be tempted to take, and must read above ``scan_gap``'s
limit where the program reads below it.

    python3 chipbench/control.py --workload dense100k.poisson --seed 5 \\
        --seconds 4

Sets the cell up from the seed, plays its traffic for ``--seconds`` (all
requests are finished and recorded), then, over every served request's
perturbed query, prints one JSON line: the widest scan gap of the program's
candidates and of the control's, against the float64 reference.  The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys

from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench.run import play, set_up  # noqa: E402

import numpy as np  # noqa: E402

from chipbench import reference  # noqa: E402


def bf16_candidates(emb, perturbed: np.ndarray, kprime: int) -> np.ndarray:
    """Top-k' ids of each query by a bfloat16 inner product (float32
    accumulation) over ``emb`` (a device array or a host array)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def scan(q, e):
        s = jnp.dot(q.astype(jnp.bfloat16), e.astype(jnp.bfloat16).T,
                    preferred_element_type=jnp.float32)
        return jax.lax.top_k(s, kprime)[1]

    return np.asarray(scan(jnp.asarray(perturbed, jnp.float32),
                           jnp.asarray(emb)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    su = set_up(args.workload, args.seed)
    system = su.system
    engine = system.engine()
    _sched, _q, lg = play(system, su.mix, args.seconds, engine)
    engine.close()
    recs = [engine.records[rid] for rid in sorted(engine.records)]
    pert = np.stack([r[0] for r in recs])
    kprime = system.plan.kprime
    program = reference.scan_gaps(system.emb, pert, [r[1] for r in recs],
                                  kprime)
    ctrl_ids = bf16_candidates(system.index.embeddings, pert, kprime)
    control = reference.scan_gaps(system.emb, pert, list(ctrl_ids), kprime)
    pick = np.random.default_rng(system.seeds["sample"]).choice(
        len(recs), size=min(reference.SAMPLE, len(recs)), replace=False)
    print(json.dumps({
        "workload": args.workload, "seed": args.seed, "device": su.dev,
        "requests": len(recs), "failed": int(np.sum(~lg.ok[lg.attempted()])),
        "program_scan_gap": float(program.max()),
        "program_scan_gap_p50": float(np.median(program)),
        "control_scan_gap": float(control.max()),
        "control_scan_gap_min": float(control.min()),
        "control_scan_gap_p50": float(np.median(control)),
        "control_scan_gap_sampled": float(control[pick].max()),
        "program_scan_gap_sampled": float(program[pick].max()),
        "limit": reference.LIMITS["scan_gap"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
