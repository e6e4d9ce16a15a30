#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chip this process holds.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration and a traffic mix.  The
run sets the system up from ``--seed`` (corpus, documents, index, candidate
pool where the configuration has one, one session per tenant, a warm-up of
every batch shape), then plays the mix against the engine for ``--seconds``
and checks what the window served against the plain reference
(`chipbench.reference`).  ``--trace 1`` runs the same window under the JAX
profiler and reports the cell's per-layer metrics instead of its end-to-end
ones.

Standard output: an early line naming the device, a line with the set-up's
phases and the JAX compile events of its warm-up, a line counting the
compilations and garbage-collector pauses inside the window, and last one
JSON line with ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (and with ``--trace 1`` ``breakdown``), and ``checks``: each
number compared beside its limit.  The same numbers are the last lines of
standard error.  Exits 2 without a result when JAX's first device is not a
TPU or there are fewer devices than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse   # noqa: E402 — the set-up clock starts before any import
import dataclasses  # noqa: E402
import gc         # noqa: E402
import json       # noqa: E402
import sys        # noqa: E402
import tempfile   # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from chipbench import corpus, loadgen, reference, spec  # noqa: E402
from chipbench import trace as tr  # noqa: E402

KERNELS = ("score_topk", "rerank_fused_intt")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"


class NoChip(Exception):
    """JAX's devices are not the chips the cell asks for."""


def log(**record) -> None:
    print(json.dumps(record), flush=True)


def device_info(chips: int, require_tpu: bool = True) -> dict:
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    log(device=info)
    if require_tpu and (info["platform"] != "tpu" or len(devs) < chips):
        raise NoChip(f"cell needs {chips} TPU chip(s); JAX sees "
                     f"{len(devs)} {info['platform']} device(s)")
    return info


class EventTally:
    """Count and summed seconds of each JAX compile-path event (trace,
    lowering, backend compile or persistent-cache load) while ``active``."""

    def __init__(self):
        import jax

        self.active = False
        self.events: Dict[str, list] = {}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_kw) -> None:
        if self.active:
            tally = self.events.setdefault(event, [0, 0.0])
            tally[0] += 1
            tally[1] += secs

    def start(self) -> None:
        self.events, self.active = {}, True

    def count(self, event: str) -> int:
        return self.events.get(event, [0])[0]


class GcPauses:
    """The garbage collector's pauses while ``active``: a stop of the
    whole interpreter that lands on every request in flight."""

    def __init__(self):
        self.active = False
        self.pauses: list = []      # (generation, seconds)
        self._t: Optional[float] = None
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict) -> None:
        if not self.active:
            return
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t))
            self._t = None

    def summary(self) -> dict:
        d = [s for _, s in self.pauses]
        return {"collections": len(d),
                "gen2": sum(1 for g, _ in self.pauses if g == 2),
                "max_ms": 1e3 * max(d, default=0.0),
                "total_ms": 1e3 * sum(d)}


@dataclasses.dataclass
class SetUp:
    """One cell's configuration, mix and warmed system on this process's
    chip: what a run, a knee sweep and the control all start from."""
    cell: dict
    cfg: dict
    mix: dict
    dev: dict
    peaks: Optional[dict]
    system: object
    events: EventTally
    bench: dict


def set_up(cell_name: str, seed: int, *, root: Path = ROOT,
           require_tpu: bool = True) -> SetUp:
    """Find the cell's parts by name, check the chip, and set the system
    up from ``seed`` with every batch shape warmed."""
    bench = spec.load_benchmark(root)
    cell = spec.find_cell(bench, cell_name)
    cfg = spec.load_config(bench, cell["config"], root)
    mix = spec.load_traffic(cell["traffic"], root / "chipbench")
    dev = device_info(cell["chips"], require_tpu)

    from repro.launch.serve import enable_compile_cache

    from chipbench import kernels, serving

    peaks = None
    if require_tpu:
        log(compile_cache=enable_compile_cache())
        peaks = kernels.peaks(dev["kind"])
    events = EventTally()
    system = serving.System(cfg, seed, mix["tenants"])
    events.start()
    system.warm()
    events.active = False
    log(setup_phases_s=system.phases, warm_batches_s=system.warm_batches_s,
        warm_events=events.events)
    return SetUp(cell=cell, cfg=cfg, mix=mix, dev=dev, peaks=peaks,
                 system=system, events=events, bench=bench)


def play(system, mix: dict, seconds: float, engine,
         stream_seed: Optional[int] = None) -> tuple:
    """Play ``mix`` against ``engine`` for ``seconds``: returns the
    schedule, the true queries and the loadgen log.  The requests are
    drawn from the system's seed, or from ``stream_seed`` where given."""
    from chipbench import serving

    s = system.seeds if stream_seed is None else corpus.seeds(stream_seed)
    sched = loadgen.make_schedule(mix, seconds, system.cfg["n_docs"],
                                  np.random.default_rng(s["traffic"]))
    queries = corpus.queries_near(np.random.default_rng(s["queries"]),
                                  system.emb, sched.row, mix["query_jitter"])
    keys = serving.noise_keys(np.random.default_rng(s["keys"]), len(sched))
    names = system.tenant_names

    def submit_one(i: int) -> int:
        return engine.submit(names[sched.tenant[i]], queries[i], key=keys[i])

    if mix["loop"] == loadgen.OPEN:
        lg = loadgen.drive_open(engine, sched, submit_one, seconds)
    else:
        lg = loadgen.drive_closed(engine, sched, submit_one, seconds,
                                  mix["clients"])
    return sched, queries, lg


def end_to_end(lg: loadgen.Log, setup_s: float) -> dict:
    """Every end-to-end metric this run can give; the cell keeps its own."""
    idx = lg.attempted()
    ok = idx[lg.ok[idx]]
    lat = lg.latencies(idx)
    done_in = np.sum(lg.ok[idx] & (lg.done[idx] <= lg.t_end))
    wire = [lg.results[i].transcript.total_bytes for i in ok]
    out = {"setup_s": setup_s,
           "latency_p50_s": float(np.percentile(lat, 50)),
           "latency_p95_s": float(np.percentile(lat, 95)),
           "throughput_rps": float(done_in) / (lg.t_end - lg.t0)}
    if wire:
        out["wire_kb_per_req"] = float(np.mean(wire)) / 1000.0
    return out


def run_cell(cell_name: str, seed: int, seconds: float, traced: bool, *,
             root: Path = ROOT, require_tpu: bool = True,
             t_start: float = T_START) -> dict:
    """One run of one cell; returns the result line as a dict."""
    su = set_up(cell_name, seed, root=root, require_tpu=require_tpu)
    bench, cfg, mix, system = su.bench, su.cfg, su.mix, su.system
    e2e = spec.end_to_end_for(bench, cell_name)
    layers = spec.per_layer_for(bench, cell_name)
    readers = {m["name"]: spec.load_reader(m["name"], root / "chipbench")
               for m in layers} if traced else {}
    import jax

    from chipbench import serving

    tracer = serving.AnnotatingTracer() if traced else None
    engine = system.engine(tracer)
    pauses = GcPauses()
    tmp = tempfile.TemporaryDirectory(prefix="chipbench-trace-")
    try:
        if traced:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(tmp.name, profiler_options=opts)
        setup_s = time.monotonic() - t_start
        su.events.start()
        pauses.active = True
        try:
            with jax.profiler.TraceAnnotation(tr.WINDOW):
                _sched, queries, lg = play(system, mix, seconds, engine)
        finally:
            su.events.active = pauses.active = False
            if traced:
                t_stop = time.monotonic()
                jax.profiler.stop_trace()
        log(window={"compiles": su.events.count(COMPILE_EVENT),
                    "traces": su.events.count(TRACE_EVENT),
                    "seconds": seconds, "requests": int(len(lg.attempted())),
                    "gave_up": lg.gave_up,
                    "longest_step_s": lg.longest_step_s,
                    "gc": pauses.summary()})
        mem = [d.memory_stats() or {} for d in jax.devices()]
        peak = max(m.get("peak_bytes_in_use", 0) for m in mem)
        summary = None
        if traced:
            t_reduce = time.monotonic()
            summary = tr.reduce_file(tr.find_xplane(tmp.name), KERNELS)
            log(trace_s={"stop": t_reduce - t_stop,
                         "reduce": time.monotonic() - t_reduce})
    finally:
        tmp.cleanup()
    occupancy = engine.metrics.occupancy(system.ecfg.max_batch)
    spans = tracer.spans() if traced else []
    engine.close()
    records, batch_sizes = engine.records, engine.batch_sizes
    del engine
    system.index = system.sessions = None     # free the device state

    t_ref = time.monotonic()
    numbers = reference.compare(
        emb=system.emb, docs=system.docs, queries=queries,
        attempted=lg.attempted(), ok=lg.ok, results=lg.results,
        records={lg.rid[rid]: rec for rid, rec in records.items()
                 if rid in lg.rid},
        k=cfg["k"], kprime=system.plan.kprime,
        sq=cfg["crypto"]["scale_q_bits"], sc=cfg["crypto"]["scale_c_bits"],
        dp_eps=cfg["dp_eps"],
        rng=np.random.default_rng(system.seeds["sample"]))
    log(reference_s=time.monotonic() - t_ref)
    correct = reference.verdict(numbers)
    attempted = int(len(lg.attempted()))

    device = dict(su.dev, memory_peak_bytes=int(peak))
    out = {"correct": correct, "attempted": attempted,
           "failed": int(numbers["failed"])}
    if traced:
        run = {"loop": mix["loop"], "log": lg, "spans": spans,
               "trace": summary, "batch_sizes": batch_sizes,
               "occupancy": occupancy, "config": cfg,
               "kprime": system.plan.kprime, "peaks": su.peaks}
        metrics = {}
        for m in layers:
            value = readers[m["name"]](run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        out["metrics"] = metrics
        out["device"] = device
        out["breakdown"] = {
            "device_ops": [[n, s] for n, s in summary.top_ops],
            "idle_gaps": [[n, s] for n, s in summary.idle_gaps]}
    else:
        values = end_to_end(lg, setup_s)
        out["metrics"] = {m["name"]: {"value": values[m["name"]],
                                      "unit": m["unit"]}
                          for m in e2e if m["name"] in values}
        out["device"] = device
    out["checks"] = {name: {"value": numbers[name], "limit": limit}
                     for name, limit in reference.LIMITS.items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except (NoChip, spec.SpecError) as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
