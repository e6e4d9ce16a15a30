"""Reduction of a JAX profiler trace (``.xplane.pb``) to the benchmark's
device numbers: busy time as the union of device-op intervals, each kernel's
summed device time by its stable name, the ops that took most time, and the
idle gaps of the device attributed to the benchmark's host annotations.

The trace is read with `jax.profiler.ProfileData`.  Device planes are those
named ``/device:TPU:<n>``; their ops are the events of the ``XLA Ops`` line.
Host annotations are the events whose name starts with ``ANNOTATION_PREFIX``
on any line of the ``/host:CPU`` plane (the harness opens one per engine
span, and one named ``WINDOW`` around the measured window).

On a v5e the device's timestamps run up to about a millisecond ahead of
the host's (a kernel shows as starting before the host call that
launched it), so a gap shorter than that is not attributed reliably.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
ANNOTATION_PREFIX = "chipbench/"
WINDOW = ANNOTATION_PREFIX + "window"
TOP = 10


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                       # mean over device planes
    devices: int
    kernel_s: Dict[str, float]          # kernel name -> summed device time
    kernel_calls: Dict[str, int]
    top_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]  # longest gaps, by host annotation
    ops: int

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted, disjoint cover of the given [start, end) intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: Sequence[Tuple[float, float]], lo: float,
         hi: float) -> List[Tuple[float, float]]:
    """The complement of a merged cover within [lo, hi)."""
    out, t = [], lo
    for s, e in clip(busy, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def op_name(text: str) -> str:
    """The stable name of a device op from its event name, which on the
    TPU is the HLO instruction (``%score_topk.1 = (f32[...]) custom-call(
    ...)``): the instruction's name without ``%`` and its ``.N`` suffix.
    A Pallas kernel's instruction carries the kernel's ``name``."""
    head = text.split(" = ", 1)[0].lstrip("%")
    base, _, num = head.rpartition(".")
    return base if base and num.isdigit() else head


def op_label(text: str) -> str:
    """Short label of a device op for the breakdown: its stable name and
    result type (``score_topk (f32[977,8,161]``)."""
    name = op_name(text)
    _, _, rest = text.partition(" = ")
    shape = rest.split("{", 1)[0].split(" ", 1)[0]
    return f"{name} {shape}" if shape else name


def kernel_of(ev, kernels: Sequence[str]) -> Optional[str]:
    name = op_name(ev.name)
    return name if name in kernels else None


def reduce_planes(planes, kernels: Sequence[str] = ()) -> Summary:
    """Reduce ``ProfileData.planes`` (or an equivalent sequence of objects
    with ``name``/``lines``/``events``) to a `Summary`."""
    host_marks: List[Tuple[float, float, str]] = []
    window: Optional[Tuple[float, float]] = None
    dev_ops: List[List[Tuple[float, float, str, Optional[str]]]] = []
    for plane in planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if not ev.name.startswith(ANNOTATION_PREFIX):
                        continue
                    s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                    if ev.name == WINDOW:
                        window = (s, e)
                    else:
                        host_marks.append((s, e, ev.name[len(
                            ANNOTATION_PREFIX):]))
        elif plane.name.startswith(DEVICE_PLANE_PREFIX):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    ops.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name, kernel_of(ev, kernels)))
            if ops:
                dev_ops.append(ops)
    if not dev_ops:
        raise ValueError("the trace has no device ops on a TPU plane")
    if window is None:
        window = (min(s for ops in dev_ops for s, *_ in ops),
                  max(e for ops in dev_ops for _, e, *_ in ops))
    lo, hi = window
    busy_ns = 0.0
    kernel_ns: Dict[str, float] = {}
    calls: Dict[str, int] = {}
    by_op: Dict[str, float] = {}
    idle: List[Tuple[float, float]] = []
    marks = sorted(host_marks)
    n_ops = 0
    for ops in dev_ops:
        inside = [o for o in ops if o[1] > lo and o[0] < hi]
        n_ops += len(inside)
        cover = union((s, e) for s, e, *_ in clip(
            [(s, e) for s, e, *_ in inside], lo, hi))
        busy_ns += sum(e - s for s, e in cover)
        for s, e, name, kern in inside:
            d = min(e, hi) - max(s, lo)
            label = op_label(name)
            by_op[label] = by_op.get(label, 0.0) + d
            if kern is not None:
                kernel_ns[kern] = kernel_ns.get(kern, 0.0) + d
                calls[kern] = calls.get(kern, 0) + 1
        idle.extend(gaps(cover, lo, hi))
    n_dev = len(dev_ops)
    idle.sort(key=lambda g: g[0] - g[1])
    longest = [(_attribute(marks, gs, ge), (ge - gs) * 1e-9)
               for gs, ge in idle[:TOP]]
    top = sorted(by_op.items(), key=lambda x: -x[1])[:TOP]
    return Summary(
        window_s=(hi - lo) * 1e-9, busy_s=busy_ns * 1e-9 / n_dev,
        devices=n_dev,
        kernel_s={k: v * 1e-9 / n_dev for k, v in kernel_ns.items()},
        kernel_calls=calls,
        top_ops=[(k, v * 1e-9 / n_dev) for k, v in top],
        idle_gaps=longest, ops=n_ops)


def _attribute(marks, gs: float, ge: float) -> str:
    """What the host was doing in the gap [gs, ge): each instant goes to
    the innermost (shortest) annotation open then, and the annotation with
    most of the gap names it.  "none" when no annotation was open: the
    host was outside every engine span."""
    longest = max((e - s for s, e, _ in marks), default=0.0)
    first = bisect.bisect_left(marks, (gs - longest,))
    over = []
    for s, e, name in marks[first:]:
        if s >= ge:
            break
        if e > gs:
            over.append((e - s, max(s, gs), min(e, ge), name))
    over.sort()
    taken: List[Tuple[float, float]] = []
    share: Dict[str, float] = {}
    for _dur, s, e, name in over:
        free = sum(b - a for a, b in gaps(union(taken), s, e))
        share[name] = share.get(name, 0.0) + free
        taken.append((s, e))
    idle_host = (ge - gs) - sum(b - a for a, b in clip(union(taken), gs, ge))
    if not share or idle_host >= max(share.values()):
        return "none"
    return max(share, key=share.get)


def reduce_file(path: str, kernels: Sequence[str] = ()) -> Summary:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes, kernels)
