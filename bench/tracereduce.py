"""Reduce a ``jax.profiler`` trace of one measured window to numbers.

Device operations are the events of the device planes' "XLA Ops" lines
(on a TPU: ``/device:TPU:<n>``).  Busy time is the union of their
intervals inside the window, so overlapping operations count once; an
idle gap is a stretch of the window with no operation on the device,
labelled by what the host was doing at its middle: the innermost of the
program's host spans (``repro:...``, on the profiler's clock) open
there, else the benchmark's own annotation (``bench:...``).  The window
itself is the ``bench:window`` annotation.  Only ``jax`` is needed to
read the trace.
"""

from __future__ import annotations

import dataclasses
import glob
import os

WINDOW = "bench:window"
PREFIX = "bench:"
#: prefix of the program's host spans
PROGRAM = "repro:"


@dataclasses.dataclass
class Trace:
    """Device ops per device ((name, start_ns, end_ns) each), the host
    annotations of program and benchmark and the window's bounds, in
    ns."""

    ops: dict[str, list[tuple[str, float, float]]]
    annotations: list[tuple[str, float, float]]
    lo: float
    hi: float

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9


def newest_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except Exception:  # noqa: BLE001 - a stat the reader cannot decode
        return {}


def read(path: str, device_prefix: str = "/device:TPU") -> Trace:
    """Parse one ``.xplane.pb``.  Device ops are the "XLA Ops" line of
    each plane whose name starts with ``device_prefix``; where a plane has
    no such line (the CPU backend runs ops on host threads), events that
    carry an ``hlo_op`` stat are taken instead."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: dict[str, list] = {}
    notes = []
    for plane in pd.planes:
        lines = list(plane.lines)
        if plane.name.startswith(device_prefix):
            xla = [ln for ln in lines if ln.name == "XLA Ops"]
            found = []
            for ln in xla or lines:
                for ev in ln.events:
                    if xla or "hlo_op" in _stats(ev):
                        found.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
            if found:
                ops.setdefault(plane.name, []).extend(found)
        if plane.name.startswith("/host"):
            for ln in lines:
                for ev in ln.events:
                    if ev.name.startswith((PROGRAM, PREFIX)):
                        notes.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    win = [n for n in notes if n[0] == WINDOW]
    if not win:
        raise ValueError(f"{path}: no {WINDOW!r} annotation")
    lo, hi = win[0][1], win[0][2]
    return Trace(ops=ops, annotations=[n for n in notes if n[0] != WINDOW],
                 lo=lo, hi=hi)


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged intervals clipped to [lo, hi]."""
    out: list[list[float]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for _, a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_s(trace: Trace) -> float:
    """Seconds some operation ran, averaged over the devices traced."""
    if not trace.ops:
        return 0.0
    tot = sum(sum(b - a for a, b in union(evs, trace.lo, trace.hi))
              for evs in trace.ops.values())
    return tot * 1e-9 / len(trace.ops)


def gaps(trace: Trace) -> list[tuple[float, float]]:
    """Idle stretches of the window on the first device."""
    if not trace.ops:
        return [(trace.lo, trace.hi)]
    busy = union(next(iter(sorted(trace.ops.items())))[1], trace.lo,
                 trace.hi)
    out, t = [], trace.lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < trace.hi:
        out.append((t, trace.hi))
    return out


def label(trace: Trace, t: float) -> str:
    """The innermost program span (``repro:``) open at ``t``; else the
    innermost benchmark annotation (``bench:``); else
    ``bench:unlabelled``."""
    for prefix in (PROGRAM, PREFIX):
        open_ = [(b - a, name) for name, a, b in trace.annotations
                 if name.startswith(prefix) and a <= t < b]
        if open_:
            return min(open_)[1]
    return "bench:unlabelled"


def idle_gaps(trace: Trace, top: int = 10) -> list[list]:
    """The longest idle gaps as [label, seconds], longest first, each
    labelled by ``label`` at its middle."""
    gs = sorted(gaps(trace), key=lambda g: g[0] - g[1])[:top]
    return [[label(trace, (a + b) / 2), (b - a) * 1e-9] for a, b in gs]


#: HLO control flow: their events span the ops of their bodies, which
#: the trace lists too
CONTAINERS = ("while", "conditional", "call")


def op_name(event_name: str) -> str:
    """An event's HLO instruction name: a TPU trace names each op by
    its HLO text, ``%disco_band_contract.31 = f32[...] custom-call(...)``;
    the name is ``disco_band_contract.31``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def op_family(event_name: str) -> str:
    """The name without its instance number: ``disco_band_contract``."""
    name = op_name(event_name)
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def op_seconds(trace: Trace) -> dict[str, float]:
    """Device seconds per op family inside the window, per device (the
    mean over devices); control-flow containers are left out."""
    out: dict[str, float] = {}
    for evs in trace.ops.values():
        for name, a, b in evs:
            fam = op_family(name)
            d = min(b, trace.hi) - max(a, trace.lo)
            if d > 0 and fam not in CONTAINERS:
                out[fam] = out.get(fam, 0.0) + d * 1e-9
    n = max(len(trace.ops), 1)
    return {k: v / n for k, v in out.items()}


def top_ops(trace: Trace, top: int = 10) -> list[list]:
    return [[k, v] for k, v in sorted(op_seconds(trace).items(),
                                      key=lambda kv: -kv[1])[:top]]


def kernel_seconds(trace: Trace, kernel: str,
                   span_s: float | None = None) -> tuple[float, int]:
    """Device seconds (mean over devices) and event count of the ops
    whose family names ``kernel``, in the window's first ``span_s``
    seconds (the whole window without it): a Pallas call is named after
    the function that makes it (``disco_band_contract``), and under
    ``vmap`` after the transformed one (``vmap_jit_disco_band_contract__``)."""
    hi = trace.hi if span_s is None else min(trace.hi,
                                             trace.lo + span_s * 1e9)
    secs, n = 0.0, 0
    for evs in trace.ops.values():
        for name, a, b in evs:
            if kernel in op_family(name):
                d = min(b, hi) - max(a, trace.lo)
                if d > 0:
                    secs += d * 1e-9
                    n += 1
    k = max(len(trace.ops), 1)
    return secs / k, n
