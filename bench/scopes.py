"""Device time per named scope of the program, and the device's module
executions, from the ``jax.profiler`` trace of one measured window.

The program runs each operator of a model family, and the noise and
products of the engine's scan body, under a ``jax.named_scope``
(``SCOPES``: every family's ``bench/counts/<family>.py`` ``SCOPES`` and
the engine's two, the program's ``repro.telemetry.SCOPES``).  On a TPU
each op of the device plane's "XLA Ops" line has event metadata whose
``tf_op`` stat is the op's name stack
(``jit(chunk)/while/body/.../<family>.<operator>/...``): its scope is
the innermost name of ``SCOPES`` in that stack, and an op with none is
unscoped.  ``jax.profiler.ProfileData`` does not show event metadata,
so ``name_stacks`` reads it from the ``.xplane.pb`` protobuf itself (a
few wire-format fields, with the standard library only).  An op name
whose stacks give two different scopes (two programs with the same op
text) is attributed to neither: it counts as unscoped and is listed by
``conflicts``.

    python3 -m bench.scopes bench/.cache/trace/full_forecast

prints, for the newest trace under a directory, the device seconds per
scope and the op families in each, the chunk program's executions and
the idle gaps labelled by program spans (``tracereduce.label``).
"""

from __future__ import annotations

import dataclasses
import functools
import glob
import json
import os
import statistics
import sys

from bench import family, tracereduce

#: the engine's scan body, which every family's chunk program runs
ENGINE_SCOPES = ("engine.noise", "engine.products")
UNSCOPED = "unscoped"
#: the engine's chunk program on the device's "XLA Modules" line
#: (``jax.jit`` of the engine's ``chunk`` function)
CHUNK_MODULE = "jit_chunk"
#: where ``bench/run.py`` writes the traces of ``--trace 1`` runs
TRACES = os.path.join(os.path.dirname(os.path.abspath(__file__)), ".cache",
                      "trace")


# ---------------------------------------------------------------------------
# Event metadata from the protobuf
# ---------------------------------------------------------------------------

def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf):
    """(field number, value) of one protobuf message: ints for varints,
    memoryviews for length-delimited and fixed-width fields."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} not supported")
        yield key >> 3, value


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


def name_stacks(path: str, device_prefix: str = "/device:TPU",
                stat: str = "tf_op") -> dict[str, set[str]]:
    """Per op name on the device planes (an event's name: on a TPU its
    HLO text), the name stacks its event metadata carries in ``stat``.
    Messages read: XSpace.planes (1); XPlane name (2), event_metadata
    (4) and stat_metadata (5), map entries of key (1) and value (2);
    XEventMetadata name (2) and stats (5); XStatMetadata id (1) and name
    (2); XStat metadata_id (1), str_value (5) and ref_value (7, the id
    of the stat metadata whose name is the string)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: dict[str, set[str]] = {}
    for num, plane in _fields(space):
        if num != 1:
            continue
        name, events, stat_names = "", [], {}
        for pnum, value in _fields(plane):
            if pnum == 2:
                name = _text(value)
            elif pnum == 4:
                events.extend(v for k, v in _fields(value) if k == 2)
            elif pnum == 5:
                for k, v in _fields(value):
                    if k == 2:
                        md = dict(_fields(v))
                        stat_names[md.get(1, 0)] = _text(md.get(2, b""))
        if not name.startswith(device_prefix):
            continue
        wanted = {i for i, n in stat_names.items() if n == stat}
        for ev in events:
            ev_name, stacks = "", []
            for k, v in _fields(ev):
                if k == 2:
                    ev_name = _text(v)
                elif k == 5:
                    st = dict(_fields(v))
                    if st.get(1) not in wanted:
                        continue
                    if 5 in st:
                        stacks.append(_text(st[5]))
                    elif 7 in st:
                        stacks.append(stat_names.get(st[7], ""))
            if stacks:
                out.setdefault(ev_name, set()).update(stacks)
    return out


# ---------------------------------------------------------------------------
# Scopes
# ---------------------------------------------------------------------------

def vocabulary() -> tuple[str, ...]:
    """Every family's ``SCOPES`` (``bench/counts/<family>.py``, families
    by name), then the engine's, each name once."""
    names = [s for c in family.all_counts() for s in c.SCOPES]
    return tuple(dict.fromkeys(names + list(ENGINE_SCOPES)))


SCOPES = vocabulary()


def scope_of(stack: str) -> str | None:
    """The innermost name of ``SCOPES`` in a name stack; a component
    may come wrapped by a transformation (``vmap(<scope>)``)."""
    for part in reversed(stack.split("/")):
        part = part.rstrip(")").rsplit("(", 1)[-1]
        if part in SCOPES:
            return part
    return None


def _scope(stacks: set[str] | None) -> str:
    found = {scope_of(s) for s in stacks or ()}
    return found.pop() if len(found) == 1 and None not in found \
        else UNSCOPED


def conflicts(stacks: dict[str, set[str]]) -> list[str]:
    """Op names whose stacks give more than one scope (or a scope and
    none): they are attributed to no scope."""
    return sorted(n for n, s in stacks.items()
                  if len({scope_of(x) for x in s}) > 1)


def scope_seconds(trace: tracereduce.Trace, stacks: dict[str, set[str]],
                  span_s: float | None = None
                  ) -> tuple[dict[str, float], dict[str, int]]:
    """Device seconds (mean over devices) and event counts per scope,
    and under ``UNSCOPED``, of the ops in the window's first ``span_s``
    seconds (the whole window without it), each op clipped to that
    stretch; control-flow containers are left out, as
    ``tracereduce.op_seconds`` leaves them out, so over the whole window
    the scopes partition its op time."""
    hi = trace.hi if span_s is None else min(trace.hi,
                                             trace.lo + span_s * 1e9)
    secs: dict[str, float] = {}
    counts: dict[str, int] = {}
    for evs in trace.ops.values():
        for name, a, b in evs:
            if tracereduce.op_family(name) in tracereduce.CONTAINERS:
                continue
            d = min(b, hi) - max(a, trace.lo)
            if d > 0:
                scope = _scope(stacks.get(name))
                secs[scope] = secs.get(scope, 0.0) + d * 1e-9
                counts[scope] = counts.get(scope, 0) + 1
    k = max(len(trace.ops), 1)
    return {s: v / k for s, v in secs.items()}, counts


# ---------------------------------------------------------------------------
# Modules and program spans
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Profile:
    """What the metrics read besides ``tracereduce.Trace``: op name
    stacks and the device's module executions (name, start_ns, end_ns),
    with the window's bounds."""

    stacks: dict[str, set[str]]
    modules: list[tuple[str, float, float]]
    lo: float
    hi: float


def load(path: str, device_prefix: str = "/device:TPU") -> Profile:
    """Read one ``.xplane.pb``: module executions from the "XLA
    Modules" line of the first device plane, the window's annotation
    and the op name stacks (``name_stacks``)."""
    from jax.profiler import ProfileData
    modules, win = [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(device_prefix) and not modules:
            for ln in plane.lines:
                if ln.name == "XLA Modules":
                    modules = [(ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns)
                               for ev in ln.events]
        if plane.name.startswith("/host"):
            for ln in plane.lines:
                win.extend((ev.start_ns, ev.start_ns + ev.duration_ns)
                           for ev in ln.events
                           if ev.name == tracereduce.WINDOW)
    if not win:
        raise ValueError(f"{path}: no {tracereduce.WINDOW!r} annotation")
    return Profile(stacks=name_stacks(path, device_prefix), modules=modules,
                   lo=win[0][0], hi=win[0][1])


def module_runs(profile: Profile, module: str = CHUNK_MODULE,
                span_s: float | None = None) -> list[float]:
    """Seconds of each execution of ``module`` (its name before the
    program id: ``jit_chunk(1631...)``) that starts inside the window and
    ends by its first ``span_s`` seconds (by its end without it)."""
    hi = profile.hi if span_s is None else min(profile.hi,
                                               profile.lo + span_s * 1e9)
    return [(b - a) * 1e-9 for name, a, b in profile.modules
            if name.split("(", 1)[0] == module
            and profile.lo <= a and b <= hi]


# ---------------------------------------------------------------------------
# For the per-layer metrics
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=1)
def _load_once(path: str, mtime: float) -> Profile:
    """``load`` once for the nine readers of one run."""
    return load(path)


def for_run(run: dict) -> Profile | None:
    """The ``Profile`` of the trace ``run["trace"]`` was read from: the
    newest trace under ``TRACES``, whose window must be that trace's;
    None without a trace."""
    trace = run.get("trace")
    paths = glob.glob(os.path.join(TRACES, "**", "*.xplane.pb"),
                      recursive=True)
    if trace is None or not paths:
        return None
    path = max(paths, key=os.path.getmtime)
    profile = _load_once(path, os.path.getmtime(path))
    if (profile.lo, profile.hi) != (trace.lo, trace.hi):
        return None
    return profile


def _scoped(run: dict):
    """Per-scope seconds and counts up to the last completion, or None
    where no op carries a scope (a program without named scopes)."""
    profile = for_run(run)
    if profile is None or not run["member_steps"]:
        return None
    secs, counts = scope_seconds(run["trace"], profile.stacks,
                                 run["busy_to_s"])
    if not any(s in counts for s in SCOPES):
        return None
    return secs, counts


def ms_per_member_step(run: dict, scope: str) -> float | None:
    """Device milliseconds of ``scope`` per member-step completed: its
    device seconds from the window's start to the last completion (the
    stretch ``member_steps_per_s`` divides by), over those member-steps.
    None where the scope has no op there."""
    scoped = _scoped(run)
    if scoped is None or scope not in scoped[1]:
        return None
    return 1e3 * scoped[0][scope] / run["member_steps"]


def unscoped_share(run: dict) -> float | None:
    """Unscoped device op time over all device op time, up to the last
    completion, as a percentage."""
    scoped = _scoped(run)
    if scoped is None:
        return None
    secs = scoped[0]
    return 100.0 * secs.get(UNSCOPED, 0.0) / sum(secs.values())


def chunk_device_s(run: dict) -> float | None:
    """Median device time of one execution of the chunk program inside
    the window, up to the last completion (an execution still running
    at the window's end can come cut short)."""
    profile = for_run(run)
    runs = (module_runs(profile, span_s=run["busy_to_s"])
            if profile is not None else [])
    return statistics.median(runs) if runs else None


# ---------------------------------------------------------------------------
# By hand
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__.split("\n\n")[-1].strip(), file=sys.stderr)
        return 2
    path = (argv[0] if argv[0].endswith(".xplane.pb")
            else tracereduce.newest_xplane(argv[0]))
    trace = tracereduce.read(path)
    profile = load(path)
    secs, counts = scope_seconds(trace, profile.stacks)
    families: dict[str, dict[str, float]] = {}
    for evs in trace.ops.values():
        for name, a, b in evs:
            fam = tracereduce.op_family(name)
            d = min(b, trace.hi) - max(a, trace.lo)
            if d > 0 and fam not in tracereduce.CONTAINERS:
                per = families.setdefault(_scope(profile.stacks.get(name)),
                                          {})
                per[fam] = per.get(fam, 0.0) + d * 1e-9 / len(trace.ops)
    print(json.dumps({
        "trace": path, "window_s": trace.window_s,
        "scope_seconds": secs, "scope_events": counts,
        "scope_ops": {s: sorted(f.items(), key=lambda kv: -kv[1])[:15]
                      for s, f in families.items()},
        "conflicts": conflicts(profile.stacks)[:20],
        "chunk_runs_s": module_runs(profile),
        "idle_gaps": tracereduce.idle_gaps(trace)}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
