"""From a profiler trace to the numbers per-layer metrics read.

``jax.profiler`` writes an ``.xplane.pb``; ``xspace.read`` reads it with
each event's metadata stats, which ``jax.profiler.ProfileData`` drops. On
a TPU each chip is a plane ``/device:TPU:<n>`` whose
``XLA Ops`` line holds one event per operation run, nested where an
operation holds others (a while loop holds its body); the host is the
plane ``/host:CPU``, one line per thread. Every number here comes from
those events:

  busy       union of a chip's operation intervals
  self time  an operation's duration less that of the operations nested
             in it, so a loop and its body are not counted twice
  idle gaps  the stretches between busy intervals, each named after the
             host event that overlaps it most

An operation is known by its name (``fusion.12``, ``closed_call.3``) and
by the JAX scope that emitted it, such as
``jit(batch_search)/.../jit(page_scan)/.../pallas_call``: a kernel with
no name of its own is found by the latter. The scope is the event's
``tf_op`` stat; else the ``op_name`` of the instruction of that name in
the HLO of the module that the ``XLA Modules`` line shows running at the
op's start; else the op's HLO text (its ``long_name`` stat).
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import re
from collections import defaultdict

from bench import xspace

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"
HOST_PLANE = "/host:CPU"
NS = 1e-9


@dataclasses.dataclass
class Trace:
    window_s: float   # host-clock length of the traced span
    # per chip: (name, start_ns, end_ns, scope) of every operation, by start
    device: list
    # (name, start_ns, end_ns) of every host event with a duration
    host: list


def from_profile(data, window_s: float, span: str | None = None) -> Trace:
    """An ``xspace.Space`` -> Trace. No chip plane is an error:
    a trace without the device says nothing of it. Where the host plane
    holds an event named ``span`` (the run's ``TraceAnnotation`` around
    the seconds it means to trace), the chip's operations are clipped to
    it and ``window_s`` is its length: the profiler records from its start
    to its stop, which is longer, and on the same clock as the span."""
    device, host, seen = [], [], []
    scopes = _module_scopes(data.planes)
    for plane in data.planes:
        lines = list(plane.lines)
        seen.append(f"{plane.name}: {[ln.name for ln in lines]}")
        if DEVICE_PLANE.match(plane.name):
            ops = [ln for ln in lines if ln.name == OPS_LINE]
            if not ops:
                raise ValueError(f"{plane.name} has no {OPS_LINE!r} line")
            in_module = _module_at(lines)

            def scope(e) -> str:
                stats = dict(e.stats or ())
                return str(
                    stats.get("tf_op")
                    or scopes.get(in_module(e.start_ns), {}).get(_short(e.name))
                    or stats.get("long_name", ""))

            device.append(sorted(
                ((_short(e.name), int(e.start_ns), int(e.end_ns), scope(e))
                 for ln in ops for e in ln.events),
                key=lambda ev: ev[1],
            ))
        elif plane.name == HOST_PLANE:
            host.extend(
                (e.name, int(e.start_ns), int(e.end_ns))
                for ln in lines for e in ln.events if e.duration_ns > 0
            )
    if not device:
        raise ValueError("no TPU plane in the trace; planes: " + "; ".join(seen))
    spans = [(s, e) for name, s, e in host if name == span]
    if spans:
        # the span names no host work: keep it out of the gaps' labels
        host = [ev for ev in host if ev[0] != span]
        lo, hi = spans[0]
        window_s = (hi - lo) * NS
        device = [[(n, max(s, lo), min(e, hi), sc) for n, s, e, sc in ev
                   if e > lo and s < hi] for ev in device]
    return Trace(window_s, device, host)


def _short(name: str) -> str:
    """An op's name without the HLO text a TPU trace appends to it
    (``%fusion.157 = s32[15360]{0} fusion(...)`` -> ``fusion.157``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def _module_scopes(planes) -> dict[str, dict[str, str]]:
    """Module name -> {instruction name: JAX scope}, from the HLO the
    profiler stores in its metadata plane."""
    out = {}
    for plane in planes:
        if plane.name != METADATA_PLANE:
            continue
        for name, stats in getattr(plane, "meta", {}).values():
            hlo = stats.get(HLO_STAT)
            if isinstance(hlo, bytes):
                out[name] = xspace.op_names(hlo)
    return out


def _module_at(lines):
    """start_ns -> name of the ``XLA Modules`` event running then ("" if
    none): the module whose HLO names an op of the chip."""
    mods = sorted(((int(e.start_ns), int(e.end_ns), e.name)
                   for ln in lines if ln.name == MODULES_LINE
                   for e in ln.events))
    starts = [m[0] for m in mods]

    def at(t: float) -> str:
        i = bisect.bisect_right(starts, t) - 1
        return mods[i][2] if i >= 0 and t < mods[i][1] else ""

    return at


def read(log_dir: str, window_s: float, span: str | None = None) -> Trace:
    """The one ``.xplane.pb`` under ``log_dir``."""
    files = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    if len(files) != 1:
        raise ValueError(f"{len(files)} xplane files under {log_dir}")
    return from_profile(xspace.read(files[0]), window_s, span)


def merged(events) -> list[tuple[int, int]]:
    """The union of the events' (start, end) intervals, sorted."""
    out: list[list[int]] = []
    for _, s, e, *_ in sorted(events, key=lambda ev: ev[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_s(trace: Trace) -> float:
    """Seconds in which an operation ran, averaged over the chips."""
    per_chip = [sum(e - s for s, e in merged(ev)) * NS for ev in trace.device]
    return sum(per_chip) / len(per_chip)


def self_times(events, key=lambda ev: ev[0]) -> dict[str, float]:
    """Seconds of self time of one chip's operations, summed by ``key``
    (the name by default)."""
    out: dict[str, float] = defaultdict(float)
    stack: list[list] = []          # [key, end, self_ns]
    for ev in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        s, e = ev[1], ev[2]
        while stack and stack[-1][1] <= s:
            done = stack.pop()
            out[done[0]] += done[2] * NS
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([key(ev), e, e - s])
    for k, _, self_ns in stack:
        out[k] += self_ns * NS
    return dict(out)


def _gaps(events) -> list[tuple[int, int]]:
    spans = merged(events)
    return [(a[1], b[0]) for a, b in zip(spans, spans[1:]) if b[0] > a[1]]


def _label(gap: tuple[int, int], host) -> str:
    s, e = gap
    best, best_key = "no host event", (0, 0)
    for name, hs, he in host:
        over = min(e, he) - max(s, hs)
        key = (over, -(he - hs))
        if over > 0 and key > best_key:
            best, best_key = name, key
    return best


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The operations with the most self time and the longest idle gaps
    of the first chip, as [name, seconds] lists; an op is named by its
    HLO name and its JAX scope."""
    ops = sorted(self_times(trace.device[0],
                            key=lambda ev: f"{ev[0]} {ev[3]}".strip()).items(),
                 key=lambda kv: -kv[1])
    gaps = sorted(_gaps(trace.device[0]), key=lambda g: g[0] - g[1])[:top]
    return {
        "device_ops": [[n, s] for n, s in ops[:top]],
        "idle_gaps": [[_label(g, trace.host), (g[1] - g[0]) * NS]
                      for g in gaps],
    }


def op_seconds(trace: Trace, pattern: str) -> tuple[float, int]:
    """Self seconds and count of the operations whose name or scope
    matches ``pattern``, summed over the chips."""
    rx = re.compile(pattern)

    def hit(ev) -> bool:
        return bool(rx.search(ev[0]) or rx.search(ev[3]))

    secs, count = 0.0, 0
    for events in trace.device:
        secs += self_times(events, key=hit).get(True, 0.0)
        count += sum(1 for ev in events if hit(ev))
    return secs, count
