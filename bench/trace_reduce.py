"""From a profiler trace to the benchmark's device numbers.

A traced run writes the JAX profiler's ``.xplane.pb``. Its device planes
(``/device:TPU:<i>``) hold one line of XLA operations and one of XLA programs
("modules"); the host plane holds the benchmark's own spans, written with
``jax.profiler.TraceAnnotation`` around every call it makes into the session.
This module reduces those to:

* busy time: the union of the intervals in which an operation ran on a
  device, inside the traced window, averaged over the devices used;
* per-program and per-operation device time;
* the idle gaps between busy intervals, each named by the host span that was
  open at its midpoint;
* a kernel's roofline share: the bytes its work needs over its device time
  times the chip's peak bandwidth, with the peaks looked up by
  ``device_kind`` in ``peaks.json``.

The functions work on plain ``Interval`` lists, so they can be checked on a
synthetic trace as well as on a recorded one.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass(frozen=True)
class Interval:
    name: str
    start_ns: float
    end_ns: float

    @property
    def dur_ns(self) -> float:
        return self.end_ns - self.start_ns


@dataclasses.dataclass
class Trace:
    """One traced run: device operations and programs per device, and the
    host spans named in ``span_names``."""

    ops: dict[str, list[Interval]]       # device plane -> XLA operations
    modules: dict[str, list[Interval]]   # device plane -> XLA programs
    spans: list[Interval]                # host spans, any thread

    def window(self, name: str = "window") -> Interval:
        """The host span that bounds the measured window."""
        found = [s for s in self.spans if s.name == name]
        if not found:
            raise ValueError(f"no {name!r} span in the trace")
        return max(found, key=lambda s: s.dur_ns)


def load(log_dir: str | os.PathLike, span_names) -> Trace:
    """Read the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(os.fspath(log_dir), "**",
                                          "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    wanted = set(span_names)
    ops: dict[str, list[Interval]] = {}
    modules: dict[str, list[Interval]] = {}
    spans: list[Interval] = []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = _intervals(line.events)
                elif line.name == MODULES_LINE:
                    modules[plane.name] = _intervals(line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(_intervals(e for e in line.events
                                        if e.name in wanted))
    return Trace(ops=ops, modules=modules, spans=spans)


def _intervals(events) -> list[Interval]:
    return [Interval(e.name, float(e.start_ns),
                     float(e.start_ns) + float(e.duration_ns))
            for e in events]


def clip(intervals, lo: float, hi: float) -> list[Interval]:
    """The parts of ``intervals`` inside ``[lo, hi]``."""
    out = []
    for iv in intervals:
        s, e = max(iv.start_ns, lo), min(iv.end_ns, hi)
        if e > s:
            out.append(Interval(iv.name, s, e))
    return out


def union(intervals) -> list[tuple[float, float]]:
    """Merged ``(start, end)`` pairs covering ``intervals``."""
    merged: list[list[float]] = []
    for iv in sorted(intervals, key=lambda i: i.start_ns):
        if merged and iv.start_ns <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], iv.end_ns)
        else:
            merged.append([iv.start_ns, iv.end_ns])
    return [(s, e) for s, e in merged]


def busy_seconds(trace: Trace, window: Interval) -> float:
    """Seconds in which an operation ran, averaged over the devices traced."""
    if not trace.ops:
        return 0.0
    total = 0.0
    for ivs in trace.ops.values():
        total += sum(e - s for s, e in
                     union(clip(ivs, window.start_ns, window.end_ns)))
    return total / len(trace.ops) * 1e-9


def matching(intervals, patterns) -> list[Interval]:
    """Events whose name matches any of the regular expressions."""
    rx = [re.compile(p) for p in patterns]
    return [iv for iv in intervals if any(r.search(iv.name) for r in rx)]


def program_executions(trace: Trace, window: Interval, patterns
                       ) -> tuple[int, float]:
    """``(executions, device seconds)`` of the programs whose module name
    matches ``patterns``, inside the window, over all devices traced."""
    n, secs = 0, 0.0
    for ivs in trace.modules.values():
        hits = clip(matching(ivs, patterns), window.start_ns, window.end_ns)
        n += len(hits)
        secs += sum(iv.dur_ns for iv in hits) * 1e-9
    return n, secs


def kernel_seconds(trace: Trace, window: Interval, patterns,
                   within=None) -> float:
    """Device seconds of the operations whose name matches ``patterns``;
    with ``within``, only of those that run inside a program whose module
    name matches ``within``."""
    total = 0.0
    for plane, ivs in trace.ops.items():
        hits = clip(matching(ivs, patterns), window.start_ns, window.end_ns)
        if within is not None:
            progs = matching(trace.modules.get(plane, []), within)
            hits = [iv for iv in hits if _inside(iv, progs)]
        total += sum(iv.dur_ns for iv in hits)
    return total * 1e-9


def _inside(iv: Interval, progs: list[Interval]) -> bool:
    return any(p.start_ns <= iv.start_ns < p.end_ns for p in progs)


def idle_gaps(trace: Trace, window: Interval, top: int = 10
              ) -> list[list]:
    """The ``top`` longest gaps between busy intervals on the first device,
    each as ``[host span open at its midpoint, seconds]``."""
    if not trace.ops:
        return []
    plane = sorted(trace.ops)[0]
    busy = union(clip(trace.ops[plane], window.start_ns, window.end_ns))
    edges = [window.start_ns] + [x for se in busy for x in se] \
        + [window.end_ns]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    spans = [s for s in trace.spans if s.name != "window"]
    out = []
    for s, e in gaps[:top]:
        mid = (s + e) / 2
        open_ = [sp for sp in spans if sp.start_ns <= mid < sp.end_ns]
        name = min(open_, key=lambda sp: sp.dur_ns).name if open_ else "none"
        out.append([name, (e - s) * 1e-9])
    return out


_OPCODE = re.compile(r" ([a-z][\w\-]*)\(")


def op_label(hlo: str) -> str:
    """``%fusion.63 fusion`` from an operation's HLO text (the trace names an
    operation by its whole instruction)."""
    lhs, _, rhs = hlo.partition(" = ")
    m = _OPCODE.search(" " + rhs)
    return f"{lhs} {m.group(1)}" if rhs and m else hlo[:80]


def program_label(module: str) -> str:
    """``alert_write_step`` from ``jit_alert_write_step(1204...)``."""
    name = module.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def top_ops(trace: Trace, window: Interval, top: int = 10) -> list[list]:
    """``[[program/operation, device seconds], ...]``: the ``top``
    operations that took most time, summed by program and operation over
    the devices traced."""
    import bisect

    total: dict[str, float] = {}
    for plane, ivs in trace.ops.items():
        mods = sorted(trace.modules.get(plane, []), key=lambda m: m.start_ns)
        starts = [m.start_ns for m in mods]
        for iv in clip(ivs, window.start_ns, window.end_ns):
            k = bisect.bisect_right(starts, iv.start_ns) - 1
            prog = program_label(mods[k].name) \
                if k >= 0 and iv.start_ns < mods[k].end_ns else "?"
            key = f"{prog} {op_label(iv.name)}"
            total[key] = total.get(key, 0.0) + iv.dur_ns * 1e-9
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])
            [:top]]


# ---------------------------------------------------------------- roofline
def peaks(device_kind: str, path: str | os.PathLike = PEAKS) -> dict:
    """The published peaks of one chip of ``device_kind``. An unknown kind
    is an error: no default peak stands in for a chip not in the table."""
    table = json.loads(Path(path).read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}")
    return table[device_kind]


def reduce_bytes(edges: int, rows: int, width: int) -> int:
    """Bytes a segment reduction needs: each real edge's ``width`` float32
    values and its int32 destination index read once, each destination
    row's ``width`` float32 values written once. Lane padding and the
    implementation's own buffers are not work the reduction needs."""
    return int(edges) * (4 * int(width) + 4) + int(rows) * 4 * int(width)


def roofline_share(need_bytes: float, kernel_s: float,
                   peak_bytes_per_s: float) -> float | None:
    """Per cent of the bandwidth roofline: the least time the bytes need at
    peak, over the time the kernel took. ``None`` without kernel time."""
    if kernel_s <= 0:
        return None
    return 100.0 * need_bytes / (kernel_s * peak_bytes_per_s)
