"""The program's own instrumentation, read from a traced run's profiler trace.

The session names its work in two ways (README, "Tracing"):

* device scopes: ``jax.named_scope("eagr.<part>")`` inside its jitted
  steps. XLA keeps the scope path in each operation's metadata, and a TPU
  trace carries it as the ``tf_op`` stat of the operation's event
  metadata, e.g. ``jit(alert_write_step)/while/body/closed_call/
  eagr.level_gather/gather``. ``jax.profiler.ProfileData`` exposes events'
  own stats but not their metadata's, so :func:`op_paths` reads that stat
  from the ``.xplane.pb`` itself;
* host spans: ``jax.profiler.TraceAnnotation("eagr.<part>", batch=...)``
  around the session's ``update``, ``read``, ``drain_fired`` and ``flush``
  and the ring's dispatch, routing, expansion, transfer, waits and fired-set
  readback, with their arguments as stats.

``trace_reduce.load`` keeps neither (only operation names and the
benchmark's own spans), so this module reads the same ``.xplane.pb`` again
and keeps both, on the same clock. A program without them (an older commit)
reads as a trace with no scopes and no program spans: every reduction below
then finds nothing and returns ``None``.

Device time under a scope sums leaf operations only: an operation whose
opcode is ``while``, ``conditional`` or ``call`` encloses its body's
operations on the trace's timeline and is never counted.

    python3 bench/program_trace.py bench/.cache/trace/alerts-1m.bulk

prints a traced run's breakdown by scope, the write-step operations no
scope covers, the program spans' means, and the idle gaps named by the
innermost span open in them.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import json
import os
import re
import sys

if __package__ in (None, ""):  # run as a script: make ``bench`` importable
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import trace_reduce as tr  # noqa: E402

PREFIX = "eagr."
# the benchmark's own spans, kept beside the program's for naming idle gaps
BENCH_SPANS = ("window", "update", "read", "drain", "generate", "barrier")
# the jitted write-step programs (as the write_step_device_ms reader has them)
WRITE_PROGRAMS = (r"^jit_(_write_body|write_step|alert_write_step)",)
CONTROL_OPS = frozenset({"while", "conditional", "call"})
_SCOPE = re.compile(r"(?:^|/)(eagr\.[A-Za-z_]+)")


@dataclasses.dataclass(frozen=True)
class Interval(tr.Interval):
    """A trace event with what ``trace_reduce.Interval`` drops: a device
    operation's scope path, a host span's arguments."""

    scope: str = ""
    args: tuple = ()

    @property
    def scopes(self) -> tuple[str, ...]:
        """The ``eagr.*`` scopes on the path, outermost first."""
        return tuple(_SCOPE.findall(self.scope))

    def arg(self, key: str, default=None):
        return dict(self.args).get(key, default)


@dataclasses.dataclass
class ProgramTrace:
    ops: dict[str, list[Interval]]          # device plane -> operations
    modules: dict[str, list[tr.Interval]]   # device plane -> programs
    spans: list[Interval]                   # program and benchmark spans

    window = tr.Trace.window

    def named(self, name: str, window: tr.Interval) -> list[Interval]:
        """Host spans ``name`` that lie inside ``window``."""
        return [s for s in self.spans if s.name == name
                and window.start_ns <= s.start_ns
                and s.end_ns <= window.end_ns]


# ------------------------------------------------- the trace file's metadata
def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one protobuf message's fields: varints
    as ints, length-delimited fields as bytes slices."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} in a trace file")
        yield key >> 3, v


def op_paths(path, stat: str = "tf_op") -> dict[str, dict[str, str]]:
    """``{plane name: {event name: stat value}}`` for the string stat
    ``stat`` of each event metadata in an ``.xplane.pb`` (an ``XSpace``:
    planes are field 1; a plane's name is 2, its event metadata map 4 and
    stat metadata map 5; an event metadata's name is 2 and its stats 5; a
    stat's metadata id is 1, its string value 5 or a reference 7)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: dict[str, dict[str, str]] = {}
    for fno, plane in _fields(space):
        if fno != 1:
            continue
        name, events, stat_names = "", [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = bytes(v).decode()
            elif f in (4, 5):
                entry = dict(_fields(v))
                if 2 not in entry:
                    continue
                if f == 4:
                    events.append(entry[2])
                else:
                    meta = dict(_fields(entry[2]))
                    stat_names[meta.get(1, entry.get(1))] = bytes(
                        meta.get(2, b"")).decode()
        found = {}
        for ev in events:
            ev_name, value = "", None
            for f, v in _fields(ev):
                if f == 2:
                    ev_name = bytes(v).decode()
                elif f == 5:
                    st = dict(_fields(v))
                    if stat_names.get(st.get(1)) != stat:
                        continue
                    if 5 in st:
                        value = bytes(st[5]).decode()
                    elif 7 in st:
                        value = stat_names.get(st[7])
            if value is not None:
                found[ev_name] = value
        if found:
            out[name] = found
    return out


def opcode(op: tr.Interval) -> str:
    """The HLO opcode of a device operation named by its instruction
    (``%while.57 = (...) while(...)``), or the instruction's own name
    stem when the text holds no opcode."""
    label = tr.op_label(op.name).split(" ")
    if len(label) == 2:
        return label[1]
    return op.name.lstrip("%").split(".", 1)[0].split(" ", 1)[0]


def is_leaf(op: tr.Interval) -> bool:
    return opcode(op) not in CONTROL_OPS


def load(log_dir, span_names=BENCH_SPANS) -> ProgramTrace:
    """The newest ``.xplane.pb`` under ``log_dir``: device operations with
    their scope paths, programs, and every host span whose name starts
    with ``eagr.`` or is one of ``span_names``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(os.fspath(log_dir), "**",
                                          "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    scopes = op_paths(paths[-1])
    wanted = set(span_names)
    ops: dict[str, list[Interval]] = {}
    modules: dict[str, list[tr.Interval]] = {}
    spans: list[Interval] = []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            for line in plane.lines:
                if line.name == tr.OPS_LINE:
                    path_of = scopes.get(plane.name, {})
                    ops[plane.name] = [
                        _interval(e, scope=path_of.get(e.name, ""))
                        for e in line.events]
                elif line.name == tr.MODULES_LINE:
                    modules[plane.name] = tr._intervals(line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX) or e.name in wanted:
                        spans.append(_interval(
                            e, args=tuple(sorted(dict(e.stats).items()))))
    return ProgramTrace(ops=ops, modules=modules, spans=spans)


def _interval(e, **kw) -> Interval:
    return Interval(e.name, float(e.start_ns),
                    float(e.start_ns) + float(e.duration_ns), **kw)


def for_context(ctx) -> ProgramTrace | None:
    """The program trace of the run ``ctx`` reads (``harness.Context``),
    loaded once per run and kept on the context; ``None`` when the run was
    not traced or left no trace."""
    if ctx.trace is None or ctx.window is None:
        return None
    got = vars(ctx).get("program_trace", False)
    if got is False:
        from bench import harness

        try:
            got = load(harness.CACHE / "trace" / ctx.cell.name)
        except FileNotFoundError:
            got = None
        vars(ctx)["program_trace"] = got
    return got


# ------------------------------------------------------------- device scopes
def leaf_ops(pt: ProgramTrace, window: tr.Interval, within=WRITE_PROGRAMS):
    """``(operation, device seconds inside the window)`` for every leaf
    operation that starts inside a program matching ``within``."""
    for plane, ivs in pt.ops.items():
        progs = sorted(tr.matching(pt.modules.get(plane, []), within),
                       key=lambda m: m.start_ns)
        starts = [m.start_ns for m in progs]
        for iv in ivs:
            d = min(iv.end_ns, window.end_ns) \
                - max(iv.start_ns, window.start_ns)
            if d <= 0 or not is_leaf(iv):
                continue
            k = bisect.bisect_right(starts, iv.start_ns) - 1
            if k >= 0 and iv.start_ns < progs[k].end_ns:
                yield iv, d * 1e-9


def scope_seconds(pt: ProgramTrace, window: tr.Interval,
                  within=WRITE_PROGRAMS) -> dict[str, float]:
    """Device seconds of leaf operations inside the window and inside the
    programs matching ``within``, by innermost ``eagr.*`` scope; ``""``
    collects the operations no scope covers. Summed over devices."""
    out: dict[str, float] = {}
    for iv, d in leaf_ops(pt, window, within):
        key = (iv.scopes or ("",))[-1]
        out[key] = out.get(key, 0.0) + d
    return out


def scope_ms_per_step(pt: ProgramTrace | None, window: tr.Interval,
                      scope: str, within=WRITE_PROGRAMS) -> float | None:
    """Device milliseconds per execution of the ``within`` programs spent
    in leaf operations under ``scope`` (at any depth of their path).
    ``None`` when no operation of the trace carries the scope, or no such
    program ran in the window."""
    if pt is None or not any(scope in iv.scopes for ivs in pt.ops.values()
                             for iv in ivs):
        return None
    n, _ = tr.program_executions(pt, window, within)
    if not n:
        return None
    secs = sum(d for iv, d in leaf_ops(pt, window, within)
               if scope in iv.scopes)
    return 1e3 * secs / n


def uncovered_ops(pt: ProgramTrace, window: tr.Interval, top: int = 10,
                  within=WRITE_PROGRAMS) -> list[list]:
    """``[[operation, device seconds], ...]``: the leaf operations inside
    the ``within`` programs that no ``eagr.*`` scope covers, longest first."""
    total: dict[str, float] = {}
    for iv, d in leaf_ops(pt, window, within):
        if not iv.scopes:
            key = tr.op_label(iv.name)
            total[key] = total.get(key, 0.0) + d
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])
            [:top]]


# --------------------------------------------------------------- host spans
def nested(spans, outer: Interval, name: str) -> list[Interval]:
    """Spans ``name`` that lie inside ``outer``."""
    return [s for s in spans if s.name == name
            and outer.start_ns <= s.start_ns and s.end_ns <= outer.end_ns]


def self_ms(pt: ProgramTrace | None, window: tr.Interval, outer: str,
            less: str) -> float | None:
    """Mean milliseconds of the ``outer`` spans in the window, less the
    ``less`` spans inside each; ``None`` without an ``outer`` span."""
    if pt is None:
        return None
    outs = pt.named(outer, window)
    if not outs:
        return None
    inner = [s for s in pt.spans if s.name == less]
    tot = sum(o.dur_ns - sum(s.dur_ns for s in nested(inner, o, less))
              for o in outs)
    return 1e-6 * tot / len(outs)


def mean_ms(pt: ProgramTrace | None, window: tr.Interval,
            name: str) -> float | None:
    """Mean milliseconds of the spans ``name`` in the window."""
    if pt is None:
        return None
    got = pt.named(name, window)
    return 1e-6 * sum(s.dur_ns for s in got) / len(got) if got else None


def arg_share(pt: ProgramTrace | None, window: tr.Interval, name: str,
              key: str) -> float | None:
    """Per cent of the spans ``name`` in the window whose argument ``key``
    is set (non-zero); ``None`` without such a span."""
    if pt is None:
        return None
    got = [s for s in pt.named(name, window) if s.arg(key) is not None]
    if not got:
        return None
    return 100.0 * sum(1 for s in got if s.arg(key)) / len(got)


def idle_gaps(pt: ProgramTrace, window: tr.Interval, top: int = 10
              ) -> list[list]:
    """``trace_reduce.idle_gaps`` over this trace, so each gap is named by
    the innermost of the program's and the benchmark's spans open at its
    midpoint."""
    return tr.idle_gaps(pt, window, top)


def summary(pt: ProgramTrace, window: tr.Interval) -> dict:
    """What a traced run's program instrumentation reads, for ``PERF.md``."""
    n, secs = tr.program_executions(pt, window, WRITE_PROGRAMS)
    by_scope = scope_seconds(pt, window)
    leaf = sum(by_scope.values())
    spans: dict[str, dict] = {}
    for s in pt.spans:
        if s.name.startswith(PREFIX) and window.start_ns <= s.start_ns \
                and s.end_ns <= window.end_ns:
            d = spans.setdefault(s.name, {"n": 0, "total_ms": 0.0})
            d["n"] += 1
            d["total_ms"] += s.dur_ns * 1e-6
    for d in spans.values():
        d["mean_ms"] = d["total_ms"] / d["n"]
    return {
        "write_steps": n,
        "write_step_device_ms": 1e3 * secs / n if n else None,
        "leaf_ms_per_step_by_scope": {
            k or "(none)": 1e3 * v / n for k, v in sorted(
                by_scope.items(), key=lambda kv: -kv[1])} if n else {},
        "scope_coverage_of_leaf_time": (
            1.0 - by_scope.get("", 0.0) / leaf) if leaf else None,
        "scope_coverage_of_step_time": (
            (leaf - by_scope.get("", 0.0)) / secs) if secs else None,
        "uncovered_ops": uncovered_ops(pt, window),
        "update_less_ring_wait_ms": self_ms(pt, window, "eagr.update",
                                            "eagr.ring_wait"),
        "read_less_read_wait_ms": self_ms(pt, window, "eagr.read",
                                          "eagr.read_wait"),
        "fired_overflow_share": arg_share(pt, window, "eagr.fired_readback",
                                          "overflow"),
        "spans": spans,
        "idle_gaps": idle_gaps(pt, window),
    }


if __name__ == "__main__":
    pt = load(sys.argv[1])
    print(json.dumps(summary(pt, pt.window()), indent=1))
