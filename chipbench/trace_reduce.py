"""From a profiler trace to device busy time, per-name device time and
idle gaps attributed to what the host was doing.

    python3 chipbench/trace_reduce.py --describe <file.xplane.pb>

prints the planes, lines and the most frequent event names of a trace,
the device ops that took most time, and each custom call (Pallas
kernels) in full, for looking at one by hand.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes, with
nothing but JAX.  ``reduce`` works on plain ``Span`` lists, so the tests
can feed it a small synthetic trace.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses

#: device planes of the chips (``/device:TPU:0``, ...)
DEVICE_PLANE = "/device:TPU:"
#: host annotation that marks the traced window
WINDOW = "chipbench.window"


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float                 # seconds, on the trace's clock
    end: float
    detail: str = ""             # the event's string stats, joined

    def clip(self, lo: float, hi: float) -> float:
        return max(0.0, min(self.end, hi) - max(self.start, lo))


@dataclasses.dataclass
class Trace:
    ops: list[Span]              # device ops (one list over all chips)
    modules: list[Span]          # device programs
    host: list[Span]             # the benchmark thread's host events
    chips: int


def _stats_text(ev) -> str:
    try:
        stats = dict(ev.stats)
    except (TypeError, ValueError):
        return ""
    return " ".join(str(v) for v in stats.values() if isinstance(v, str))


def op_name(text: str) -> str:
    """The instruction name of a device op, whose event the profiler names
    by its whole HLO text (``%paged_decode_attention.5 = bf16[...]
    custom-call(...)`` gives ``paged_decode_attention.5``)."""
    return text.split(" = ", 1)[0].lstrip("%") if " = " in text else text


def leaves(spans: list[Span]) -> list[Span]:
    """The ops that hold no other op: a ``while`` or a call spans the ops
    of its body on the same line, and would count their time twice."""
    order = sorted(spans, key=lambda s: (s.start, -s.end))
    outer = set()
    for i, s in enumerate(order[:-1]):
        nxt = order[i + 1]
        if nxt.start < s.end and nxt.end <= s.end:
            outer.add(i)
    return [s for i, s in enumerate(order) if i not in outer]


def _spans(line, name=lambda n: n) -> list[Span]:
    return [Span(name(ev.name), ev.start_ns * 1e-9,
                 (ev.start_ns + ev.duration_ns) * 1e-9, _stats_text(ev))
            for ev in line.events]


def load(path: str) -> Trace:
    """Device ops and programs of every chip plane, and the host events of
    the thread that recorded the :data:`WINDOW` annotation."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops, modules, host, chips = [], [], [], 0
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE) and plane.name[
                len(DEVICE_PLANE):].isdigit():
            chips += 1
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops += _spans(line, op_name)
                elif line.name == "XLA Modules":
                    modules += _spans(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans = _spans(line)
                if any(s.name == WINDOW for s in spans):
                    host = spans
    return Trace(ops, modules, host, chips)


def union(spans: list[Span], lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged intervals of ``spans`` clipped to ``[lo, hi]``."""
    out: list[list[float]] = []
    for s in sorted(spans, key=lambda s: s.start):
        a, b = max(s.start, lo), min(s.end, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def gaps(busy: list[tuple[float, float]], lo: float,
         hi: float) -> list[tuple[float, float]]:
    """The complement of ``busy`` inside ``[lo, hi]``."""
    out, at = [], lo
    for a, b in busy:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def host_activity(host: list[Span], t: float) -> str:
    """Name of the innermost host event covering instant ``t``."""
    cover = [s for s in host if s.start <= t < s.end and s.name != WINDOW
             and not s.name.startswith("$")]        # "$" = Python frames
    if not cover:
        return "(no host event)"
    return min(cover, key=lambda s: s.end - s.start).name


@dataclasses.dataclass
class Reduction:
    window: tuple[float, float]
    busy_s: float                # union of device ops, per chip averaged
    by_op: dict[str, float]      # device seconds per op name (leaf ops)
    by_module: dict[str, float]  # device seconds per program name
    clipped: list[tuple[Span, float]]  # each leaf op, seconds in window
    idle_by_host: dict[str, float]   # idle seconds per host activity

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def op_seconds(self, *needles: str) -> float:
        """Device seconds of the ops whose name or string stats hold any
        of ``needles``."""
        return sum(sec for s, sec in self.clipped
                   if any(n in s.name or n in s.detail for n in needles))

    def module_seconds(self, *needles: str) -> float:
        """Device seconds of the programs whose name holds any of
        ``needles``."""
        return sum(v for k, v in self.by_module.items()
                   if any(n in k for n in needles))


def window_of(trace: Trace) -> tuple[float, float]:
    marks = [s for s in trace.host if s.name == WINDOW]
    if not marks:
        raise ValueError(f"trace has no {WINDOW!r} host event")
    return marks[0].start, marks[0].end


def reduce(trace: Trace, window: tuple[float, float] | None = None
           ) -> Reduction:
    lo, hi = window or window_of(trace)
    ops = trace.ops or trace.modules
    busy = union(ops, lo, hi)
    busy_s = sum(b - a for a, b in busy) / max(trace.chips, 1)
    clipped = [(s, s.clip(lo, hi)) for s in leaves(ops)]
    clipped = [(s, sec) for s, sec in clipped if sec > 0]
    by_op: dict[str, float] = collections.defaultdict(float)
    for s, sec in clipped:
        by_op[s.name] += sec
    by_module: dict[str, float] = collections.defaultdict(float)
    for s in trace.modules:
        by_module[s.name] += s.clip(lo, hi)
    idle: dict[str, float] = collections.defaultdict(float)
    for a, b in gaps(busy, lo, hi):
        idle[host_activity(trace.host, (a + b) / 2)] += b - a
    return Reduction((lo, hi), busy_s, dict(by_op), dict(by_module),
                     clipped, dict(idle))


def top(table: dict[str, float], n: int = 10) -> list[list]:
    return [[k, v] for k, v in sorted(table.items(), key=lambda kv: -kv[1])[:n]]


def describe(path: str) -> None:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            names = collections.Counter(e.name for e in evs)
            print(f"  line {line.name!r}: {len(evs)} events")
            for name, n in names.most_common(12):
                ev = next(e for e in evs if e.name == name)
                try:
                    stats = dict(ev.stats)
                except (TypeError, ValueError):
                    stats = {}
                print(f"    {n:6d} x {name[:100]!r} dur {ev.duration_ns} ns "
                      f"start {ev.start_ns} stats {str(stats)[:300]}")
            if line.name != "XLA Ops":
                continue
            total: dict[str, float] = collections.defaultdict(float)
            for e in evs:
                total[e.name] += e.duration_ns * 1e-9
            print("    most time:")
            for name, sec in top(total, 25):
                print(f"    {sec:10.6f} s {name[:160]!r}")
            seen = set()
            for e in evs:
                if "custom-call" in e.name and e.name not in seen:
                    seen.add(e.name)
                    print(f"    custom call {e.name[:1500]!r} stats "
                          f"{str(dict(e.stats))[:1500]}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--describe", required=True)
    describe(ap.parse_args().describe)
