"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

* The window runs from the start of the first of the benchmark's own round
  spans (``TraceAnnotation("bench.round")``) to the end of the last.
* Device busy is the union of the intervals of the ops on each device's
  ``XLA Ops`` line inside the window, averaged over the devices.
* Pallas kernels are the Mosaic custom calls: ops whose HLO text names the
  ``tpu_custom_call`` target, found by what they are and not by the
  Python function that built them.
* Collectives are the all-reduce, all-gather, reduce-scatter, all-to-all
  and collective-permute ops.
* Ops that enclose other ops on the same line (a loop and its body) count
  once, by their body, in every sum but the busy union.
* The breakdown lists the device ops that took most time in all, and the
  longest idle gaps of the device, each named by the innermost benchmark
  span that the host was in at the gap's midpoint.

Run ``python3 bench/trace_reduce.py <file.xplane.pb>`` to print what a
trace holds: its planes, lines, and a few events with their stats.
"""

from __future__ import annotations

import dataclasses
import re
import sys
import warnings

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
ROUND_SPAN = "bench.round"
MOSAIC_MARK = "tpu_custom_call"
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|all-to-all|"
                        r"collective-permute|allreduce|allgather")
TOP = 10


@dataclasses.dataclass
class Op:
    name: str
    start: float          # ns
    end: float            # ns
    mosaic: bool = False
    collective: bool = False
    parent: bool = False  # encloses other ops (a loop), not counted as one


@dataclasses.dataclass
class Trace:
    devices: dict         # device plane name -> [Op]
    spans: list           # [(name, start_ns, end_ns)] benchmark host spans


def _text_stats(event) -> str:
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return " ".join(str(v) for _, v in event.stats if isinstance(v, str))


def short_name(text: str) -> str:
    """``%fusion.22 = f32[68153376]{0:T(1024)} fusion(...), ...`` ->
    ``fusion.22 = f32[68153376] fusion``: the HLO instruction, its (first)
    result shape and its opcode."""
    m = re.match(r"%?([\w.\-]+) = \(?([a-z0-9]+\[[\d,]*\])", text)
    op = re.search(r"[})\]] ([a-z][\w\-]*)\(", text)
    if not m or not op:
        return text[:120]
    return f"{m.group(1)} = {m.group(2)} {op.group(1)}"


def _is_mosaic(event) -> bool:
    if MOSAIC_MARK in event.name:
        return True
    return "custom-call" in event.name and MOSAIC_MARK in _text_stats(event)


def from_profile(pd) -> Trace:
    """Pull device ops and the benchmark's host spans out of a
    ``jax.profiler.ProfileData``."""
    devices, spans = {}, []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    ops.append(Op(ev.name, ev.start_ns, ev.end_ns,
                                  _is_mosaic(ev),
                                  bool(COLLECTIVE.search(ev.name))))
            ops.sort(key=lambda o: (o.start, -o.end))
            for a, b in zip(ops, ops[1:]):
                a.parent = b.start < a.end
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, ev.start_ns, ev.end_ns))
    return Trace(devices, sorted(spans, key=lambda s: s[1]))


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(path))


def _union(intervals, lo: float, hi: float) -> list:
    """Merged ``[start, end)`` intervals clipped to ``[lo, hi)``."""
    out = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _covered(merged, lo: float, hi: float) -> float:
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


def reduce(trace: Trace) -> dict | None:
    """The numbers of one traced window, or None where the trace holds no
    round span or no device op."""
    rounds = [(s, e) for n, s, e in trace.spans if n == ROUND_SPAN]
    if not rounds or not any(trace.devices.values()):
        return None
    lo, hi = rounds[0][0], rounds[-1][1]
    window_ns = hi - lo
    merged = {d: _union([(o.start, o.end) for o in ops], lo, hi)
              for d, ops in trace.devices.items()}
    ndev = len(merged)
    busy_ns = sum(_covered(m, lo, hi) for m in merged.values()) / ndev

    def summed(pick):
        return sum(min(o.end, hi) - max(o.start, lo)
                   for ops in trace.devices.values() for o in ops
                   if pick(o) and not o.parent and o.end > lo
                   and o.start < hi) / ndev

    round_host_ns = []
    for s, e in rounds:
        dev = sum(_covered(m, s, e) for m in merged.values()) / ndev
        round_host_ns.append((e - s) - dev)

    totals = {}
    for ops in trace.devices.values():
        for o in ops:
            if o.end > lo and o.start < hi and not o.parent:
                name = short_name(o.name)
                totals[name] = totals.get(name, 0.0) + (
                    min(o.end, hi) - max(o.start, lo)) / ndev
    top_ops = sorted(totals.items(), key=lambda kv: -kv[1])[:TOP]

    gaps = []
    for m in merged.values():
        edges = [lo] + [x for iv in m for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, s, e))
    named = []
    for dur, s, e in sorted(gaps, reverse=True)[:TOP]:
        mid = 0.5 * (s + e)
        inside = [(ss, n) for n, ss, ee in trace.spans if ss <= mid < ee]
        named.append([max(inside)[1] if inside else "outside spans",
                      dur * 1e-9])
    return {
        "window_s": window_ns * 1e-9,
        "busy_s": busy_ns * 1e-9,
        "rounds": len(rounds),
        "devices": ndev,
        "mosaic_s": summed(lambda o: o.mosaic) * 1e-9,
        "mosaic_events": sum(o.mosaic and not o.parent
                             for ops in trace.devices.values()
                             for o in ops if lo < o.end and o.start < hi),
        "collective_s": summed(lambda o: o.collective) * 1e-9,
        "round_host_s": [x * 1e-9 for x in round_host_ns],
        "breakdown": {
            "device_ops": [[n, t * 1e-9] for n, t in top_ops],
            "idle_gaps": named,
        },
    }


def dump(path: str, per_line: int = 4) -> None:
    """Print the planes and lines of a trace, with a few events each."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines")
        for line in lines:
            evs = list(line.events)
            print(f"  line {line.name!r}: {len(evs)} events")
            marked = [ev for ev in evs
                      if MOSAIC_MARK in ev.name + " " + _text_stats(ev)]
            for ev in evs[:per_line] + marked[:per_line]:
                print(f"    {ev.name!r} start={ev.start_ns} "
                      f"dur={ev.duration_ns} stats={list(ev.stats)[:12]}")


if __name__ == "__main__":
    dump(sys.argv[1])
