"""Reduce a profiler trace (``.xplane.pb``) to the round's layers: device
time per ``fl.*`` named scope and host time per ``fl.*`` host span.

The program names its layers twice (``repro.core.federated`` and
``repro.core.server``): every op of the round program carries the
``jax.named_scope`` it was traced under in its ``op_name``
(``fl.local_update``, ``fl.mask``, ``fl.encode``, ``fl.decode``,
``fl.aggregate``), and ``FederatedServer.run`` wraps each segment in
``TraceAnnotation`` spans (``fl.round`` around ``fl.launch``, with
``fl.compile`` inside it on a cache miss, ``fl.wait`` and ``fl.readback``).

``reduce`` returns everything ``trace_reduce.reduce`` returns for the same
trace, read the same way, except that the idle gaps are named by the
innermost span of either kind (``bench.`` or ``fl.``) and each top device
op is named ``<op> @<scope>``; and adds:

* ``scope_s``: device seconds in the window per innermost ``fl.`` scope of
  each op, plus ``"unscoped"``, summed as ``trace_reduce`` sums (ops that
  enclose others count by their body; mean over the devices);
* ``scope_inferred_s``: the part of ``scope_s`` whose scope was inferred
  (``hlo_scopes``), per scope;
* ``host_span_s``: self seconds in the window per ``fl.`` host span name:
  a span's length less the ``fl.`` spans directly inside it, so
  ``fl.launch`` here excludes the ``fl.compile`` inside it;
* ``round_clock``: per ``fl.round``, ``[lead_s, lag_s]``: from the start of
  its ``fl.launch`` to its first device op, and from its last device op to
  the end of its ``fl.wait``.  Both are >= 0 when host spans and device
  ops share one clock.

On a TPU the op's ``op_name`` is the ``tf_op`` stat of the event's
metadata, which ``jax.profiler.ProfileData`` does not expose; ``load``
reads it, and each program's ``HloProto``, from the file's bytes
(``trace_op_scopes``).  The TPU compiler drops the ``op_name`` of the ops
it creates, the sorts and fusions of an expanded scatter among them;
``hlo_scopes`` infers their scope from the program's data flow.  Span
names are cut at the first ``#``, where ``TraceAnnotation`` arguments may
be encoded.

Run ``python3 bench/scope_reduce.py <file.xplane.pb>`` to print the
reduction as JSON.
"""

from __future__ import annotations

import dataclasses
import json
import re
import sys
import warnings

if __package__ in (None, ""):
    import os
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import trace_reduce  # noqa: E402

SPAN_PREFIXES = ("bench.", "fl.")
LAYERS = ("fl.local_update", "fl.mask", "fl.encode", "fl.decode",
          "fl.aggregate")
SCOPE = re.compile(r"\bfl\.[A-Za-z_]+")
UNSCOPED = "unscoped"
OP_NAME_STAT = "tf_op"
METADATA_PLANE = "/host:metadata"
INSTRUCTION = re.compile(r"%?([\w.\-]+)(?: = |$)")


@dataclasses.dataclass
class ScopedOp(trace_reduce.Op):
    """A device op with the layer it belongs to."""

    scope: str = UNSCOPED
    inferred: bool = False  # scope inferred by ``hlo_scopes``, not read


def innermost_scope(op_name: str | None) -> str:
    """``jit(f)/fl.local_update/vmap()/fl.mask/sort`` -> ``fl.mask``."""
    found = SCOPE.findall(op_name or "")
    return found[-1] if found else UNSCOPED


# ---- protobuf, as far as the trace needs it -------------------------------

def _varint(buf, i: int):
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return x, i


def _fields(buf):
    """(field number, value) of one protobuf message: ints for varints,
    memoryviews for length-delimited fields, raw bytes for fixed ones."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} not supported")
        yield key >> 3, value


def _ints(value) -> list:
    """A repeated int64 field: packed (a memoryview) or one varint."""
    if isinstance(value, int):
        return [value]
    out, i = [], 0
    while i < len(value):
        x, i = _varint(value, i)
        out.append(x)
    return out


def _text(value) -> str:
    return bytes(value).decode("utf-8", errors="replace")


# ---- scopes of the compiled program's instructions -------------------------

def _rank(scope: str) -> int:
    return LAYERS.index(scope) if scope in LAYERS else len(LAYERS)


def hlo_scopes(module) -> dict:
    """``{instruction name: (scope, inferred)}`` of a serialized
    ``HloModuleProto``.

    An instruction's scope is the innermost ``fl.`` scope of its
    ``op_name``.  The compiler drops the ``op_name`` of instructions it
    creates (the sorts and fusions a TPU scatter becomes, layout copies);
    those are inferred, in this order, and marked so: the latest layer
    (in ``LAYERS`` order, the order data flows through the round) among
    the instruction's operands; else the earliest among its users; else
    the scope of the instruction that calls its computation.
    """
    comps, callers = {}, {}
    for field, comp in _fields(module):
        if field != 3:                            # HloModuleProto.computations
            continue
        cid, instrs = 0, []
        for cf, value in _fields(comp):
            if cf == 5:                           # HloComputationProto.id
                cid = value
            elif cf == 2:                         # .instructions
                ins = {"name": "", "id": 0, "operands": [], "called": [],
                       "op_name": ""}
                for f, v in _fields(value):
                    if f == 1:                    # HloInstructionProto.name
                        ins["name"] = _text(v)
                    elif f == 35:                 # .id
                        ins["id"] = v
                    elif f == 36:                 # .operand_ids
                        ins["operands"] += _ints(v)
                    elif f == 38:                 # .called_computation_ids
                        ins["called"] += _ints(v)
                    elif f == 7:                  # .metadata (OpMetadata)
                        meta = dict(_fields(v))
                        ins["op_name"] = _text(meta.get(2, b""))
                instrs.append(ins)
        comps[cid] = instrs
        for ins in instrs:
            for c in ins["called"]:
                callers.setdefault(c, []).append(ins)
    for instrs in comps.values():
        for ins in instrs:
            ins["scope"] = innermost_scope(ins["op_name"])
            ins["inferred"] = False
    for instrs in comps.values():
        by_id = {ins["id"]: ins for ins in instrs}
        users = {}
        for ins in instrs:
            for o in ins["operands"]:
                users.setdefault(o, []).append(ins)
        open_ = [ins for ins in instrs if ins["scope"] == UNSCOPED]
        for pick, near in ((max, lambda x: [by_id[o] for o in x["operands"]
                                            if o in by_id]),
                           (min, lambda x: users.get(x["id"], []))):
            changed = True
            while changed:
                changed = False
                for ins in open_:
                    if ins["scope"] != UNSCOPED:
                        continue
                    known = [n["scope"] for n in near(ins)
                             if n["scope"] != UNSCOPED]
                    if known:
                        ins["scope"] = pick(known, key=_rank)
                        ins["inferred"] = changed = True
    changed = True
    while changed:
        changed = False
        for cid, instrs in comps.items():
            scoped = [c for c in callers.get(cid, ())
                      if c["scope"] != UNSCOPED]
            for ins in instrs:
                if ins["scope"] == UNSCOPED and scoped:
                    ins["scope"] = scoped[0]["scope"]
                    ins["inferred"] = changed = True
    return {ins["name"]: (ins["scope"], ins["inferred"])
            for instrs in comps.values() for ins in instrs}


def trace_op_scopes(data: bytes) -> dict:
    """``{device plane name: {event name: (scope, inferred)}}`` from a
    serialized ``XSpace``.  A device op's event metadata holds its
    ``op_name`` (the ``tf_op`` stat) and its ``program_id``; the
    ``/host:metadata`` plane holds each program's ``HloProto``, keyed by
    ``<module>(<program_id>)``, for the ops whose ``op_name`` is gone."""
    planes, modules = {}, {}
    for field, plane in _fields(memoryview(data)):
        if field != 1:                            # XSpace.planes
            continue
        name, events, stat_names = "", [], {}
        for pf, value in _fields(plane):
            if pf == 2:                           # XPlane.name
                name = _text(value)
            elif pf == 4:                         # XPlane.event_metadata
                events.append(dict(_fields(value)).get(2, b""))
            elif pf == 5:                         # XPlane.stat_metadata
                entry = dict(_fields(value))
                meta = dict(_fields(entry.get(2, b"")))
                stat_names[entry.get(1, 0)] = _text(meta.get(2, b""))
        if name == METADATA_PLANE:
            for meta in events:
                fields = list(_fields(meta))
                key = "".join(_text(v) for f, v in fields if f == 2)
                pid = re.search(r"\((\d+)\)$", key)
                for f, v in fields:
                    stat = dict(_fields(v)) if f == 5 else {}
                    if pid and 6 in stat:         # XStat.bytes_value
                        modules[int(pid.group(1))] = dict(
                            _fields(stat[6])).get(1, b"")  # .hlo_module
        elif trace_reduce.DEVICE_PLANE.match(name):
            ops = {}
            for meta in events:
                ev_name, stats = "", {}
                for f, v in _fields(meta):
                    if f == 2:                    # XEventMetadata.name
                        ev_name = _text(v)
                    elif f == 5:                  # XEventMetadata.stats
                        stat = dict(_fields(v))
                        sname = stat_names.get(stat.get(1))
                        if 5 in stat:             # XStat.str_value
                            stats[sname] = _text(stat[5])
                        elif 7 in stat:           # XStat.ref_value
                            stats[sname] = stat_names.get(stat[7], "")
                        else:
                            stats[sname] = stat.get(3, stat.get(4))
                ops[ev_name] = stats
            planes[name] = ops
    hlo, out = {}, {}
    for plane, ops in planes.items():
        scopes = {}
        for ev_name, stats in ops.items():
            scope = innermost_scope(stats.get(OP_NAME_STAT))
            if scope != UNSCOPED:
                scopes[ev_name] = (scope, False)
                continue
            pid = stats.get("program_id")
            if pid is None or int(pid) not in modules:
                continue
            pid = int(pid)
            if pid not in hlo:
                hlo[pid] = hlo_scopes(modules[pid])
            m = INSTRUCTION.match(ev_name)
            if m and m.group(1) in hlo[pid]:
                scopes[ev_name] = hlo[pid][m.group(1)]
        out[plane] = scopes
    return out


# ---- the trace -------------------------------------------------------------

def _stat_text(event, stat: str) -> str | None:
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for k, v in event.stats:
            if k == stat and isinstance(v, str):
                return v
    return None


def from_profile(pd, op_scopes: dict | None = None) -> trace_reduce.Trace:
    """``trace_reduce.from_profile``'s trace, with each device op's
    innermost ``fl.`` scope (from its own ``tf_op`` stat, else from
    ``op_scopes``, as ``trace_op_scopes`` returns it) and the ``fl.`` host
    spans beside the ``bench.`` ones."""
    op_scopes = op_scopes or {}
    devices, spans = {}, []
    for plane in pd.planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            scopes = op_scopes.get(plane.name, {})
            ops = []
            for line in plane.lines:
                if line.name != trace_reduce.OPS_LINE:
                    continue
                for ev in line.events:
                    scope = innermost_scope(_stat_text(ev, OP_NAME_STAT))
                    inferred = False
                    if scope == UNSCOPED:
                        scope, inferred = scopes.get(ev.name,
                                                     (UNSCOPED, False))
                    ops.append(ScopedOp(
                        ev.name, ev.start_ns, ev.end_ns,
                        trace_reduce._is_mosaic(ev),
                        bool(trace_reduce.COLLECTIVE.search(ev.name)),
                        scope=scope, inferred=inferred))
            ops.sort(key=lambda o: (o.start, -o.end))
            for a, b in zip(ops, ops[1:]):
                a.parent = b.start < a.end
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    name = ev.name.split("#", 1)[0]
                    if name.startswith(SPAN_PREFIXES):
                        spans.append((name, ev.start_ns, ev.end_ns))
    return trace_reduce.Trace(devices, sorted(spans, key=lambda s: s[1]))


def load(path: str) -> trace_reduce.Trace:
    """Read an ``.xplane.pb`` file, op names included."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        data = f.read()
    return from_profile(ProfileData.from_serialized_xspace(data),
                        trace_op_scopes(data))


# ---- the reduction ---------------------------------------------------------

def _self_times(spans, lo: float, hi: float) -> dict:
    """Self ns per name of properly nested spans, clipped to [lo, hi)."""
    out, stack = {}, []

    def close(entry):
        name, s, e, child = entry
        own = max(0.0, min(e, hi) - max(s, lo)) - child
        out[name] = out.get(name, 0.0) + own
        if stack:
            stack[-1][3] += max(0.0, min(e, hi) - max(s, lo))

    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= s:
            close(stack.pop())
        stack.append([name, s, e, 0.0])
    while stack:
        close(stack.pop())
    return out


def _round_clock(trace, lo: float, hi: float) -> list:
    fl = [s for s in trace.spans if s[0].startswith("fl.")]
    rounds = [s for s in fl if s[0] == "fl.round" and lo <= s[1] < hi]
    starts = []
    for _, s, e in rounds:
        launch = [x for x in fl if x[0] == "fl.launch" and s <= x[1] < e]
        wait = [x for x in fl if x[0] == "fl.wait" and s <= x[1] < e]
        if launch and wait:
            starts.append((launch[0][1], wait[0][2]))
    out = []
    for launch_start, wait_end in starts:
        # The ops that start while the round is launched or waited on; an
        # op of the round that ran past the wait reads as a negative lag.
        ops = [o for ops in trace.devices.values() for o in ops
               if launch_start <= o.start < wait_end]
        if ops:
            out.append([(min(o.start for o in ops) - launch_start) * 1e-9,
                        (wait_end - max(o.end for o in ops)) * 1e-9])
    return out


def reduce(trace: trace_reduce.Trace) -> dict | None:
    """``trace_reduce.reduce`` of the trace plus the layer numbers; None
    where ``trace_reduce.reduce`` reads nothing."""
    out = trace_reduce.reduce(trace)
    if out is None:
        return None
    rounds = [(s, e) for n, s, e in trace.spans
              if n == trace_reduce.ROUND_SPAN]
    lo, hi = rounds[0][0], rounds[-1][1]
    ndev = len(trace.devices)
    scope_ns, inferred_ns, totals = {}, {}, {}
    for ops in trace.devices.values():
        for o in ops:
            if o.parent or o.end <= lo or o.start >= hi:
                continue
            t = (min(o.end, hi) - max(o.start, lo)) / ndev
            scope = getattr(o, "scope", UNSCOPED)
            scope_ns[scope] = scope_ns.get(scope, 0.0) + t
            if getattr(o, "inferred", False):
                inferred_ns[scope] = inferred_ns.get(scope, 0.0) + t
            name = trace_reduce.short_name(o.name)
            if scope != UNSCOPED:
                name = f"{name} @{scope}"
            totals[name] = totals.get(name, 0.0) + t
    top = sorted(totals.items(), key=lambda kv: -kv[1])[:trace_reduce.TOP]
    fl_spans = [s for s in trace.spans if s[0].startswith("fl.")]
    out["scope_s"] = {k: v * 1e-9 for k, v in sorted(scope_ns.items())}
    out["scope_inferred_s"] = {k: v * 1e-9
                               for k, v in sorted(inferred_ns.items())}
    out["host_span_s"] = {k: v * 1e-9 for k, v in sorted(
        _self_times(fl_spans, lo, hi).items())}
    out["round_clock"] = _round_clock(trace, lo, hi)
    out["breakdown"]["device_ops"] = [[n, t * 1e-9] for n, t in top]
    return out


if __name__ == "__main__":
    print(json.dumps(reduce(load(sys.argv[1])), indent=1))
