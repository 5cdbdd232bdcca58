"""The layer reduction on synthesised traces: device time per ``fl.``
scope, host self time per ``fl.`` span, and scopes inferred from the
compiled program where the compiler dropped an op's name."""

import pytest

from bench import scope_reduce, trace_reduce

US = 1000  # ns
SCOPE = "jit(round_fn)/{}/op"


def _pb(*fields):
    """Serialize a protobuf message from ``(field, value)`` pairs: ints as
    varints, bytes and str as length-delimited, lists of ints packed."""
    def varint(x):
        out = bytearray()
        while True:
            b = x & 0x7F
            x >>= 7
            out.append(b | (0x80 if x else 0))
            if not x:
                return bytes(out)

    out = b""
    for num, value in fields:
        if isinstance(value, int):
            out += varint(num << 3) + varint(value)
            continue
        if isinstance(value, list):
            value = b"".join(varint(v) for v in value)
        elif isinstance(value, str):
            value = value.encode()
        out += varint(num << 3 | 2) + varint(len(value)) + value
    return out


def _instr(name, iid, op_name="", operands=(), called=()):
    fields = [(1, name), (2, "fusion"), (35, iid)]
    if op_name:
        fields.append((7, _pb((2, op_name))))
    if operands:
        fields.append((36, list(operands)))
    if called:
        fields.append((38, list(called)))
    return _pb(*fields)


def _module():
    """An ``HloModuleProto``: an entry computation whose compiler-made
    instructions (no ``op_name``) sit between the layers, and a loop body
    with one nameless instruction."""
    entry = [
        _instr("p", 1, "params['w']"),
        _instr("a", 2, SCOPE.format("fl.local_update"), [1]),
        _instr("m", 3, SCOPE.format("fl.local_update/vmap()/fl.mask"), [2]),
        _instr("e", 4, SCOPE.format("fl.encode"), [3]),
        _instr("sort.12", 5, "", [4]),               # encode's compaction
        _instr("i", 7, SCOPE.format("fl.decode"), [5]),
        _instr("fusion.23", 6, "", [5, 7]),          # decode's scatter
        _instr("g", 8, SCOPE.format("fl.aggregate"), [6, 9]),
        _instr("constant.1", 9, ""),                 # used by g only
        _instr("while.3", 10, SCOPE.format("fl.local_update"), [2], [2]),
    ]
    body = [_instr("copy.7", 11, "")]
    return _pb((1, "jit_round_fn"),
               (3, _pb((1, "main"), *[(2, x) for x in entry], (5, 1))),
               (3, _pb((1, "body"), *[(2, x) for x in body], (5, 2))))


def test_hlo_scopes_reads_op_names_and_infers_the_rest():
    got = scope_reduce.hlo_scopes(memoryview(_module()))
    assert got["a"] == ("fl.local_update", False)
    assert got["m"] == ("fl.mask", False)          # innermost scope wins
    assert got["e"] == ("fl.encode", False)
    # nameless: the latest layer among the operands ...
    assert got["sort.12"] == ("fl.encode", True)
    assert got["fusion.23"] == ("fl.decode", True)
    # ... else the earliest among the users, else the calling instruction
    assert got["p"] == ("fl.local_update", True)
    assert got["constant.1"] == ("fl.aggregate", True)
    assert got["copy.7"] == ("fl.local_update", True)


def _stats(stats):
    out = ""
    for sid, (kind, value) in stats.items():
        if kind == "str":
            out += f' stats {{ metadata_id: {sid} str_value: "{value}" }}'
        elif kind == "int":
            out += f" stats {{ metadata_id: {sid} uint64_value: {value} }}"
        else:
            esc = "".join(f"\\{b:03o}" for b in value)
            out += f' stats {{ metadata_id: {sid} bytes_value: "{esc}" }}'
    return out


def _plane(pid, name, lines, metadata, stat_names):
    """Text proto of one XPlane.  ``lines``: ``{line: [(metadata id,
    start_us, dur_us, event stats)]}``; ``metadata``: ``{id: (name,
    metadata stats)}``; stats map a stat id to ``(kind, value)``."""
    body = []
    for lid, (lname, events) in enumerate(lines.items(), 1):
        evs = [f"events {{ metadata_id: {mid} offset_ps: {s * 10**6} "
               f"duration_ps: {d * 10**6}{_stats(st)} }}"
               for mid, s, d, st in events]
        body.append(f'lines {{ id: {lid} name: "{lname}" timestamp_ns: 0 '
                    + " ".join(evs) + " }")
    body += [f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}"'
             f"{_stats(st)} }} }}" for i, (n, st) in metadata.items()]
    body += [f'stat_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
             for i, n in stat_names.items()]
    return f'planes {{ id: {pid} name: "{name}" ' + " ".join(body) + " }"


TF_OP, PROGRAM, HLO = 1, 2, 3
STAT_NAMES = {TF_OP: "tf_op", PROGRAM: "program_id", HLO: "Hlo Proto"}


def _tf_op(scope):
    return {TF_OP: ("str", SCOPE.format(scope))}


def _xspace():
    """One device, two rounds.  Ops 1-3 carry their ``op_name`` in the
    event metadata, op 4 only in its own event stats; ``sort.12`` and
    ``fusion.23`` have none and are found in the program's HloProto;
    ``fusion.9`` is in no program the trace holds."""
    prog = {PROGRAM: ("int", 77)}
    meta = {
        1: ("%fusion.1 = f32[8] fusion(...)", {**_tf_op("fl.local_update"),
                                               **prog}),
        2: ("%fusion.2 = f32[8] fusion(...)",
            {**_tf_op("fl.local_update/vmap()/fl.mask"), **prog}),
        3: ("%fusion.3 = f32[8] fusion(...)", {**_tf_op("fl.encode"),
                                               **prog}),
        4: ("%fusion.4 = f32[8] fusion(...)", prog),
        5: ("%sort.12 = s32[8] sort(...)", prog),
        6: ("%fusion.23 = f32[8] fusion(...)", prog),
        7: ("%fusion.9 = f32[8] fusion(...)", {}),
    }
    ops = [(1, 10, 100, {}), (2, 110, 20, {}), (3, 130, 30, {}),
           (5, 160, 40, {}), (6, 200, 50, {}),
           (4, 250, 10, _tf_op("fl.aggregate")),          # idle 260-400
           (1, 400, 60, {}), (7, 460, 20, {})]            # idle 480-500
    device = _plane(1, "/device:TPU:0", {"XLA Ops": ops}, meta, STAT_NAMES)
    host_events = [("bench.round", 0, 300), ("fl.round#bucket=32,rounds=1#",
                                             5, 290),
                   ("fl.launch", 6, 40), ("fl.compile#bucket=32#", 7, 20),
                   ("fl.wait", 46, 220), ("fl.readback", 266, 25),
                   ("bench.round", 300, 200), ("fl.round", 301, 194),
                   ("fl.launch", 302, 98), ("fl.wait", 400, 85),
                   ("fl.readback", 485, 9), ("other", 0, 500)]
    names = {n: i for i, n in enumerate(dict.fromkeys(
        n for n, _, _ in host_events), 1)}
    host = _plane(2, "/host:CPU",
                  {"python": [(names[n], s, d, {})
                              for n, s, d in host_events]},
                  {i: (n, {}) for n, i in names.items()}, {})
    hlo = _pb((1, _instr_module()))
    metadata = _plane(3, "/host:metadata", {},
                      {1: ("jit_round_fn(77)", {HLO: ("bytes", hlo)})},
                      STAT_NAMES)
    return "\n".join([device, host, metadata])


def _instr_module():
    entry = [_instr("fusion.3", 1, SCOPE.format("fl.encode")),
             _instr("sort.12", 2, "", [1]),
             _instr("fusion.22", 4, SCOPE.format("fl.decode"), [2]),
             _instr("fusion.23", 3, "", [2, 4])]
    return _pb((1, "jit_round_fn"),
               (3, _pb((1, "main"), *[(2, x) for x in entry], (5, 1))))


def _load():
    from jax.profiler import ProfileData
    data = ProfileData.text_proto_to_serialized_xspace(_xspace())
    return (scope_reduce.from_profile(ProfileData.from_serialized_xspace(data),
                                      scope_reduce.trace_op_scopes(data)),
            trace_reduce.from_profile(ProfileData.from_serialized_xspace(data)))


def test_device_time_per_innermost_scope():
    trace, _ = _load()
    r = scope_reduce.reduce(trace)
    assert r["scope_s"] == pytest.approx({
        "fl.local_update": (100 + 60) * 1e-6, "fl.mask": 20e-6,
        "fl.encode": (30 + 40) * 1e-6, "fl.decode": 50e-6,
        "fl.aggregate": 10e-6, "unscoped": 20e-6})
    assert r["scope_inferred_s"] == pytest.approx({
        "fl.encode": 40e-6, "fl.decode": 50e-6})
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["fusion.1 = f32[8] fusion @fl.local_update"] == \
        pytest.approx(160e-6)
    assert ops["sort.12 = s32[8] sort @fl.encode"] == pytest.approx(40e-6)
    assert ops["fusion.9 = f32[8] fusion"] == pytest.approx(20e-6)


def test_spans_cut_at_hash_and_self_time():
    trace, _ = _load()
    assert ("fl.round", 5 * US, 295 * US) in trace.spans
    assert ("fl.compile", 7 * US, 27 * US) in trace.spans
    assert not any("#" in n or n == "other" for n, _, _ in trace.spans)
    r = scope_reduce.reduce(trace)
    assert r["host_span_s"] == pytest.approx({
        "fl.round": (290 - 40 - 220 - 25 + 194 - 98 - 85 - 9) * 1e-6,
        "fl.launch": (40 - 20 + 98) * 1e-6, "fl.compile": 20e-6,
        "fl.wait": (220 + 85) * 1e-6, "fl.readback": (25 + 9) * 1e-6})


def test_idle_gaps_named_by_fl_spans_and_old_keys_unchanged():
    trace, plain = _load()
    r, old = scope_reduce.reduce(trace), trace_reduce.reduce(plain)
    assert {k: v for k, v in r.items() if k in old and k != "breakdown"} \
        == {k: v for k, v in old.items() if k != "breakdown"}
    assert old["breakdown"]["idle_gaps"][0][0] == "bench.round"
    gaps = r["breakdown"]["idle_gaps"]
    # [260, 400) us: mid 330 in round 2's launch; [480, 500): mid 490,
    # round 2's readback; [0, 10): mid 5, where round 1's fl.round starts.
    assert [g[0] for g in gaps[:3]] == ["fl.launch", "fl.readback",
                                        "fl.round"]
    assert [g[1] for g in gaps[:3]] == pytest.approx([140e-6, 20e-6,
                                                      10e-6])


def test_round_clock_lead_and_lag():
    trace, _ = _load()
    r = scope_reduce.reduce(trace)
    # round 1: launch 6, first op 10, last op ends 260, wait ends 266;
    # round 2: launch 302, first op 400, last op (starts 460) ends 480,
    # wait ends 485.
    lead_lag = [x for pair in r["round_clock"] for x in pair]
    assert lead_lag == pytest.approx([4e-6, 6e-6, 98e-6, 5e-6])


def test_hlo_scopes_reads_a_compiled_module():
    """The field numbers hold for a module the compiler serialised: the
    scoped ops read their scope, the parameters get an inferred one."""
    import jax
    import jax.numpy as jnp

    def f(x, i):
        with jax.named_scope("fl.encode"):
            y = jnp.zeros(64).at[i].set(x)
        with jax.named_scope("fl.aggregate"):
            return jnp.sort(y) * 2

    compiled = jax.jit(f).lower(jnp.ones(8), jnp.arange(8)).compile()
    module = compiled.runtime_executable().hlo_modules()[0]
    got = scope_reduce.hlo_scopes(
        memoryview(module.as_serialized_hlo_module_proto()))
    named = {v for k, v in got.items() if k.startswith(("sort", "scatter"))}
    assert ("fl.aggregate", False) in named and ("fl.encode", False) in named
    assert {v[0] for v in got.values()} == {"fl.encode", "fl.aggregate"}
    params = [v for k, v in got.items() if k in ("x.1", "i.1")]
    assert params and all(v[1] for v in params)
