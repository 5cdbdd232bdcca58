"""The trace reduction on a synthesised two-device trace."""

import pytest

from bench import trace_reduce

US = 1000  # ns


def _xspace(planes):
    """Text proto of an XSpace: ``planes`` maps a plane name to
    ``{line name: [(event name, start_us, dur_us, stats)]}``."""
    out = []
    for pid, (pname, lines) in enumerate(planes.items(), 1):
        names, stat_names, body = {}, {}, []
        for lid, (lname, events) in enumerate(lines.items(), 1):
            evs = []
            for name, start, dur, stats in events:
                mid = names.setdefault(name, len(names) + 1)
                st = ""
                for sname, sval in stats.items():
                    sid = stat_names.setdefault(sname, len(stat_names) + 1)
                    st += f' stats {{ metadata_id: {sid} str_value: "{sval}" }}'
                evs.append(f"events {{ metadata_id: {mid} offset_ps: "
                           f"{start * 10**6} duration_ps: {dur * 10**6}{st} }}")
            body.append(f'lines {{ id: {lid} name: "{lname}" timestamp_ns: 0 '
                        + " ".join(evs) + " }")
        meta = [f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                for n, i in names.items()]
        meta += [f'stat_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                 for n, i in stat_names.items()]
        out.append(f'planes {{ id: {pid} name: "{pname}" '
                   + " ".join(body + meta) + " }")
    return "\n".join(out)


MOSAIC = {"long_name": "custom-call(...), custom_call_target=tpu_custom_call"}


def _trace():
    from jax.profiler import ProfileData
    dev0 = {"XLA Ops": [
        ("fusion.1", 0, 100, {}),
        ("custom-call.7", 120, 30, MOSAIC),         # Pallas kernel
        ("all-reduce.2", 150, 50, {}),
        ("fusion.1", 260, 40, {}),                   # idle 200-260
        ("fusion.9", 400, 80, {}),                   # idle 300-400
    ], "XLA Modules": [("jit_round", 0, 480, {})]}
    dev1 = {"XLA Ops": [
        ("fusion.1", 0, 200, {}),
        ("custom-call.7", 200, 20, MOSAIC),
        ("fusion.9", 300, 180, {}),                  # idle 220-300
    ]}
    host = {"python": [
        ("bench.round", 0, 300, {}),
        ("bench.round", 300, 200, {}),
        ("other", 0, 500, {}),
    ]}
    text = _xspace({"/device:TPU:0": dev0, "/device:TPU:1": dev1,
                    "/host:CPU": host, "/device:TPU:0 extra": dev0})
    return trace_reduce.from_profile(ProfileData.from_text_proto(text))


def test_busy_union_kernels_collectives_and_gaps():
    t = _trace()
    assert sorted(t.devices) == ["/device:TPU:0", "/device:TPU:1"]
    r = trace_reduce.reduce(t)
    assert r["window_s"] == pytest.approx(500 * US * 1e-9)
    # device 0 busy: [0,100) [120,200) [260,300) [400,480) = 300 us;
    # device 1 busy: [0,220) [300,480) = 400 us; mean 350 us.
    assert r["busy_s"] == pytest.approx(350e-6)
    assert r["rounds"] == 2 and r["devices"] == 2
    assert r["mosaic_events"] == 2
    assert r["mosaic_s"] == pytest.approx((30 + 20) / 2 * 1e-6)
    assert r["collective_s"] == pytest.approx(50 / 2 * 1e-6)
    # round 1 [0,300): busy 220 (dev0) and 220 (dev1); round 2 [300,500):
    # busy 80 and 180.
    assert r["round_host_s"] == pytest.approx([(300 - 220) * 1e-6,
                                               (200 - 130) * 1e-6])
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["fusion.1"] == pytest.approx((140 + 200) / 2 * 1e-6)
    assert list(ops)[0] == "fusion.1"
    gaps = r["breakdown"]["idle_gaps"]
    # longest first: dev0 [300,400) in round 2, dev1 [220,300) in round 1,
    # dev0 [200,260) in round 1; each named by the innermost bench span.
    assert [g[1] for g in gaps[:3]] == pytest.approx([100e-6, 80e-6, 60e-6])
    assert [g[0] for g in gaps[:3]] == ["bench.round"] * 3


def test_no_round_span_reads_nothing():
    t = _trace()
    t.spans = [s for s in t.spans if s[0] != "bench.round"]
    assert trace_reduce.reduce(t) is None
