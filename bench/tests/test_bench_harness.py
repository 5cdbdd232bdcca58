"""The harness is driven by data: every part is found by its name, and a
new configuration, cell or metric is a new file plus a new entry."""

import json
import os
import shutil

import pytest

from bench import harness

BENCH = harness.load_benchmark()
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


def test_benchmark_keys_and_names():
    assert set(BENCH) == KEYS
    assert all(p == "bench" for p in BENCH["paths"])
    names = [c["name"] for c in BENCH["configs"]] + \
        [w["name"] for w in BENCH["workloads"]] + \
        [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    for w in BENCH["workloads"]:
        names += [w["config"], w["traffic"]]
    for c in BENCH["configs"]:
        names += c["reduced"]
    for n in names:
        assert harness.NAME_RE.match(n), n
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert harness.UNIT_RE.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])


def test_entries_keep_to_the_contract():
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    assert 1 <= len(BENCH["command"]) <= 32
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"},
            "end_to_end": {"name", "unit", "better", "bound", "source",
                           "workloads"},
            "per_layer": {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}}
    for section, allowed in keys.items():
        for e in BENCH[section]:
            assert set(e) <= allowed, e
            for k in ("why", "layer", "source"):
                if k in e:
                    assert 1 <= len(e[k]) <= 200 and "\n" not in e[k] \
                        and "\t" not in e[k], e
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    assert len(json.dumps(BENCH)) <= 64 * 1024


def test_every_workload_names_an_existing_config():
    configs = {c["name"]: c for c in BENCH["configs"]}
    pairs = set()
    for w in BENCH["workloads"]:
        cell = harness.load_json("workloads", w["name"])
        assert cell["config"] == w["config"] in configs
        assert cell["chips"] == w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        cfg = configs[w["config"]]
        assert os.path.isfile(os.path.join(harness.ROOT, cfg["file"]))
        assert harness.load_json("configs", w["config"])["name"] == cfg["name"]
        harness.load_module("configs", w["config"])
        harness.load_module("traffic", cell["traffic"]["generator"])
        harness.load_module("engines", cell["engine"])
        assert set(cell["check"]["limits"]) <= {
            "loss_gap", "loss1_gap", "update1_gap", "update1_diff",
            "change3_gap", "change3_median_gap"}


def test_every_metric_has_a_reader_and_reporting_cells():
    cells = {w["name"] for w in BENCH["workloads"]}
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        mod = harness.load_module("metrics", m["name"])
        assert mod.UNIT == m["unit"]
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
        assert mod.read({}) is None            # nothing to read: no number
    for c in cells:
        assert harness.cell_metrics(BENCH, c, "per_layer")
        names = {m["name"] for m in harness.cell_metrics(BENCH, c,
                                                         "end_to_end")}
        assert "setup_s" in names and len(names) >= 2


def test_new_config_cell_and_metric_are_found_by_name(tmp_path):
    """Files dropped into a copy of the benchmark are found with no edit
    to the harness, and the new cell runs (on the CPU, at a toy size)."""
    from bench import run

    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(harness.ROOT, "bench"), root / "bench")
    b = root / "bench"
    cfg = json.loads((b / "configs" / "gru_lm_wikitext2.json").read_text())
    cfg.update(name="tiny_gru", vocab_size=64)
    (b / "configs" / "tiny_gru.json").write_text(json.dumps(cfg))
    shutil.copy(b / "configs" / "gru_lm_wikitext2.py",
                b / "configs" / "tiny_gru.py")
    (b / "metrics" / "rounds_traced.py").write_text(
        'UNIT = "rounds"\n\n\ndef read(ctx):\n'
        '    return len(ctx.get("round_updates", [])) or None\n')
    cell = json.loads((b / "workloads" / "gru_lm.topk_fused.json")
                      .read_text())
    cell.update(name="tiny_gru.dense", config="tiny_gru")
    cell["traffic"].update(num_clients=4, train_tokens=4 * 2 * 4 * 32 + 1)
    cell["strategy"].update(preset="dense-baseline", masking={"mode": "none"})
    del cell["strategy"]["codec_of"]
    (b / "workloads" / "tiny_gru.dense.json").write_text(json.dumps(cell))
    bench = dict(BENCH)
    bench["configs"] = BENCH["configs"] + [dict(
        BENCH["configs"][0], name="tiny_gru",
        file="bench/configs/tiny_gru.json")]
    bench["workloads"] = [{"name": "tiny_gru.dense", "config": "tiny_gru",
                           "traffic": "dense", "chips": 1, "why": "test"}]
    bench["per_layer"] = BENCH["per_layer"] + [dict(
        BENCH["per_layer"][0], name="rounds_traced", unit="rounds")]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    mod = harness.load_module("metrics", "rounds_traced", str(b))
    assert mod.read({"round_updates": [1, 1]}) == 2
    line = run.run_cell("tiny_gru.dense", 5, 0.5, False, root=str(root),
                        require_tpu=False)
    assert line["correct"] is True
    assert set(line["metrics"]) == {m["name"] for m in BENCH["end_to_end"]}
    assert line["metrics"]["upload_bytes"]["value"] == 4 * (
        64 * 128 + 3 * 256 * 128 + 3 * 128 + 128 * 128)
    assert list(line)[-1] == "checks"


def test_no_chip_no_result():
    from bench import run
    with pytest.raises(run.NoChip):
        run.run_cell("gru_lm.topk_fused", 1, 1.0, False)
