"""Finds the benchmark's parts by name: cells, configurations, traffic
generators, engines and per-layer metric readers.

Every part lives in a file of its own under ``bench/`` and is found from
the name that ``BENCHMARK.json`` or a cell's file gives, so a new cell,
configuration or metric is a new file and a new entry, never an edit here:

    workloads/<cell>.json        one cell: config, chips, traffic, strategy
    configs/<config>.json        the configuration's sizes, as run
    configs/<config>.py          its model code, FLOP count and reference
    traffic/<generator>.py       a traffic generator, fed the cell's data
    engines/<engine>.py          the loop that drives the system under test
    metrics/<metric>.py          a reader of one per-layer metric
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def load_benchmark(root: str = ROOT) -> dict:
    """``BENCHMARK.json`` at the checkout root."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _check_name(name: str) -> str:
    if not NAME_RE.match(name):
        raise ValueError(f"not a benchmark name: {name!r}")
    return name


def load_json(kind: str, name: str, bench_dir: str = BENCH_DIR) -> dict:
    """``bench/<kind>/<name>.json``."""
    path = os.path.join(bench_dir, kind, _check_name(name) + ".json")
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str, bench_dir: str = BENCH_DIR):
    """Import ``bench/<kind>/<name>.py`` by its path, under a module name
    of its own, so that a file dropped into the directory is found with no
    registry to edit."""
    path = os.path.join(bench_dir, kind, _check_name(name) + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} named {name!r} ({path})")
    mod_name = f"_bench_{kind}_{name.replace('.', '_').replace('-', '_')}"
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: dict, cell: str, section: str) -> list:
    """The entries of ``bench[section]`` that ``cell`` reports: those whose
    ``workloads`` list names it, and those with no such list."""
    return [m for m in bench[section]
            if "workloads" not in m or cell in m["workloads"]]
