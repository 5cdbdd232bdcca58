#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the checkout root.  The cell is an entry of ``BENCHMARK.json``
and a file ``bench/workloads/<cell>.json``.  The run loads, warms up (the
cell's one round program, compiled or loaded from the persistent cache in
``<checkout>/.jax_cache``), measures whole rounds for ``--seconds``
seconds, and checks the rounds it produced against the plain reference.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``, with
``--trace 1`` a ``breakdown``, and last ``checks``: each compared number
beside its limit.  The same numbers end standard error.

Without a TPU, or with fewer chips than the cell asks for, the run exits
with code 2 and prints no result.
"""

from __future__ import annotations

import time

CLOCK0 = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

_BENCH = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_BENCH)
if sys.path and os.path.abspath(sys.path[0] or ".") == _BENCH:
    sys.path.pop(0)
for _p in (os.path.join(_ROOT, "src"), _ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import harness  # noqa: E402
from bench.peaks import peak  # noqa: E402


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


class Tracer:
    """A profiler trace of the window, reduced when it stops."""

    def __init__(self):
        import jax
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self):
        import jax
        from bench import trace_reduce
        jax.profiler.stop_trace()
        try:
            files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            if not files:
                return None
            return trace_reduce.reduce(trace_reduce.load(files[0]))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def _memory_peak(devices) -> int | None:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             root: str = harness.ROOT, require_tpu: bool = True,
             overrides: dict | None = None) -> dict:
    """One run of cell ``name``; returns the result line as a dict.

    ``overrides`` updates the cell's ``config``, ``traffic`` and
    ``strategy`` entries (tests run a cell at a toy size with it).  With ``require_tpu``
    unset the run takes whatever devices JAX has.
    """
    import jax

    bench = harness.load_benchmark(root)
    entry = {w["name"]: w for w in bench["workloads"]}.get(name)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    bench_dir = os.path.join(root, "bench")
    cell = harness.load_json("workloads", name, bench_dir)
    cfg = harness.load_json("configs", cell["config"], bench_dir)
    overrides = overrides or {}
    cfg.update(overrides.get("config", {}))
    cell["traffic"].update(overrides.get("traffic", {}))
    cell["strategy"].update(overrides.get("strategy", {}))

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"JAX found platform {devices[0].platform!r} "
                     f"({devices[0].device_kind}), not a TPU")
    if len(devices) < entry["chips"]:
        raise NoChip(f"the cell needs {entry['chips']} chips; JAX found "
                     f"{len(devices)}")
    used = devices[:entry["chips"]]
    if require_tpu:
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    job = types.SimpleNamespace(
        cell=cell, config=cfg, seed=seed, seconds=seconds, trace=trace,
        model=harness.load_module("configs", cell["config"], bench_dir),
        traffic=harness.load_module("traffic", cell["traffic"]["generator"],
                                    bench_dir),
        clock0=CLOCK0,
        tracer=Tracer,
        memory_peak=lambda: _memory_peak(used))
    engine = harness.load_module("engines", cell["engine"], bench_dir)
    raw = engine.run(job)

    limits = cell["check"]["limits"]
    checks = {k: {"value": raw["checks"][k], "limit": lim}
              for k, lim in limits.items()}
    correct = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in checks.values())
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": raw[
                  "memory_peak_bytes"]}
    metrics = {}
    if not trace:
        for m in harness.cell_metrics(bench, name, "end_to_end"):
            metrics[m["name"]] = {"value": raw["end_to_end"][m["name"]],
                                  "unit": m["unit"]}
    line = {"correct": correct, "attempted": raw["attempted"],
            "failed": raw["failed"], "metrics": metrics, "device": device}
    if trace:
        t = raw["trace"]
        ctx = dict(raw["context"], trace=t, chips=len(used),
                   peak=peak(d0.device_kind), config=cfg, cell=cell)
        for m in harness.cell_metrics(bench, name, "per_layer"):
            value = harness.load_module("metrics", m["name"],
                                        bench_dir).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if t:
            device["busy_s"] = t["busy_s"]
            device["window_s"] = t["window_s"]
            line["breakdown"] = t["breakdown"]
    line["check_detail"] = dict(
        raw["checks"].get("detail", {}),
        not_compared={k: v for k, v in raw["checks"].items()
                      if k not in limits and k != "detail"})
    line["checks"] = checks
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(_ROOT, "src", "repro")):
        print(f"bench: no program under {_ROOT}/src", file=sys.stderr)
        return 3
    try:
        line = run_cell(args.workload, args.seed, args.seconds,
                        bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}; no result", file=sys.stderr)
        return 2
    print(f"correct: {line['correct']}", file=sys.stderr)
    for k, c in line["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
