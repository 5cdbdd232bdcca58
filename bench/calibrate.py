#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, for the control
and the planted faults, at a cell's own size.

    python3 bench/calibrate.py --workload <cell> --seeds <s1,s2,...> \
        [--variants control,half_batch,upload_altered,index_permuted]

For each seed it runs the plain reference (float32 at ``highest``
matmul precision) over the cell's first rounds, then each variant put in
the program's place, and prints one JSON line per (seed, variant) with the
numbers that ``bench/reference.py`` compares:

* ``control``: the reference computed in bfloat16, the precision below
  the configuration's float32;
* ``default_precision``: the reference in float32 at the backend's
  default matmul precision (on a TPU, one bfloat16 pass), as the program
  runs; not a fault, but the look at what precision alone moves;
* ``half_batch``: each local step sees half of its batch and takes the
  mean over it;
* ``upload_altered``: the first participant's upload is negated where it
  is produced;
* ``index_permuted``: every upload's values move one index along each
  flattened leaf, as a wire decode that scatters to the wrong indices
  would put them; leaf norms are unchanged.

A step that returns its state unchanged reads 1 on ``update1_gap``,
``update1_diff`` and ``change3_gap`` by construction, and needs no run.  The benchmark's own
runs do not run this script.  Runs on a TPU only, like the benchmark.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_BENCH = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_BENCH)
if sys.path and os.path.abspath(sys.path[0] or ".") == _BENCH:
    sys.path.pop(0)
for _p in (os.path.join(_ROOT, "src"), _ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import harness, reference  # noqa: E402

VARIANTS = {
    "control": dict(dtype="bfloat16", precision=None),
    "default_precision": dict(precision=None),
    "half_batch": dict(fault="half_batch", precision=None),
    "upload_altered": dict(fault="upload_altered", precision=None),
    "index_permuted": dict(fault="index_permuted", precision=None),
}


def readings(name: str, seed: int, variants, overrides=None) -> list:
    """One reading per variant for one seed of cell ``name``."""
    import jax

    cell = harness.load_json("workloads", name)
    cfg = harness.load_json("configs", cell["config"])
    overrides = overrides or {}
    cfg.update(overrides.get("config", {}))
    cell["traffic"].update(overrides.get("traffic", {}))
    cell["strategy"].update(overrides.get("strategy", {}))
    model = harness.load_module("configs", cell["config"])
    gen = harness.load_module("traffic", cell["traffic"]["generator"])
    seed = seed % 2 ** 32
    k_params, k_data = jax.random.split(jax.random.PRNGKey(seed))
    batches, n = gen.generate(k_data, cfg, cell["traffic"])
    ids = reference.cell_cohorts(cell, seed)
    kw = reference.settings(cell)
    p0 = jax.jit(lambda k: model.ref_init(k, cfg))(k_params)
    t0 = time.perf_counter()
    ref_losses, ref_trail = reference.run_rounds(model.ref_loss, p0, batches,
                                                 n, ids, **kw)
    ref_s = time.perf_counter() - t0
    out = []
    for v in variants:
        t0 = time.perf_counter()
        losses, trail = reference.run_rounds(model.ref_loss, p0, batches, n,
                                             ids, **kw, **VARIANTS[v])
        prog = {"losses": losses, "p0": p0, "p1": trail[0],
                "p3": trail[-1]}
        r = reference.readings(prog, ref_losses, ref_trail, p0)
        out.append(dict(r, workload=name, seed=seed, variant=v,
                        reference_s=ref_s,
                        variant_s=time.perf_counter() - t0))
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants",
                    default="control,half_batch,upload_altered,"
                            "index_permuted")
    args = ap.parse_args()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU; no readings", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    for s in args.seeds.split(","):
        for r in readings(args.workload, int(s), args.variants.split(",")):
            print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
