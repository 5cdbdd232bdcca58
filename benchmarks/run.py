"""Run every benchmark (one module per paper figure + kernels + roofline).

  PYTHONPATH=src python -m benchmarks.run [--only fig3,fig4,...]

Prints one CSV block per figure and writes results/benchmarks.json; exits
non-zero when any figure failed.
"""

import argparse
import json
import os
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (fig3_sampling, fig4_masking, fig5_combined,
                            fig67_vgg, fig89_lm, hetero_sampling,
                            kernels_bench, noniid, roofline)
    from benchmarks.common import fmt_rows

    modules = {
        "fig3": fig3_sampling, "fig4": fig4_masking, "fig5": fig5_combined,
        "fig67": fig67_vgg, "fig89": fig89_lm, "kernels": kernels_bench,
        "noniid": noniid, "hetero": hetero_sampling, "roofline": roofline,
    }
    only = set(args.only.split(",")) if args.only else None

    all_rows = []
    failed = []
    for name, mod in modules.items():
        if only and name not in only:
            continue
        t0 = time.time()
        try:
            rows = mod.run()
        except Exception as e:  # noqa: BLE001 — report, run the rest, exit 1
            print(f"== {name}: FAILED: {e!r}")
            failed.append(name)
            continue
        print(f"== {name} ({time.time() - t0:.0f}s)")
        print(fmt_rows(rows))
        print()
        all_rows.extend(rows)

    os.makedirs("results", exist_ok=True)
    with open("results/benchmarks.json", "w") as f:
        json.dump(all_rows, f, indent=1)
    print(f"wrote results/benchmarks.json ({len(all_rows)} rows)")
    if failed:
        raise SystemExit(f"{len(failed)} figure(s) failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
