#!/usr/bin/env python3
"""Readings for the limits of ``correct``, at a cell's own size, on the chip.

    python3 benchmark/tools/calibrate.py --workload <name> --seeds 201,202,203

For each seed, in ONE process: the benchmark's seeded weights and
regenerated rows, the plain reference in float32 (HIGHEST), then the
reference put in the program's place in bfloat16 (a witness: it should
read as the program reads) and in fp8 (the control: it has to come out
as not correct). Prints, per seed and mode, every number that
``lib/check.py`` compares, with the spread of the per-leaf gaps, and
last the smallest control reading next to the largest witness reading.
The program's own readings come from ``run.py``'s runs, which print the
same numbers. Needs no ``fit()`` and no measured window: a training
cell's readings are of its first steps.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--modes", default="bf16,fp8")
    args = parser.parse_args(argv)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    from benchmark.lib import cells, check, drive

    cell = cells.load_cell(args.workload)
    import jax

    print("calibrate:", drive.device_info(), flush=True)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        weights = drive.make_weights(cell, seed)
        batches = drive.regenerate_batches(cell, seed)
        ref = drive.reference_run(cell, weights, batches)
        for mode in args.modes.split(","):
            got = drive.reference_run(cell, weights, batches, mode)
            row = {"seed": seed, "mode": mode}
            for k, (a, b) in enumerate(zip(got["loss"], ref["loss"]), 1):
                row[f"loss_gap_{k}"] = abs(a - b)
            for name, key in (("grad_gap", "trace1"), ("delta_gap", "delta")):
                for stat, value in check.summarize(got[key],
                                                    ref[key]).items():
                    row[f"{name}_{stat}"] = value
                row[name + "_global"] = check.global_gap(got[key], ref[key])
            rows.append(row)
            print(json.dumps(row), flush=True)
        jax.clear_caches()
    names = ["loss_gap_1", "loss_gap_2", "loss_gap_3"] + [
        f"{n}_{stat}" for n in ("grad_gap", "delta_gap")
        for stat in ("worst", "kernels", "p90", "median", "global")]
    for name in names:
        per = {m: [r[name] for r in rows if r["mode"] == m]
               for m in args.modes.split(",")}
        print(name, {m: (min(v), max(v)) for m, v in per.items() if v},
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
