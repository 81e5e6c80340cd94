#!/usr/bin/env python3
"""Per-conv-fusion roofline audit of the headline step (PERF.md round 5).

Rounds 2-4 booked the conv bucket as "23.7 ms at ~72% of roofline" from
aggregate arithmetic. This closes the audit at the granularity that
claim needs: ONE table with a row per conv-containing fusion —
device time (XLA trace) x FLOPs (from every convolution's dim_labels,
exact) x HBM bytes (fusion operands + outputs) x its OWN roofline
max(MXU time, traffic time) — so "the residual is emitter-bound" is
either demonstrated per layer or refuted by specific outliers.

Machine constants: bf16 peak 197 TFLOP/s (published), sustained HBM
635 GB/s (an in-program chain measured before PR 1 on a set-up that no
longer exists — re-measure before trusting a roofline share, PERF.md).
Per-op times come from the device trace, not the host clock.

Writes CONV_ROOFLINE.json (repo root) and prints the table.

Usage: python scripts/exp_conv_roofline.py [--batch 128] [--iters 6]
"""

import argparse
import collections
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

PEAK_FLOPS = 197e12  # bf16, measured in-program (PERF.md round 3)
HBM_BW = 635e9       # B/s, measured in-program (PERF.md round 3)

_DTYPE_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4,
                "pred": 1, "u8": 1, "s8": 1, "f64": 8, "s64": 8, "u64": 8}


def parse_shapes(text):
    """name -> list of (dtype, [dims]) for every instruction (tuples give
    multiple entries)."""
    shapes = {}
    for line in text.splitlines():
        # opname must admit hyphens (get-tuple-element, copy-done, ...):
        # missing those entries silently under-counts fusion operand bytes
        m = re.match(
            r"\s*(?:ROOT\s+)?%?([\w.-]+)\s+=\s+(.*?)\s+[\w-]+\(", line
        )
        if not m:
            continue
        name, typestr = m.group(1), m.group(2)
        entries = []
        for dt, dims in re.findall(r"(\w+)\[([\d,]*)\]", typestr):
            if dt not in _DTYPE_BYTES:
                continue
            entries.append(
                (dt, [int(d) for d in dims.split(",") if d] or [1])
            )
        if entries:
            shapes[name] = entries
    return shapes


def nbytes(entries):
    return sum(
        _DTYPE_BYTES[dt] * int(np.prod(dims)) for dt, dims in entries
    )


def conv_flops(line, shapes):
    """Exact FLOPs of one convolution instruction from its dim_labels:
    2 * prod(output) * prod(rhs contracted dims) — rhs 'i' dim and rhs
    spatial dims are the contraction (holds for forward, grad-input and
    grad-filter forms alike)."""
    m = re.match(
        r"\s*(?:ROOT\s+)?%?([\w.-]+)\s+=\s+(\w+)\[([\d,]*)\]", line
    )
    ops = re.findall(r"%?([\w.-]+)", line[line.index("convolution(") :])
    # operands: first two names after 'convolution('
    opnd = re.search(r"convolution\(\s*%?([\w.-]+)(?:\.clone)?\s*,\s*%?([\w.-]+)", line)
    dl = re.search(r"dim_labels=([\w]+)_([\w]+)->([\w]+)", line)
    if not (m and opnd and dl):
        return None
    out_dims = [int(d) for d in m.group(3).split(",") if d] or [1]
    rhs_name = opnd.group(2)
    rhs_entry = shapes.get(rhs_name)
    if not rhs_entry:
        return None
    rhs_dims = rhs_entry[0][1]
    rhs_labels = dl.group(2)
    contracted = 1
    for ch, size in zip(rhs_labels, rhs_dims):
        if ch == "i" or ch.isdigit():
            contracted *= size
    fgc = re.search(r"feature_group_count=(\d+)", line)
    # grouped convs already carry Ci/g in the kernel's i dim — no extra
    # correction needed; batch_group_count likewise rides the labels
    return 2.0 * float(np.prod(out_dims)) * contracted, (
        f"{m.group(2)}[{m.group(3)}]",
        "x".join(str(d) for d in rhs_dims),
        int(fgc.group(1)) if fgc else 1,
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--iters", type=int, default=6)
    ap.add_argument("--out", default="CONV_ROOFLINE.json")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from dptpu.models import create_model
    from dptpu.ops.schedules import make_step_decay_schedule
    from dptpu.train import create_train_state, make_optimizer, make_train_step
    from dptpu.utils.profiling import profile_device_time

    model = create_model("resnet50", dtype=jnp.bfloat16)
    tx = make_optimizer(0.9, 1e-4)
    state = create_train_state(
        jax.random.PRNGKey(0), model, tx, input_shape=(1, 224, 224, 3)
    )
    step = make_train_step(
        None, jnp.bfloat16, lr_schedule=make_step_decay_schedule(0.1, 100)
    )
    rng = np.random.RandomState(0)
    batch = {
        "images": rng.randint(
            0, 256, (args.batch, 224, 224, 3)
        ).astype(np.uint8),
        "labels": rng.randint(0, 1000, (args.batch,)).astype(np.int32),
    }
    compiled = step.lower(state, batch).compile()
    text = compiled.as_text()
    shapes = parse_shapes(text)

    # map fused computation name -> conv instructions inside it
    comp_convs = collections.defaultdict(list)
    current = None
    for line in text.splitlines():
        cm = re.match(r"\s*%?([\w.-]+)\s+\(.*\)\s+->\s+.*\{", line)
        if cm and " = " not in line:
            current = cm.group(1)
            continue
        if line.strip() == "}":
            current = None
            continue
        if " convolution(" in line and current:
            fl = conv_flops(line, shapes)
            if fl:
                comp_convs[current].append(fl)

    # map fusion instruction -> (calls computation, operands, out bytes)
    fusions = {}
    for line in text.splitlines():
        if " fusion(" not in line and " convolution(" not in line:
            continue
        m = re.match(r"\s*(?:ROOT\s+)?%?([\w.-]+)\s+=", line)
        if not m:
            continue
        name = m.group(1)
        out_b = nbytes(shapes.get(name, []))
        if " fusion(" in line:
            cm = re.search(r"calls=%?([\w.-]+)", line)
            if not cm or cm.group(1) not in comp_convs:
                continue
            arglist = re.search(r"fusion\((.*?)\)", line)
            operands = re.findall(r"%?([\w.-]+)", arglist.group(1)) if arglist else []
            in_b = sum(nbytes(shapes.get(o, [])) for o in operands)
            fusions[name] = {
                "convs": comp_convs[cm.group(1)],
                "bytes": in_b + out_b,
            }
        else:  # bare convolution at module level
            fl = conv_flops(line, shapes)
            if fl:
                opnd = re.search(
                    r"convolution\(\s*%?([\w.-]+)\s*,\s*%?([\w.-]+)", line
                )
                in_b = sum(
                    nbytes(shapes.get(o, []))
                    for o in (opnd.group(1), opnd.group(2))
                ) if opnd else 0
                fusions[name] = {"convs": [fl], "bytes": in_b + out_b}

    print(f"{len(fusions)} conv-bearing instructions in HLO")

    # the step donates its state, so the profiled callable must carry it
    # (same pattern as bench.py's device-time cross-check)
    holder = {"state": state}

    def traced_step():
        holder["state"], m = step(holder["state"], batch)
        return m

    total_ms, per_op = profile_device_time(traced_step, iters=args.iters)
    print(f"device op-sum: {total_ms:.2f} ms/step")

    # normalize trace names (strip leading %, xla sometimes suffixes)
    trace = {k.lstrip("%"): v for k, v in per_op.items()}

    rows = []
    unmatched = []
    for name, info in fusions.items():
        ms = trace.get(name)
        if ms is None:
            # trace names may carry the computation prefix; try suffix match
            cands = [v for k, v in trace.items()
                     if k == name or k.endswith("/" + name)]
            ms = cands[0] if cands else None
        if ms is None:
            # no device-time entry for this HLO instruction — report it,
            # never silently shrink the audit (an unmatched fusion with
            # real runtime would falsify the table's completeness)
            unmatched.append(name)
            continue
        flops = sum(f for f, _ in info["convs"])
        mxu_ms = flops / PEAK_FLOPS * 1e3
        mem_ms = info["bytes"] / HBM_BW * 1e3
        roof_ms = max(mxu_ms, mem_ms)
        rows.append({
            "fusion": name,
            "ms": round(ms, 3),
            "n_convs": len(info["convs"]),
            "main_conv": info["convs"][0][1][0],
            "kernel": info["convs"][0][1][1],
            "gflop": round(flops / 1e9, 2),
            "mbytes": round(info["bytes"] / 1e6, 1),
            "mxu_ms": round(mxu_ms, 3),
            "mem_ms": round(mem_ms, 3),
            "roof_ms": round(roof_ms, 3),
            "eff": round(roof_ms / ms, 3) if ms else None,
            "bound": "mxu" if mxu_ms >= mem_ms else "mem",
        })
    rows.sort(key=lambda r: -r["ms"])
    tot = sum(r["ms"] for r in rows)
    roof_tot = sum(r["roof_ms"] for r in rows)
    print(f"matched conv-fusion time: {tot:.2f} ms; "
          f"sum of per-fusion rooflines: {roof_tot:.2f} ms; "
          f"aggregate efficiency {roof_tot / tot:.1%}")
    if unmatched:
        # completeness cross-check: the matched rows + every other traced
        # op must still account for the whole step — a large residual
        # here would mean the audit is partial
        print(f"WARNING: {len(unmatched)} conv-bearing HLO instructions "
              f"have no trace entry (e.g. {unmatched[:5]}); device "
              f"op-sum {total_ms:.2f} ms vs matched {tot:.2f} ms + "
              f"other traced ops "
              f"{total_ms - tot:.2f} ms")
    hdr = (f"{'fusion':28s} {'ms':>7s} {'eff':>6s} {'bound':>5s} "
           f"{'GF':>8s} {'MB':>8s} {'roof':>7s}  main conv (kernel)")
    print(hdr)
    for r in rows:
        print(f"{r['fusion'][:28]:28s} {r['ms']:7.3f} "
              f"{(r['eff'] if r['eff'] else 0):6.2f} {r['bound']:>5s} "
              f"{r['gflop']:8.1f} {r['mbytes']:8.1f} {r['roof_ms']:7.3f}  "
              f"{r['main_conv']} ({r['kernel']}, n={r['n_convs']})")
    with open(args.out, "w") as f:
        json.dump({"total_step_ms": total_ms,
                   "conv_fusion_ms": round(tot, 2),
                   "conv_roofline_ms": round(roof_tot, 2),
                   "peak_flops": PEAK_FLOPS, "hbm_bw": HBM_BW,
                   "unmatched_fusions": unmatched,
                   "rows": rows}, f, indent=1)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
