"""HLO static budget gates — the compile-time half of ``dptpu check``.

Compiles the representative step configs on the CPU backend (the fake
8-device pod, tests/conftest.py's trick) and statically asserts the
committed budget table ``HLO_BUDGETS.json``:

* per-link collective instruction counts and per-chip ring-send bytes
  EXACTLY as committed, and within 2% of the analytic formulas locked
  in tests/test_hierarchy.py (flat DDP: ``2(n-1)/n × (G + P)`` of pure
  all-reduce; ZeRO-1: same total volume as DDP; accum: identical
  collectives to DDP — ONE reduction per update; hierarchical:
  RS+AG on ICI at ``2(I-1)/I·G``, the shard-sized AR crossing DCN at
  ``2(S-1)/S·G/I`` plus the world pmean);
* donation honored — the compiled module's ``input_output_alias`` map
  covers at least every parameter leaf, so the update never
  materializes a full-parameter copy;
* zero f64 shapes anywhere (no accidental double promotion);
* overlap evidence (the ``*_overlap`` configs, ISSUE 13): the bucketed
  engine (``DPTPU_OVERLAP=1``, dptpu/parallel/overlap.py) emits >= 2
  independent per-bucket reductions INTERLEAVED with backward compute
  in the compiled schedule (``hlo_accounting.overlap_evidence``), at
  total collective bytes within 0.1% of the unbucketed program;
* the rules-engine configs (ISSUE 16): ``zero3`` reproduces the DDP
  collective volume as AG+RS+AR (the r06 equivalence, stage-3 form),
  ``gspmd_hier`` keeps DCN bytes under half of flat GSPMD's all-DCN
  volume on the ``{slice, data}``-factored mesh, and ``gspmd_overlap``
  holds the partitioner's reduction volume at the DDP analytic with
  the same interleaving evidence as the shard_map overlap configs;
* the serve-quant config (ISSUE 18): the int8 serve forward's
  REQUESTED matmul dtypes, from pre-optimization HLO (this backend's
  float normalization hides them post-optimization) — s8 parameters
  present, >= 1 bf16 dot/convolution, ZERO f32/f64 dots — so a silent
  fp32 fallback in the quantized fast path fails statically.

A comms/sharding regression therefore fails ``dptpu check`` BEFORE any
bench runs. After an INTENDED change, re-commit the table with
``dptpu check --update-hlo-budgets``.

All jax/flax imports are lazy: importing this module (and the lint
half of dptpu.analysis) stays stdlib-only.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Dict, List, Optional, Tuple

BUDGETS_FILENAME = "HLO_BUDGETS.json"

# the representative geometries: 4 fake devices, 2 slices × 2 chips for
# the hierarchical arm (the tests/test_hierarchy.py geometry)
_N = 4
_SLICES = 2

REPRESENTATIVE_CONFIGS = ("ddp", "zero1", "accum", "slices",
                          "ddp_overlap", "zero1_overlap", "slices_overlap",
                          "zero3", "gspmd_hier", "gspmd_overlap",
                          "serve_quant")

# bucket bound for the overlap configs: small enough that the probe
# model's ~7 KB of gradients split into >= 2 buckets (the evidence
# gates need at least two independent per-bucket reductions)
_OVERLAP_BUCKET_BYTES = 2048

# The budgets gate the collective structure THIS REPO emits, so the one
# XLA:CPU pass that rewrites it is held off for these compiles: the
# CPU backend's all-reduce combiner folds every per-leaf and per-bucket
# all-reduce (and the metric psums) into a single instruction, which
# hides both the instruction counts and the overlap evidence. Bytes
# are unchanged either way; the TPU combiner has its own thresholds.
COMPILER_OPTIONS = {"xla_disable_hlo_passes": "cpu-all-reduce-combiner"}

# |parsed − analytic| / analytic tolerance: the formulas count gradient
# + pmean payload; the compiled program adds a handful of scalar-sized
# control collectives (same 2% bound tests/test_hierarchy.py locks)
_ANALYTIC_RTOL = 0.02


@dataclasses.dataclass(frozen=True)
class BudgetViolation:
    """One failed budget gate — formats to an actionable message."""

    config: str
    field: str
    message: str

    def format(self) -> str:
        return (
            f"hlo-budget: {BUDGETS_FILENAME}: [{self.config}] "
            f"{self.field}: {self.message} (if this comms/sharding "
            f"change is INTENDED, re-commit the table with "
            f"`dptpu check --update-hlo-budgets` and say why in the PR)"
        )


def _budget_model():
    """The budget probe model — dense-heavy so every leaf scatters at
    the 2/4-way geometries (the tests/test_hierarchy.py TinyDense
    pattern), with BN for the replicated batch_stats pmean."""
    from flax import linen as nn

    class BudgetNet(nn.Module):
        num_classes: int = 10

        @nn.compact
        def __call__(self, x, train: bool = False):
            x = nn.Conv(16, (3, 3), use_bias=False)(x)
            x = nn.BatchNorm(use_running_average=not train,
                             momentum=0.9)(x)
            x = nn.relu(x)
            x = x.mean(axis=(1, 2))
            x = nn.Dense(32)(x)
            x = nn.relu(x)
            return nn.Dense(self.num_classes)(x)

    return BudgetNet()


def _state():
    import jax

    from dptpu.train import create_train_state, make_optimizer

    tx = make_optimizer(momentum=0.9, weight_decay=1e-4)
    return create_train_state(
        jax.random.PRNGKey(0), _budget_model(), tx,
        input_shape=(1, 8, 8, 3),
    )


def _batch():
    import numpy as np

    rng = np.random.RandomState(0)
    return {
        "images": rng.randint(0, 256, (16, 8, 8, 3)).astype(np.uint8),
        "labels": rng.randint(0, 10, (16,)).astype(np.int32),
    }


def _leaf_counts(state) -> dict:
    import jax
    import numpy as np

    def total(tree):
        return 4 * sum(
            int(np.prod(l.shape)) if l.shape else 1
            for l in jax.tree_util.tree_leaves(tree)
        )

    return {
        "param_leaves": len(jax.tree_util.tree_leaves(state.params)),
        "state_leaves": len(jax.tree_util.tree_leaves(state)),
        # analytic payloads (fp32): gradient bytes, and the BN-stat +
        # 3-scalar-metric pmean payload — tests/test_hierarchy.py's
        # _grad_bytes/_pmean_bytes
        "grad_bytes": total(state.params),
        "pmean_bytes": total(state.batch_stats) + 4 * 3,
    }


def _compiled_text(step, st, batch) -> str:
    return step.lower(st, batch).compile(
        compiler_options=COMPILER_OPTIONS
    ).as_text()


def _compile_config(name: str) -> Tuple[str, dict]:
    """Compiled HLO text + model facts for one representative config."""
    import jax

    from dptpu.parallel import (
        make_hierarchical_mesh,
        make_mesh,
        make_zero1_train_step,
        make_zero3_train_step,
        replicated_sharding,
        shard_host_batch,
        shard_zero1_state,
        shard_zero3_state,
        zero3_param_specs,
    )
    from dptpu.train import make_train_step

    devices = jax.devices()[:_N]
    if len(devices) < _N:
        raise RuntimeError(
            f"HLO budget gates need {_N} devices, got {len(devices)} — "
            f"run under XLA_FLAGS=--xla_force_host_platform_device_"
            f"count=8 (tests/conftest.py does this automatically)"
        )
    st = _state()
    facts = _leaf_counts(st)
    if name == "slices":
        mesh = make_hierarchical_mesh(_SLICES, devices)
        step = make_train_step(mesh)
    elif name == "slices_overlap":
        mesh = make_hierarchical_mesh(_SLICES, devices)
        step = make_train_step(mesh, overlap=True,
                               bucket_bytes=_OVERLAP_BUCKET_BYTES)
    elif name == "accum":
        mesh = make_mesh(devices, {"data": _N})
        step = make_train_step(mesh, accum_steps=2)
    elif name == "zero1":
        mesh = make_mesh(devices, {"data": _N})
        step = make_zero1_train_step(mesh, st)
    elif name == "zero1_overlap":
        mesh = make_mesh(devices, {"data": _N})
        step = make_zero1_train_step(mesh, st, overlap=True,
                                     bucket_bytes=_OVERLAP_BUCKET_BYTES)
    elif name == "ddp":
        mesh = make_mesh(devices, {"data": _N})
        step = make_train_step(mesh)
    elif name == "ddp_overlap":
        mesh = make_mesh(devices, {"data": _N})
        step = make_train_step(mesh, overlap=True,
                               bucket_bytes=_OVERLAP_BUCKET_BYTES)
    elif name == "zero3":
        # ZeRO-3/FSDP: rules-table placement over the data axis; the
        # probe model is not a registry family, so the GENERIC table's
        # AUTO_FSDP row drives it (same as any CNN)
        mesh = make_mesh(devices, {"data": _N})
        z3_specs = zero3_param_specs("budgetnet", st.params, mesh)
        step = make_zero3_train_step(mesh, st, z3_specs)
    elif name in ("gspmd_hier", "gspmd_overlap"):
        from dptpu.parallel.gspmd import (
            dp_specs,
            gspmd_specs_for_arch,
            make_gspmd_train_step,
            shard_gspmd_state,
        )

        if name == "gspmd_hier":
            # the {slice, data}-factored mesh + rules-table FSDP
            # placement: the partitioner derives the DCN-aware
            # decomposition itself (the by_link gate below)
            mesh = make_hierarchical_mesh(_SLICES, devices)
            specs = gspmd_specs_for_arch("budgetnet", st.params, mesh,
                                         fsdp=True)
            step = make_gspmd_train_step(mesh, st, specs)
        else:
            mesh = make_mesh(devices, {"data": _N})
            specs = dp_specs(st.params)
            step = make_gspmd_train_step(
                mesh, st, specs, overlap=True,
                bucket_bytes=_OVERLAP_BUCKET_BYTES,
            )
        st = shard_gspmd_state(st, mesh, specs)
        batch = shard_host_batch(_batch(), mesh)
        return _compiled_text(step, st, batch), facts
    else:
        raise ValueError(
            f"unknown budget config {name!r} "
            f"(representative set: {', '.join(REPRESENTATIVE_CONFIGS)})"
        )
    if name == "zero3":
        st = shard_zero3_state(st, mesh, z3_specs)
    elif name.startswith("zero1"):
        st = shard_zero1_state(st, mesh)
    else:
        st = jax.tree_util.tree_map(
            lambda x: jax.device_put(x, replicated_sharding(mesh)), st
        )
    batch = shard_host_batch(_batch(), mesh)
    return _compiled_text(step, st, batch), facts


def _serve_quant_hlo() -> str:
    """Pre-optimization HLO of the serve engine's REAL int8 forward
    (``ServeEngine._forward_int8`` on a quantized resnet18@32 tree) —
    lowered, not compiled: the requested dot dtypes are the gate, and
    they exist before XLA's backend-specific rewrites (this container's
    CPU backend promotes bf16 gemms to f32 in the optimized text)."""
    import jax
    import jax.numpy as jnp

    from dptpu.ops.quant import quantize_tree
    from dptpu.serve.engine import ServeEngine

    engine = ServeEngine("resnet18", buckets=(1,), num_classes=8,
                         image_size=32, placement="replicated")
    qvars = {
        "params": quantize_tree(engine._host_variables["params"]),
        "batch_stats": engine._host_variables["batch_stats"],
    }
    structs = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), qvars
    )
    img = jax.ShapeDtypeStruct((1, 32, 32, 3), jnp.uint8)
    return jax.jit(engine._forward_int8).lower(
        structs, img
    ).compiler_ir(dialect="hlo").as_hlo_text()


def extract_budget(name: str) -> Tuple[dict, Optional[dict]]:
    """Parse one config's compiled program into its budget row."""
    from dptpu.parallel.hlo_accounting import (
        collective_bytes_by_link,
        collective_bytes_per_chip,
        donated_alias_count,
        dot_dtype_census,
        op_census,
        overlap_evidence,
        parse_collectives,
    )

    if name == "serve_quant":
        txt = _serve_quant_hlo()
        row = dot_dtype_census(txt)
        row["f64_shapes"] = op_census(txt)["f64_shapes"]
        return row, None

    txt, facts = _compile_config(name)
    inner = _N // _SLICES
    counts = {"all-gather": 0, "reduce-scatter": 0, "all-reduce": 0}
    for inst in parse_collectives(txt):
        counts[inst["op"]] += 1
    row = {
        "collective_instructions": counts,
        "per_chip": collective_bytes_per_chip(txt, _N),
        "alias_entries": donated_alias_count(txt),
        "f64_shapes": op_census(txt)["f64_shapes"],
    }
    if name in ("slices", "slices_overlap", "gspmd_hier"):
        row["by_link"] = collective_bytes_by_link(
            txt, lambda p: p // inner, _N
        )
    if name.endswith("_overlap"):
        # the overlap-evidence block: per-bucket reductions interleaved
        # with backward compute in the compiled schedule. Only the
        # GATED properties are committed — entry_instructions /
        # compute_between shift on any compute-only fusion change, and
        # locking them exactly would turn every XLA upgrade into a
        # phantom comms regression.
        ev = overlap_evidence(txt)
        row["overlap"] = {k: ev[k] for k in (
            "reductions", "interleaved_gaps", "contiguous_tail_block",
        )}
    return row, facts


def compute_budgets() -> dict:
    """The full budget table (what ``--update-hlo-budgets`` commits)."""
    configs = {}
    facts = None
    for name in REPRESENTATIVE_CONFIGS:
        configs[name], f = extract_budget(name)
        if f is not None:
            facts = f
    return {
        "version": 1,
        "geometry": {"devices": _N, "slices": _SLICES,
                     "inner": _N // _SLICES},
        "model": facts,
        "configs": configs,
    }


def load_budgets(root: str) -> Optional[dict]:
    path = os.path.join(root, BUDGETS_FILENAME)
    if not os.path.exists(path):
        return None
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def write_budgets(root: str, budgets: dict) -> str:
    path = os.path.join(root, BUDGETS_FILENAME)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(budgets, f, indent=1, sort_keys=True)
        f.write("\n")
    return path


def _analytic_violations(computed: dict) -> List[BudgetViolation]:
    """The committed-table-independent half: the compiled programs must
    reproduce the analytic formulas (so even a stale committed table
    cannot bless a regression)."""
    out = []
    n, s = _N, _SLICES
    inner = n // s
    g = computed["model"]["grad_bytes"]
    p = computed["model"]["pmean_bytes"]
    cfg = computed["configs"]

    def close(got, want):
        return want > 0 and abs(got - want) / want < _ANALYTIC_RTOL

    ddp = cfg["ddp"]["per_chip"]
    if ddp["reduce-scatter"] or ddp["all-gather"]:
        out.append(BudgetViolation(
            "ddp", "per_chip",
            f"flat DDP must emit ONLY all-reduce, got RS="
            f"{ddp['reduce-scatter']} AG={ddp['all-gather']} bytes",
        ))
    want = 2 * (n - 1) / n * (g + p)
    if not close(ddp["all-reduce"], want):
        out.append(BudgetViolation(
            "ddp", "per_chip.all-reduce",
            f"{ddp['all-reduce']} bytes vs analytic 2(n-1)/n·(G+P) = "
            f"{want:.0f} (r06 lock, tests/test_hierarchy.py)",
        ))
    z = cfg["zero1"]["per_chip"]["total"]
    if not (ddp["total"] > 0
            and abs(z - ddp["total"]) / ddp["total"] < 0.001):
        out.append(BudgetViolation(
            "zero1", "per_chip.total",
            f"{z} bytes vs DDP's {ddp['total']} — ZeRO-1's AG+RS volume "
            f"must equal the DDP all-reduce (the r06 equivalence)",
        ))
    # ZeRO-3: gather-on-use + scatter-on-grad is the SAME volume as the
    # DDP all-reduce (AG (n-1)/n·G forward + RS (n-1)/n·G backward +
    # the pmean AR — the r06 equivalence extended to stage 3), and the
    # program must actually show the gather/scatter shape
    z3 = cfg["zero3"]["per_chip"]
    if not (z3["all-gather"] > 0 and z3["reduce-scatter"] > 0):
        out.append(BudgetViolation(
            "zero3", "per_chip",
            f"AG={z3['all-gather']} RS={z3['reduce-scatter']} bytes — "
            f"ZeRO-3 must all-gather params at use and reduce-scatter "
            f"the grads (did the placement collapse to replicated?)",
        ))
    if not (ddp["total"] > 0
            and abs(z3["total"] - ddp["total"]) / ddp["total"] < 0.001):
        out.append(BudgetViolation(
            "zero3", "per_chip.total",
            f"{z3['total']} bytes vs DDP's {ddp['total']} — ZeRO-3's "
            f"AG+RS+AR volume must equal the DDP all-reduce (the r06 "
            f"equivalence, stage-3 form)",
        ))
    if (cfg["accum"]["collective_instructions"]
            != cfg["ddp"]["collective_instructions"]):
        out.append(BudgetViolation(
            "accum", "collective_instructions",
            f"{cfg['accum']['collective_instructions']} vs DDP's "
            f"{cfg['ddp']['collective_instructions']} — accumulation "
            f"must keep ONE reduction per update, never per microbatch",
        ))
    want_ici = 2 * (inner - 1) / inner * g
    want_dcn = (2 * (s - 1) / s * g / inner
                + 2 * (n - 1) / n * p)
    for cname in ("slices", "slices_overlap"):
        link = cfg[cname]["by_link"]
        structural = (link["ici"]["all-reduce"] == 0
                      and link["dcn"]["reduce-scatter"] == 0
                      and link["dcn"]["all-gather"] == 0)
        if not structural:
            out.append(BudgetViolation(
                cname, "by_link",
                "the hierarchical decomposition leaked: ICI must carry "
                "only RS+AG and DCN only the shard-sized AR "
                f"(got ici.AR={link['ici']['all-reduce']} "
                f"dcn.RS={link['dcn']['reduce-scatter']} "
                f"dcn.AG={link['dcn']['all-gather']})",
            ))
        if not close(link["ici"]["total"], want_ici):
            out.append(BudgetViolation(
                cname, "by_link.ici.total",
                f"{link['ici']['total']} bytes vs analytic 2(I-1)/I·G = "
                f"{want_ici:.0f}",
            ))
        if not close(link["dcn"]["total"], want_dcn):
            out.append(BudgetViolation(
                cname, "by_link.dcn.total",
                f"{link['dcn']['total']} bytes vs analytic "
                f"2(S-1)/S·G/I + 2(n-1)/n·P = {want_dcn:.0f}",
            ))
    # overlap gates (ISSUE 13 acceptance): the bucketed engine's bytes
    # are a pure regrouping — totals within 0.1% of the unbucketed
    # program — and the compiled schedule shows >= 2 independent
    # per-bucket reductions interleaved with backward compute
    for cname, base in (("ddp_overlap", "ddp"),
                        ("zero1_overlap", "zero1")):
        got = cfg[cname]["per_chip"]["total"]
        want = cfg[base]["per_chip"]["total"]
        if not (want > 0 and abs(got - want) / want < 0.001):
            out.append(BudgetViolation(
                cname, "per_chip.total",
                f"{got} bytes vs the unbucketed {base} program's {want} "
                f"— bucketing must be a pure regrouping of the same "
                f"reduction bytes (0.1% gate)",
            ))
    # GSPMD gates. The partitioner derives its own collectives, so the
    # honest assertions differ from the shard_map ones:
    # * gspmd_overlap — the bucket boundaries are sharding-constraint
    #   annotations on logically-pre-reduced grads; the partitioner's
    #   per-leaf reductions ALREADY interleave with backward compute,
    #   and bucketing must stay a pure regrouping of the same volume
    #   (in practice the compiled program is identical to unbucketed —
    #   the gate is that the volume matches the DDP analytic, plus the
    #   overlap evidence thresholds in the *_overlap loop below).
    go = cfg["gspmd_overlap"]["per_chip"]
    if not close(go["all-reduce"], want):
        out.append(BudgetViolation(
            "gspmd_overlap", "per_chip.all-reduce",
            f"{go['all-reduce']} bytes vs the DDP analytic "
            f"2(n-1)/n·(G+P) = {want:.0f} — the partitioner's gradient "
            f"reduction volume drifted",
        ))
    # * gspmd_hier — the partitioner picks its own decomposition (AG+AR
    #   mixes, not the shard_map RS/AR/AG ladder), so the gate is the
    #   CLAIM that matters: the {slice, data} factoring + FSDP placement
    #   moves traffic off DCN. Flat GSPMD on this topology map crosses
    #   its whole volume over DCN (every group spans the world), so
    #   hier DCN bytes must stay under half of that, with ICI carrying
    #   the majority.
    gh = cfg["gspmd_hier"]["by_link"]
    flat_total = cfg["gspmd_overlap"]["per_chip"]["total"]
    if not (gh["dcn"]["total"] * 2 < flat_total):
        out.append(BudgetViolation(
            "gspmd_hier", "by_link.dcn.total",
            f"{gh['dcn']['total']} DCN bytes vs flat GSPMD's "
            f"{flat_total} all-DCN bytes — the hierarchical mesh no "
            f"longer moves the reduction off the slow link",
        ))
    if not (gh["ici"]["total"] > gh["dcn"]["total"]):
        out.append(BudgetViolation(
            "gspmd_hier", "by_link",
            f"ici={gh['ici']['total']} <= dcn={gh['dcn']['total']} "
            f"bytes — ICI must carry the majority of the collective "
            f"traffic on a {_SLICES}-slice mesh",
        ))
    for cname in ("ddp_overlap", "zero1_overlap", "slices_overlap",
                  "gspmd_overlap"):
        ev = cfg[cname]["overlap"]
        if ev["reductions"] < 2:
            out.append(BudgetViolation(
                cname, "overlap.reductions",
                f"{ev['reductions']} gradient-scale reduction "
                f"collectives in the compiled schedule — the bucketed "
                f"engine must emit >= 2 independent per-bucket "
                f"reductions (did the partition collapse to one "
                f"bucket, or did a combiner fuse them?)",
            ))
        if ev["interleaved_gaps"] < 1 or ev["contiguous_tail_block"]:
            out.append(BudgetViolation(
                cname, "overlap.interleaved_gaps",
                f"per-bucket reductions form one contiguous block "
                f"(interleaved_gaps={ev['interleaved_gaps']}) — the "
                f"schedule no longer overlaps the reductions with "
                f"backward computation",
            ))
    # serve-quant (ISSUE 18): the int8 serve forward's REQUESTED matmul
    # dtypes, asserted statically — a refactor that lets the fp32 model
    # dtype promote the dequantized weights back to f32 (the silent
    # fallback that keeps the residency win but loses the compute win)
    # fails here before any bench runs
    sq = cfg["serve_quant"]
    if sq["s8_params"] < 1:
        out.append(BudgetViolation(
            "serve_quant", "s8_params",
            "no s8 parameters in the int8 forward — the quantized "
            "weights no longer travel int8 (did stage_quantized start "
            "dequantizing on the host?)",
        ))
    if sq["dots"].get("bf16", 0) < 1:
        out.append(BudgetViolation(
            "serve_quant", "dots.bf16",
            f"{sq['dots']} — the int8 forward requests no bf16 "
            f"dot/convolution at all",
        ))
    fp_dots = sq["dots"].get("f32", 0) + sq["dots"].get("f64", 0)
    if fp_dots:
        out.append(BudgetViolation(
            "serve_quant", "dots.f32",
            f"{fp_dots} f32/f64 dot/convolution instructions in the "
            f"int8 forward ({sq['dots']}) — a silent fp32 fallback: "
            f"some layer's inputs or weights promoted past bf16 "
            f"(check the model's dtype attribute survives "
            f"ServeEngine._bf16_model)",
        ))
    for name, row in cfg.items():
        if row["f64_shapes"]:
            out.append(BudgetViolation(
                name, "f64_shapes",
                f"{row['f64_shapes']} f64 shapes in the compiled "
                f"program — an accidental double-precision promotion",
            ))
        if name == "serve_quant":
            continue  # an inference forward: donates nothing
        if row["alias_entries"] < computed["model"]["param_leaves"]:
            out.append(BudgetViolation(
                name, "alias_entries",
                f"input_output_alias covers {row['alias_entries']} "
                f"buffers < {computed['model']['param_leaves']} param "
                f"leaves — donation broke and the update now "
                f"materializes a full-parameter copy",
            ))
    return out


def check_hlo_budgets(
    root: str, budgets: Optional[dict] = None,
    computed: Optional[dict] = None,
) -> Tuple[List[BudgetViolation], dict]:
    """Run the gates. Returns (violations, computed_table). ``budgets``
    overrides the committed table and ``computed`` a fresh compile —
    the seeded-regression tests inject tampered tables through these
    without paying four compiles per case."""
    if computed is None:
        computed = compute_budgets()
    violations = _analytic_violations(computed)
    committed = budgets if budgets is not None else load_budgets(root)
    if committed is None:
        violations.append(BudgetViolation(
            "*", BUDGETS_FILENAME,
            "no committed budget table — generate one with "
            "`dptpu check --update-hlo-budgets`",
        ))
        return violations, computed
    for name in REPRESENTATIVE_CONFIGS:
        want = committed.get("configs", {}).get(name)
        got = computed["configs"][name]
        if want is None:
            violations.append(BudgetViolation(
                name, "configs",
                "config missing from the committed table",
            ))
            continue
        for field in ("collective_instructions", "per_chip", "by_link",
                      "alias_entries", "f64_shapes", "overlap",
                      "dots", "s8_params"):
            if field not in got and field not in want:
                continue
            if got.get(field) != want.get(field):
                violations.append(BudgetViolation(
                    name, field,
                    f"compiled program changed: committed "
                    f"{json.dumps(want.get(field), sort_keys=True)} "
                    f"vs compiled "
                    f"{json.dumps(got.get(field), sort_keys=True)}",
                ))
    return violations, computed


def budget_summary(violations: List[BudgetViolation],
                   computed: dict) -> Dict:
    """The ANALYSIS.json block for the HLO half."""
    return {
        "ok": not violations,
        "violations": [v.format() for v in violations],
        "configs": computed["configs"],
        "model": computed["model"],
        "geometry": computed["geometry"],
    }
