"""Which train step runs, on which mesh, with the state placed how.

``fit()`` asks once. ``decide`` reads the parallelism knobs under the
fail-fast contract and settles the precedence among the step families
(``DPTPU_TP`` > ``DPTPU_SP`` > ``DPTPU_ZERO=3`` > ``DPTPU_ZERO1`` >
``DPTPU_GSPMD``) without touching a device, so the rule can be tested
as a table; ``open_mesh`` makes the mesh the plan names; ``build``
constructs that family's step around the initialized state and places
the state where the step will leave it. The builders themselves live
in ``dptpu/train/step.py`` and ``dptpu/parallel``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional

import jax

from dptpu.envknob import env_axis, env_bool, env_choice, env_int
from dptpu.parallel import (
    gather_state,
    hierarchy_knobs,
    make_hierarchical_mesh,
    make_mesh,
    make_zero1_train_step,
    make_zero3_train_step,
    replicated_sharding,
    rules_fingerprint,
    shard_zero1_state,
    shard_zero3_state,
    state_shard_bytes,
    zero1_update_shard_bytes,
    zero3_param_specs,
    zero3_state_specs,
)
from dptpu.parallel.gspmd import tp_rule_for_arch
from dptpu.parallel.mesh import DATA_AXIS, MODEL_AXIS, SLICE_AXIS
from dptpu.parallel.overlap import overlap_knobs
from dptpu.parallel.sequence import SEQ_AXIS
from dptpu.train.step import make_eval_step, make_train_step


@dataclass(frozen=True)
class Plan:
    family: str  # "ddp" | "zero1" | "zero3" | "gspmd" | "seq"
    # axis name -> size in mesh order: a "model" axis is tensor
    # parallelism, "seq" sequence parallelism, "slice" the hierarchical
    # mesh; empty: one device and no mesh
    mesh_axes: dict
    sp_mode: str
    dcn_dtype: str  # as the knob says; only a hierarchical mesh has the hop
    overlap: bool
    bucket_bytes: int
    # stamped into checkpoints; a mid-epoch resume compares it
    fingerprint: str
    notices: tuple  # the "=> ..." lines, in the order they are printed


@dataclass
class Built:
    train_step: Callable
    eval_step: Callable
    state: object  # placed where ``train_step`` will leave it
    # what validation and the checkpoint writer are handed; a gathering
    # view is a collective that every host must join
    eval_view: Callable
    eval_view_gathers: bool
    opt_shard_bytes: Optional[int]
    # the same family's step on another schedule (a batch-ramp phase)
    rebuild: Callable
    notices: tuple


def decide(cfg, derived, *, task: str, n_devices: int, accum_steps: int,
           batch_ramp) -> Plan:
    """The plan for this run. Pure: environment, arguments and the
    partition-rules tables in, a ``Plan`` (or a knob's ``ValueError``)
    out. ``batch_ramp`` is the parsed ``DPTPU_BATCH_RAMP`` of a run that
    trains (None under ``--evaluate``)."""
    from dptpu.models.registry import GENERIC_RULES, partition_rules_for_arch

    arch, evaluate = cfg.arch, cfg.evaluate
    notices = []
    say = notices.append
    slices, dcn_dtype = hierarchy_knobs(cfg)
    want_overlap, bucket_bytes, bucket_explicit = overlap_knobs()
    single_device = cfg.gpu is not None or n_devices == 1

    # DPTPU_TP=N opens a model axis of size N on the mesh and routes
    # training through the GSPMD tensor-parallel step. The model axis
    # is INNER: on multi-host pods the hierarchical mesh keeps its
    # collectives on ICI (make_mesh guards the DCN crossing).
    tp_n = env_axis("DPTPU_TP", "model-axis size")
    if tp_n == 1:
        say("=> DPTPU_TP=1 is a no-op: a one-way model axis is just "
            "data parallelism")
    use_tp = tp_n > 1 and not single_device and not evaluate
    if tp_n > 1 and not use_tp:
        why = (
            "--evaluate does not train"
            if evaluate and not single_device
            else "single-device run (no mesh to open a model axis on)"
        )
        say(f"=> DPTPU_TP ignored: {why}")
    # An arch with no TP rule (CNNs, MaxViT) gets the flat full-width
    # data mesh: factoring a model axis it cannot use would make those
    # devices compute 100% redundantly instead of joining the data axis.
    # The request is demoted entirely, so that the precedence below
    # (DPTPU_ZERO1 etc.) does not see an inert TP claim.
    tp_fallback = use_tp and tp_rule_for_arch(arch) == "dp_specs"
    if tp_fallback:
        say(
            f"=> DPTPU_TP={tp_n}: no tensor-parallel rule for "
            f"'{arch}' (TP ships for vit_*/swin*/convnext_*; classic "
            f"CNNs and MaxViT keep the data axis — see dp_specs "
            f"docstring) — "
            f"running data parallelism over all "
            f"{n_devices} devices instead"
        )
        use_tp = False
    if use_tp and n_devices % tp_n != 0:
        raise ValueError(
            f"DPTPU_TP={tp_n} does not divide the {n_devices} "
            f"available devices — pick a divisor so the "
            f"{{data, model}} mesh factors"
        )
    # DPTPU_SP=N: sequence/context parallelism — a {data, seq: N} mesh,
    # the ViT token axis sharded over the inner seq axis with Ulysses or
    # ring attention. ViT-only: Swin's windowed attention is already
    # local and parallelizes spatially via the data axis (README); CNNs
    # have no token axis at all.
    sp_n = env_axis("DPTPU_SP", "seq-axis size")
    # fail-fast even when SP is off: a typo'd mode must not sit silently
    # in the environment waiting for the day DPTPU_SP is turned on
    sp_mode = env_choice("DPTPU_SP_MODE", ("ulysses", "ring"), "ulysses")
    if sp_n == 1:
        say("=> DPTPU_SP=1 is a no-op: a one-way seq axis is just "
            "data parallelism")
    use_sp = sp_n > 1 and not single_device and not evaluate and not use_tp
    if sp_n > 1 and not use_sp:
        why = (
            "DPTPU_TP takes precedence (TP x SP composition is not "
            "implemented)"
            if use_tp
            else "--evaluate does not train"
            if evaluate and not single_device
            else "single-device run (no mesh to open a seq axis on)"
        )
        say(f"=> DPTPU_SP ignored: {why}")
    if use_sp and not arch.startswith("vit_"):
        say(
            f"=> DPTPU_SP={sp_n}: no sequence-parallel path for "
            f"'{arch}' (global-attention ViTs only; Swin windows "
            f"are spatially local, CNNs have no token axis) — "
            f"running plain data parallelism over all "
            f"{n_devices} devices instead"
        )
        use_sp = False
    if use_sp and n_devices % sp_n != 0:
        raise ValueError(
            f"DPTPU_SP={sp_n} does not divide the {n_devices} "
            f"available devices — pick a divisor so the "
            f"{{data, seq}} mesh factors"
        )
    if use_sp and accum_steps > 1:
        # fail fast rather than silently changing the effective batch:
        # the sequence-parallel step has no microbatch scan (its token
        # axis already divides the work another way). Name the offending
        # knob AND the supported alternatives (message locked by
        # tests/test_opt_knobs.py::test_sp_accum_error_names_knob_and_alternative)
        raise ValueError(
            f"--accum-steps/DPTPU_ACCUM={accum_steps} has no "
            f"sequence-parallel implementation (DPTPU_SP={sp_n} replaces "
            f"the microbatch scan with a token-axis split); supported "
            f"alternatives: set DPTPU_ACCUM=1 and keep DPTPU_SP={sp_n}, "
            f"or unset DPTPU_SP to get data-parallel gradient "
            f"accumulation"
        )
    # DPTPU_SLICES/--slices > 1: two-level hierarchical data
    # parallelism (dptpu/parallel/hierarchy.py) — the gradient
    # all-reduce decomposes into reduce-scatter(ICI) + shard-sized
    # all-reduce(DCN) + all-gather(ICI). Composes with the default DDP
    # step, with DPTPU_ZERO1/DPTPU_ZERO=3 (state shards over the
    # intra-slice axis, so the weight all-gather stays on ICI), AND
    # with DPTPU_GSPMD (the {slice, data}-factored mesh + rules-table
    # FSDP placement make the partitioner derive its own DCN-aware
    # decomposition); TP/SP keep their own single-level topologies
    # (explicit requests win, with a notice).
    want_gspmd = bool(env_bool("DPTPU_GSPMD", False))
    use_hier = (
        slices > 1 and not single_device and not evaluate
        and not use_tp and not use_sp
    )
    if slices == 1 and env_int("DPTPU_SLICES", None) == 1:
        say("=> DPTPU_SLICES=1 is a no-op: one slice is the flat "
            "single-level data mesh")
    if slices > 1 and not use_hier:
        why = (
            "DPTPU_TP drives the GSPMD tensor-parallel step"
            if use_tp
            else "DPTPU_SP drives the sequence-parallel step"
            if use_sp
            else "--evaluate does not train"
            if evaluate and not single_device
            else "single-device run (no DCN hop to factor)"
        )
        say(f"=> DPTPU_SLICES={slices} ignored: {why}")
    if dcn_dtype != "fp32" and not use_hier:
        say(f"=> DPTPU_DCN_DTYPE={dcn_dtype} ignored: no hierarchical "
            f"mesh (set DPTPU_SLICES >= 2), so there is no DCN-only "
            f"hop to compress")
    if single_device:
        mesh_axes = {}
    elif use_tp:
        mesh_axes = {DATA_AXIS: n_devices // tp_n, MODEL_AXIS: tp_n}
    elif use_sp:
        mesh_axes = {DATA_AXIS: n_devices // sp_n, SEQ_AXIS: sp_n}
    elif use_hier:
        mesh_axes = {SLICE_AXIS: slices, DATA_AXIS: n_devices // slices}
        if want_gspmd or tp_fallback:
            say(
                f"=> hierarchical data parallelism: {slices} slices "
                f"x {n_devices // slices} chips/slice — "
                f"the SPMD partitioner derives the per-link "
                f"decomposition from the {{slice, data}}-factored "
                f"mesh + rules-table FSDP placement"
            )
        else:
            say(
                f"=> hierarchical data parallelism: {slices} slices x "
                f"{n_devices // slices} chips/slice — "
                f"gradient reduction is reduce-scatter(ICI) + "
                f"shard-sized all-reduce(DCN, {dcn_dtype}) + "
                f"all-gather(ICI)"
            )
    else:
        mesh_axes = {DATA_AXIS: n_devices}
    has_mesh = bool(mesh_axes)
    # DPTPU_ZERO selects the ZeRO stage by number: 1 is the shipped
    # weight-update sharding (same as DPTPU_ZERO1=1), 3 the full
    # param+grad+optimizer sharding driven by the arch's partition
    # rules table (dptpu/parallel/rules.py); DPTPU_FSDP=1 is the
    # synonym the FSDP literature spells stage 3 with.
    zero_stage = env_int("DPTPU_ZERO", None)
    if zero_stage not in (None, 0, 1, 3):
        raise ValueError(
            f"DPTPU_ZERO={zero_stage} is not a supported stage — use 1 "
            f"(weight-update sharding, the DPTPU_ZERO1=1 alias), 3 "
            f"(param+grad+optimizer sharding, the DPTPU_FSDP=1 alias), "
            f"or 0/unset for replicated data parallelism"
        )
    want_zero3 = zero_stage == 3 or bool(env_bool("DPTPU_FSDP", False))
    want_zero1 = bool(env_bool("DPTPU_ZERO1", False)) or zero_stage == 1
    # --evaluate never trains: sharding the state only to re-gather it
    # for validation would be two pointless full-state device_put rounds
    use_zero3 = (
        want_zero3 and has_mesh and not evaluate
        and not use_tp and not use_sp
    )
    use_zero1 = (
        want_zero1 and has_mesh and not evaluate and not use_tp
        and not use_sp and not use_zero3
    )
    if want_zero3 and use_tp:
        say("=> DPTPU_ZERO=3/DPTPU_FSDP ignored: DPTPU_TP drives the "
            "GSPMD tensor-parallel step (params shard over the model "
            "axis per the same rules table)")
    elif want_zero3 and use_sp:
        say("=> DPTPU_ZERO=3/DPTPU_FSDP ignored: DPTPU_SP drives the "
            "sequence-parallel step")
    if want_zero1 and use_zero3:
        say("=> DPTPU_ZERO1 noted: DPTPU_ZERO=3 supersedes it (stage "
            "3 shards everything stage 1 shards, plus the params)")
    elif want_zero1 and use_tp:
        say("=> DPTPU_ZERO1 ignored: DPTPU_TP drives the GSPMD "
            "tensor-parallel step (params shard over the model axis, "
            "not the optimizer state over data)")
    elif want_zero1 and use_sp:
        say("=> DPTPU_ZERO1 ignored: DPTPU_SP drives the "
            "sequence-parallel step")
    # DPTPU_GSPMD=1: the single-program GSPMD/pjit data-parallel step
    # (dp_specs) instead of the shard_map DDP step. Under GSPMD the
    # global batch is one logical program, so BN statistics are ALWAYS
    # global (SyncBN behavior) and the model must not carry a
    # shard-local axis name: fit() builds the model after this.
    use_gspmd = (
        (want_gspmd or use_tp or tp_fallback)
        and has_mesh and not evaluate
        and not use_zero3 and not use_zero1 and not use_sp
    )
    if task == "tokens" and (use_tp or use_sp or use_zero3 or use_zero1
                             or use_gspmd or batch_ramp is not None):
        raise ValueError(
            f"'{arch}' is a token-sequence model: it trains on the "
            f"replicated data-parallel step only (one chip, or "
            f"--slices over a data mesh); unset DPTPU_TP / DPTPU_SP / "
            f"DPTPU_ZERO* / DPTPU_FSDP / DPTPU_GSPMD / DPTPU_BATCH_RAMP"
        )
    if want_gspmd and use_sp:
        say("=> DPTPU_GSPMD ignored: DPTPU_SP drives the "
            "sequence-parallel step")
    if want_gspmd and not use_gspmd and not use_sp:
        # name a ZeRO stage as the reason only when it will actually run
        why = (
            "DPTPU_ZERO=3 takes precedence"
            if use_zero3
            else "DPTPU_ZERO1 takes precedence"
            if use_zero1
            else "--evaluate does not train"
            if evaluate
            else "single-device run (no mesh)"
        )
        say(f"=> DPTPU_GSPMD ignored: {why}")
    if use_gspmd and derived.sync_bn:
        say("=> --sync-bn is implicit under DPTPU_GSPMD: BatchNorm "
            "always sees the global batch in the single-program step")
    # Bucketed backward-overlapped gradient comms (DPTPU_OVERLAP=1,
    # dptpu/parallel/overlap.py): composes with the shard_map step
    # families (DDP, ZeRO-1/3, --slices, --accum-steps) AND the plain
    # GSPMD path (per-bucket sharding-constraint boundaries — the
    # partitioner already interleaves per-leaf reductions, so the
    # buckets bound its regrouping freedom rather than create overlap
    # from nothing); TP/SP place their own collectives, and a mesh-less
    # single-device step has none to overlap.
    use_overlap = (
        want_overlap and has_mesh and not evaluate
        and not use_tp and not use_sp
    )
    if want_overlap and not use_overlap:
        why = (
            "DPTPU_TP drives the GSPMD tensor-parallel step"
            if use_tp
            else "DPTPU_SP drives the sequence-parallel step"
            if use_sp
            else "--evaluate does not train"
            if evaluate and has_mesh
            else "single-device run (no gradient collective to overlap)"
        )
        say(f"=> DPTPU_OVERLAP ignored: {why}")
    if bucket_explicit and not want_overlap:
        say(f"=> DPTPU_BUCKET_MB={bucket_bytes / 1e6:g} noted: the "
            f"bucket bound only applies with DPTPU_OVERLAP=1")
    if use_overlap:
        say(
            f"=> overlapped gradient comms: reverse-layer buckets of "
            f"<= {bucket_bytes / 1e6:g} MB, each reduced as one fused "
            f"collective issued inside backward (bit-identical to the "
            f"unbucketed step)"
        )
    # "<rules-table-hash>:<placement>" for the sharded placements (the
    # hash pins the TABLE the placement came from, so editing a
    # family's rules reads as a sharding change on resume), plain
    # "replicated" for the replicated-param steps.
    arch_fp = rules_fingerprint(partition_rules_for_arch(arch))
    fingerprint = (
        f"{arch_fp}:zero3" if use_zero3
        # ZeRO-1 places per-leaf over data via the GENERIC table's
        # AUTO_FSDP row — its fingerprint must not move when a
        # family's TP rules are edited
        else f"{rules_fingerprint(GENERIC_RULES)}:zero1" if use_zero1
        else f"{arch_fp}:tp{tp_n}" if use_tp
        else f"{arch_fp}:fsdp" if (use_gspmd and use_hier)
        else "replicated"
    )
    # the ramp rebuilds the loader + step per phase, which only the
    # shard_map families support
    if batch_ramp is not None and (use_tp or use_sp or use_gspmd):
        who = ("DPTPU_TP" if use_tp else
               "DPTPU_SP" if use_sp else "DPTPU_GSPMD")
        raise ValueError(
            f"DPTPU_BATCH_RAMP has no {who} composition (the ramp "
            f"rebuilds the loader and step per phase; only the "
            f"shard_map DDP/ZeRO-1/--slices families support that); "
            f"supported alternatives: unset DPTPU_BATCH_RAMP and keep "
            f"{who}, or unset {who} to run the ramped data-parallel "
            f"recipe"
        )
    if want_zero3 and not has_mesh:
        say("=> DPTPU_ZERO=3/DPTPU_FSDP ignored: single-device run "
            "(no mesh to shard the params over)")
    elif want_zero3 and evaluate:
        say("=> DPTPU_ZERO=3/DPTPU_FSDP ignored: --evaluate does not "
            "train")
    if want_zero1 and not has_mesh:
        say("=> DPTPU_ZERO1 ignored: single-device run (no mesh to "
            "shard the optimizer state over)")
    elif want_zero1 and evaluate and not want_zero3:
        say("=> DPTPU_ZERO1 ignored: --evaluate does not train")
    return Plan(
        family=("zero3" if use_zero3 else "zero1" if use_zero1
                else "gspmd" if use_gspmd else "seq" if use_sp else "ddp"),
        mesh_axes=mesh_axes,
        sp_mode=sp_mode,
        dcn_dtype=dcn_dtype,
        overlap=use_overlap,
        bucket_bytes=bucket_bytes,
        fingerprint=fingerprint,
        notices=tuple(notices),
    )


def open_mesh(plan: Plan, *, elastic_resume: bool = False):
    """The mesh ``plan`` names over this process's world (None on one
    device). ``elastic_resume``: the run resumes under ``DPTPU_ELASTIC``,
    so a shrunk world that no longer divides ``--slices`` gets the
    message naming the knob AND both fallbacks instead of the generic
    mesh-factoring error. A FRESH run with ``DPTPU_ELASTIC`` exported (a
    job env knob that must survive restarts) is a plain slices
    misconfiguration and deserves the generic message."""
    if not plan.mesh_axes:
        return None
    slices = plan.mesh_axes.get(SLICE_AXIS)
    if slices is None:
        return make_mesh(mesh_shape=dict(plan.mesh_axes))
    if elastic_resume:
        from dptpu.parallel.hierarchy import elastic_slices_check

        elastic_slices_check(jax.device_count(), slices)
    # raises when slices does not divide the device count (or the
    # host count, multi-process)
    return make_hierarchical_mesh(slices)


def build(plan: Plan, mesh, state, schedule, *, arch: str, task: str,
          num_classes: int, compute_dtype, seed: int, accum_steps: int,
          label_smoothing: float, tx_factory, put,
          setup_phase) -> Built:
    """``plan.family``'s train step on ``schedule`` around ``state`` (as
    ``create_train_state`` or a checkpoint left it), and the state where
    that step will leave it. ``tx_factory`` remakes the optimizer for
    the sharded updates (their trust-ratio norms complete across the
    data axis); ``put`` places a host batch (and, on one device, the
    state); ``setup_phase`` names the set-up phase that begins."""
    notices = []
    opt_shard_bytes = None
    tp, hier = (axis in plan.mesh_axes for axis in (MODEL_AXIS, SLICE_AXIS))
    # off a hierarchical mesh there is no DCN-only hop to compress
    dcn_dtype = plan.dcn_dtype if hier else "fp32"
    # the sharded builders read which leaves shard off a state's shapes:
    # a template of shapes serves every schedule, and holds no array
    template = jax.eval_shape(lambda s: s, state)
    shard_map_kw = dict(
        seed=seed, accum_steps=accum_steps, label_smoothing=label_smoothing,
        dcn_dtype=dcn_dtype, overlap=plan.overlap,
        bucket_bytes=plan.bucket_bytes,
    )
    gathered = lambda s: gather_state(s, mesh)  # noqa: E731
    as_is = lambda s: s  # noqa: E731
    rebuild = None
    if plan.family == "zero3":
        # ZeRO-3/FSDP: params, gradients AND optimizer state live
        # sharded over the (intra-slice) data axis — placement comes
        # from the arch's partition-rules table projected onto the
        # data axis (dptpu/parallel/rules.py), the forward/backward
        # all-gather-on-use boundary is the _zero3_gather custom VJP
        # (its backward IS the reduce-scatter), and the entire update
        # runs on the local shard exactly like ZeRO-1. Same collective
        # volume as DDP (gather + scatter = the all-reduce bytes), so
        # the win is memory: ~1/N persistent bytes per chip for the
        # whole params+opt-state footprint (tests/test_zero1.py locks
        # parity and the byte ratio; SCALEBENCH reports it).
        param_specs = zero3_param_specs(arch, state.params, mesh)

        def rebuild(sched):
            return make_zero3_train_step(
                mesh, template, param_specs, compute_dtype,
                lr_schedule=sched, tx_factory=tx_factory, **shard_map_kw,
            )

        train_step = rebuild(schedule)
        opt_shard_bytes = state_shard_bytes(
            state, mesh, zero3_state_specs(state, mesh, param_specs)
        )
        state = shard_zero3_state(state, mesh, param_specs)
        # one all-gather per validation pass / checkpoint write (the
        # ZeRO-1 discipline) — sharded leaves are global jax.Arrays,
        # so the gather is transparent to eval and the writer
        eval_view, eval_view_gathers = gathered, True
        notices.append("=> ZeRO-3 param+grad+optimizer sharding over the "
                       f"data axis (rules table; persistent state "
                       f"{opt_shard_bytes / 1e6:.1f} MB/chip)")
    elif plan.family == "zero1":
        # ZeRO-1 weight-update sharding: params + optimizer state live
        # sharded over the data axis (~1/N persistent memory per chip),
        # gradients arrive reduce-scattered through the all-gather VJP,
        # and the ENTIRE update — including LARS/LAMB trust-ratio norms,
        # completed shard-locally with one small psum via the injected
        # tx_factory — runs on the local shard (arXiv:2004.13336;
        # tests/test_zero1.py). Checkpoints and eval read the state
        # transparently (sharded leaves are global jax.Arrays);
        # eval/checkpoint gathers are per-epoch, not per-step.
        def rebuild(sched):
            return make_zero1_train_step(
                mesh, template, compute_dtype, lr_schedule=sched,
                tx_factory=tx_factory, **shard_map_kw,
            )

        train_step = rebuild(schedule)
        opt_shard_bytes = zero1_update_shard_bytes(state, mesh)
        state = shard_zero1_state(state, mesh)
        # one all-gather per validation pass / checkpoint write (instead
        # of per eval step), and multi-host save stays fully addressable
        eval_view, eval_view_gathers = gathered, True
        notices.append("=> ZeRO-1 optimizer-state sharding over the data axis"
                       f" (update touches {opt_shard_bytes / 1e6:.1f} MB/chip)")
    elif plan.family == "gspmd":
        # single-program GSPMD/pjit path: shardings annotated on jit, the
        # partitioner derives every collective (gradient all-reduce over
        # data; under TP, one all-reduce per MLP/attention block over
        # model). Batch stays batch-dim-sharded over the data axes — the
        # layout shard_host_batch already produces — so loaders are
        # unchanged. On a hierarchical mesh (--slices > 1) params take
        # the rules-table FSDP placement over the intra-slice axis, so
        # the partitioner's decomposition is DCN-aware (the per-link
        # budget gspmd_hier in HLO_BUDGETS.json locks the shape).
        from dptpu.parallel.gspmd import (
            dp_specs,
            gspmd_specs_for_arch,
            make_gspmd_train_step,
            shard_gspmd_state,
            tp_specs_for_arch,
        )

        if tp:
            rule, specs = tp_specs_for_arch(arch, state.params)
            notices.append(
                f"=> tensor parallelism: {rule} over model axis of "
                f"{plan.mesh_axes[MODEL_AXIS]} × data axis of "
                f"{plan.mesh_axes[DATA_AXIS]}"
            )
        elif hier:
            rule = "gspmd_fsdp"
            specs = gspmd_specs_for_arch(arch, state.params, mesh, fsdp=True)
            notices.append("=> GSPMD hierarchical data parallelism: "
                           "rules-table FSDP placement over the intra-slice "
                           "axis; the partitioner derives the per-link "
                           "collective decomposition")
            if plan.dcn_dtype != "fp32":
                notices.append(
                    f"=> DPTPU_DCN_DTYPE={plan.dcn_dtype} ignored: the "
                    f"GSPMD partitioner schedules its own DCN "
                    f"collectives (the compressed hop is "
                    f"shard_map-only)")
        else:
            rule, specs = "dp_specs", dp_specs(state.params)
            notices.append(
                "=> GSPMD single-program data parallelism (dp_specs)")
        train_step = make_gspmd_train_step(
            mesh, state, specs, compute_dtype, lr_schedule=schedule,
            seed=seed, accum_steps=accum_steps,
            label_smoothing=label_smoothing,
            overlap=plan.overlap, bucket_bytes=plan.bucket_bytes,
        )
        state = shard_gspmd_state(state, mesh, specs)
        if rule == "dp_specs":
            eval_view, eval_view_gathers = as_is, False
        else:
            # sharded params: one all-gather per validation pass /
            # checkpoint write (the ZeRO-1 discipline) so the replicated-
            # spec eval step and the checkpoint writer see full leaves
            eval_view, eval_view_gathers = gathered, True
    elif plan.family == "seq":
        # sequence-parallel step: token axis over the inner seq axis,
        # batch over data. Params stay replicated (no sharded state, no
        # gather needed) — the SAME TrainState trains here and evals
        # through the standard replicated eval step below. The step's
        # model is a second ViT instance with the seq flags on; its
        # param tree is identical (the flags add no params).
        from dptpu.models import create_model
        from dptpu.parallel.sequence import make_seq_train_step

        seq_model = create_model(
            arch,
            num_classes=num_classes,
            dtype=compute_dtype,
            seq_axis_name=SEQ_AXIS,
            seq_mode=plan.sp_mode,
            seq_shard_tokens=True,
        )
        train_step = make_seq_train_step(
            mesh, seq_model, compute_dtype, lr_schedule=schedule,
            label_smoothing=label_smoothing,
        )
        eval_view, eval_view_gathers = as_is, False
        notices.append(
            f"=> sequence parallelism: {plan.sp_mode} attention over seq "
            f"axis of {plan.mesh_axes[SEQ_AXIS]} × data axis of "
            f"{plan.mesh_axes[DATA_AXIS]} "
            f"(tokens pad to multiples of {plan.mesh_axes[SEQ_AXIS]}; "
            f"cls psum-recovered)"
        )
    else:
        def rebuild(sched):
            return make_train_step(
                mesh, compute_dtype, lr_schedule=sched, task=task,
                **shard_map_kw,
            )

        train_step = rebuild(schedule)
        eval_view, eval_view_gathers = as_is, False
        if jax.process_count() == 1:
            setup_phase("state_commit")
            # commit the state to where the step will leave it (this
            # device, or replicated over the mesh): the uncommitted
            # state of the first call and the committed one the step
            # returns are different jit cache keys, and the second would
            # compile the whole step again at step 1 (chip run, PR 21:
            # 44.8 s then 21.6 s for ResNet-50). One process only: a
            # host-local state is not placed on a mesh that spans hosts.
            state = (put(state) if mesh is None
                     else jax.device_put(state, replicated_sharding(mesh)))
            setup_phase("step_build")
    return Built(
        train_step=train_step,
        eval_step=make_eval_step(mesh, compute_dtype, task=task),
        state=state,
        eval_view=eval_view,
        eval_view_gathers=eval_view_gathers,
        opt_shard_bytes=opt_shard_bytes,
        rebuild=rebuild,
        notices=tuple(notices),
    )
