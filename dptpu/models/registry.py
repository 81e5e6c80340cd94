"""Model registry with torchvision discovery semantics.

The reference discovers architectures as "any lowercase, non-dunder,
callable name in ``torchvision.models.__dict__``" (imagenet_ddp.py:19-21)
and instantiates with ``models.__dict__[args.arch]()``
(imagenet_ddp.py:111-114). This registry reproduces that contract for the
in-tree Flax zoo: ``model_names()`` feeds the CLI ``choices`` and
``create_model(name)`` is the ``models.__dict__[arch]()`` analog.
"""

from jax.sharding import PartitionSpec as P

from dptpu.parallel.rules import AUTO_FSDP

_REGISTRY = {}

# --------------------------------------------------------------------------
# Partition rules: ONE declaration per family covers DP x TP x FSDP.
#
# Each table is an ordered (regex, spec) list over the FULL {data, model}
# axis vocabulary, resolved by dptpu/parallel/rules.py
# ``match_partition_rules`` (first match wins against the "/"-joined param
# path; the mandatory ``.*`` fallback closes every table). Consumers
# PROJECT the one table onto their mesh: keep ``model`` and you get the
# Megatron TP placement (the specs tests/test_gspmd.py locks, and what
# serve uses); keep ``data`` and you get the ZeRO-3/FSDP layout; keep both
# and one declaration yields the combined DPxTPxFSDP placement. The
# ``(^|/)`` anchors pin whole path segments — ``proj`` must not claim
# ``out_proj`` — reproducing the old per-module name checks exactly.
#
# Grammar per rule:
#   P("data", "model")     kernel: dim0 FSDP-sharded, dim1 column-parallel
#   P("model", "data")     kernel: dim0 row-parallel, dim1 FSDP-sharded
#   P(("data", "model"))   bias of a column-parallel layer: its one dim
#                          carries both axes (TP projection -> P("model"),
#                          FSDP projection -> P("data"))
#   P("data")              bias of a row-parallel layer: TP-replicated
#   AUTO_FSDP              everything else: largest evenly-divisible dim
#                          over ``data`` (mesh.largest_divisible_dim),
#                          replicated under pure TP
#
# Family notes (the WHY lives with the old spec functions' docstrings,
# now in dptpu/parallel/gspmd.py consumer docs): ViT and Swin fused-qkv
# kernels are stored head-major, so the contiguous column split is
# head-aligned; Swin v1's relative-position-bias table and v2's
# logit_scale/cpb_mlp_2 shard on their heads dim (the variant-specific
# rows are dead on the OTHER variant by construction — the check rule
# aggregates liveness across the family, not per model); ConvNeXt only
# TPs its pointwise MLP pair; classic CNNs and MaxViT take the pure
# AUTO_FSDP table (conv TP is deliberately not shipped — see
# gspmd.dp_specs).

VIT_RULES = (
    (r"(^|/)(in_proj|mlp_1)/kernel$", P("data", "model")),
    (r"(^|/)(in_proj|mlp_1)/bias$", P(("data", "model"))),
    (r"(^|/)(out_proj|mlp_2)/kernel$", P("model", "data")),
    (r"(^|/)(out_proj|mlp_2)/bias$", P("data")),
    (r".*", AUTO_FSDP),
)

SWIN_RULES = (
    (r"(^|/)(qkv|cpb_mlp_2|mlp_1)/kernel$", P("data", "model")),
    (r"(^|/)(qkv|cpb_mlp_2|mlp_1)/bias$", P(("data", "model"))),
    (r"(^|/)(proj|mlp_2)/kernel$", P("model", "data")),
    (r"(^|/)(proj|mlp_2)/bias$", P("data")),
    (r"(^|/)logit_scale$", P("model")),
    (r"(^|/)relative_position_bias_table$", P("data", "model")),
    (r".*", AUTO_FSDP),
)

CONVNEXT_RULES = (
    (r"(^|/)mlp_1/kernel$", P("data", "model")),
    (r"(^|/)mlp_1/bias$", P(("data", "model"))),
    (r"(^|/)mlp_2/kernel$", P("model", "data")),
    (r"(^|/)mlp_2/bias$", P("data")),
    (r".*", AUTO_FSDP),
)

GENERIC_RULES = ((r".*", AUTO_FSDP),)

FAMILY_RULES = {
    "vit": VIT_RULES,
    "swin": SWIN_RULES,
    "convnext": CONVNEXT_RULES,
    "generic": GENERIC_RULES,
}


def partition_family(arch: str) -> str:
    """Family key for an arch name — arch-name-only (no params needed)
    so ``fit()`` can pick mesh geometry BEFORE model construction, the
    same early-decision contract ``gspmd.tp_rule_for_arch`` keeps.

    ``DPTPU_RULES=<family>`` overrides the name-derived family for EVERY
    placement consumer at once (ZeRO-3, GSPMD, serve TP) — the escape
    hatch for an arch whose name doesn't encode its structure (a custom
    registry entry with ViT-shaped blocks can opt into the vit table
    instead of the generic AUTO_FSDP fallback). Fail-fast contract: an
    unknown family raises naming the valid choices."""
    from dptpu.envknob import env_choice

    override = env_choice("DPTPU_RULES", tuple(sorted(FAMILY_RULES)), None)
    if override is not None:
        return override
    if arch.startswith("vit_"):
        return "vit"
    if arch.startswith("swin"):
        return "swin"
    if arch.startswith("convnext"):
        return "convnext"
    return "generic"


def partition_rules_for_arch(arch: str):
    """THE sharding declaration for an arch: its family's ordered rules
    table. Every placement consumer (ZeRO-3 state layout, GSPMD/pjit
    shardings, serve TP) projects this one table."""
    return FAMILY_RULES[partition_family(arch)]


def register_model(fn):
    """Decorator: register a lowercase factory under its function name."""
    name = fn.__name__
    assert name.islower() and not name.startswith("__")
    _REGISTRY[name] = fn
    return fn


def register_variants(model_cls, prefix, variants, field="variant"):
    """Register ``{prefix}_{v}`` factories for a config-parameterized
    model class (EfficientNet/RegNet/ViT-style variant tables)."""
    for v in variants:
        def fn(_v=v, **kw):
            return model_cls(**{field: _v}, **kw)

        fn.__name__ = f"{prefix}_{v}"
        register_model(fn)


def model_names():
    """Sorted architecture names (imagenet_ddp.py:19-21 semantics)."""
    return sorted(_REGISTRY)


def model_task(name) -> str:
    """What ``fit()`` trains ``name`` on: ``"images"`` (rows of one image
    and one label; every torchvision architecture) or ``"tokens"`` (rows
    of token ids with next-token labels and a loss mask). A factory says
    so by a ``task`` attribute; the data source, the step's loss and the
    arguments the factory is handed follow from it."""
    if name not in _REGISTRY:
        raise KeyError(f"unknown architecture {name!r}; choices: {model_names()}")
    return getattr(_REGISTRY[name], "task", "images")


def model_key_map(name):
    """The function that gives ``name``'s checkpoint names
    (``variables -> {torch_key: (collection, path, kind)}``) where the
    model owns them (a factory's ``torch_key_map``; the token models do),
    else None: ``dptpu.models.pretrained`` then maps ``name`` by the
    torchvision tables."""
    return getattr(_REGISTRY.get(name), "torch_key_map", None)


def token_model_kwargs(cfg, task: str) -> dict:
    """The arguments a token-sequence model's factory takes from the
    command line (``--seq-len``, ``--layers``, ``--experts``,
    ``--vocab-rows``); an image model is handed none of them and a run
    that gives it one fails here, before anything is built."""
    given = {"sequence_length": cfg.seq_len or None,
             "layers": cfg.layers or None, "experts": cfg.experts or None,
             "vocab": cfg.vocab_rows or None}
    given = {k: v for k, v in given.items() if v is not None}
    if task != "tokens" and given:
        raise ValueError(
            f"--seq-len/--layers/--experts/--vocab-rows are a "
            f"token-sequence model's arguments, and '{cfg.arch}' is "
            f"trained on images"
        )
    return given


def create_model(name, pretrained=False, **kwargs):
    """``models.__dict__[arch](pretrained=...)`` analog (imagenet_ddp.py:108-114).

    With ``pretrained=True`` the converted-weights file for ``name`` must
    exist (``$DPTPU_PRETRAINED_DIR`` or ``./pretrained``); this validates
    it up front so the CLI fails fast with conversion instructions. The
    weights themselves are applied at init time via
    ``dptpu.models.pretrained.load_pretrained_variables`` (flax modules
    are stateless, so construction cannot carry them the way torch does).
    """
    if name not in _REGISTRY:
        raise KeyError(f"unknown architecture {name!r}; choices: {model_names()}")
    if pretrained:
        from dptpu.models.pretrained import require_weights

        require_weights(name)
    return _REGISTRY[name](**kwargs)
