"""Pretrained-weight loading: torchvision state dicts -> dptpu variables.

The reference exposes ``--pretrained`` by constructing
``models.__dict__[arch](pretrained=True)`` (imagenet_ddp.py:30-31,109-111),
which downloads torchvision weights. This environment has no network, so
dptpu splits the feature into two halves:

* an **offline converter** (``python -m dptpu.tools.convert_torchvision``)
  that reads a torchvision checkpoint (``.pth`` via torch's CPU unpickler,
  or an ``.npz`` of numpy arrays keyed by torch names) and writes
  ``<dir>/<arch>.npz`` in dptpu's native layout;
* a **runtime loader** with zero torch dependency: ``--pretrained`` finds
  ``<arch>.npz`` under ``$DPTPU_PRETRAINED_DIR`` (default ``./pretrained``)
  and initializes the train state from it.

Key mapping covers every in-tree family. dptpu module names intentionally
mirror torchvision's (``features_3`` <-> ``features.3``,
``layer1_block0`` <-> ``layer1.0``), so the map is mechanical:

=========== ==========================  =============================
collection  dptpu leaf                  torch leaf
=========== ==========================  =============================
params      ``kernel`` (conv, HWIO)     ``weight`` (OIHW, transposed)
params      ``kernel`` (dense, IO)      ``weight`` (OI, transposed)
params      ``scale`` (BN)              ``weight``
params      ``bias``                    ``bias``
batch_stats ``mean`` / ``var``          ``running_mean`` / ``running_var``
=========== ==========================  =============================

``num_batches_tracked`` buffers are dropped (dptpu's schedules are pure
functions of the global step).

One transpose subtlety: a Linear that consumes a *flattened conv map*
(alexnet/vgg first classifier, googlenet aux fc1) sees CHW-ordered inputs
in torch but HWC-ordered inputs here, so its kernel needs a spatial
permutation, not just the OI->IO transpose — handled by the
``dense_chw`` kinds below (shapes alone would silently match).

Fidelity evidence (``scripts/check_tv_parity.py``, committed as
TV_PARITY.json): the conversion round-trips at LOGIT level exactly —
dptpu params -> torch layout (``_to_torch``) -> back through
``convert_state_dict`` -> forward gives ``max|Δlogit| = 0.0`` for
resnet50, vit_b_16 and swin_t (every permute/transpose kind inverts
bit-exactly), and the val pipeline is pixel-exact to torchvision's
``Resize(256)→CenterCrop(224)`` (±1 LSB; dptpu/data/transforms.py).
Run the harness where torch+torchvision exist for the published-weight
cross-framework ``max|Δlogit|`` / top-1-agreement numbers per arch.
"""

from __future__ import annotations

import os
from typing import Dict, Tuple

import jax
import numpy as np

from dptpu.models.registry import model_key_map

_LEAF_TO_TORCH = {
    "kernel": "weight",
    "scale": "weight",
    "bias": "bias",
    "mean": "running_mean",
    "var": "running_var",
}

# torchvision squeezenet Sequential indices of fire modules, per version
_SQUEEZE_FIRE_IDX = {
    "1_0": {2: 3, 3: 4, 4: 5, 5: 7, 6: 8, 7: 9, 8: 10, 9: 12},
    "1_1": {2: 3, 3: 4, 4: 6, 5: 7, 6: 9, 7: 10, 8: 11, 9: 12},
}


def _vit_torch_module(mod: Tuple[str, ...]) -> str:
    """ViT paths. torch: conv_proj, raw class_token /
    encoder.pos_embedding Parameters, encoder.layers.encoder_layer_{i}
    with ln_1 / self_attention (raw fused in_proj_weight + out_proj
    Linear) / ln_2 / mlp (Sequential: Linears at 0 and 3), encoder.ln,
    heads.head. "{}"-bearing returns are formatted with the torch leaf
    name by torch_key_map (raw-Parameter keys have no ".weight" suffix).
    """
    if not mod:
        return "{}"  # class_token
    if mod[0] in ("conv_proj", "head"):
        return {"conv_proj": "conv_proj", "head": "heads.head"}[mod[0]]
    if len(mod) == 1:
        return "encoder.{}"  # pos_embedding
    if mod[1] == "ln":
        return "encoder.ln"
    base = f"encoder.layers.{mod[1]}"
    sub = mod[2]
    if sub == "self_attention":
        if mod[3] == "in_proj":
            return f"{base}.self_attention.in_proj_{{}}"
        return f"{base}.self_attention.out_proj"
    m = {"ln_1": "ln_1", "ln_2": "ln_2", "mlp_1": "mlp.0", "mlp_2": "mlp.3"}
    return f"{base}.{m[sub]}"


def _torch_module(arch: str, mod: Tuple[str, ...]) -> str:
    """Map a dptpu module path (tuple of names) to the torch module path."""
    if arch.startswith("vit_"):
        return _vit_torch_module(mod)
    head = mod[0]
    if arch.startswith(("resnet", "wide_resnet", "resnext")):
        if head.startswith("layer"):
            layer, block = head.split("_block")
            sub = {"downsample_conv": "downsample.0",
                   "downsample_bn": "downsample.1"}.get(mod[1], mod[1])
            return f"{layer}.{block}.{sub}"
        return head  # conv1 / bn1 / fc
    if arch == "alexnet" or arch.startswith("vgg"):
        prefix, idx = head.rsplit("_", 1)
        return f"{prefix}.{idx}"
    if arch.startswith("densenet"):
        if head in ("conv0", "norm0", "norm5"):
            return f"features.{head}"
        if head.startswith("denseblock"):
            block, layer = head.split("_layer")
            return f"features.{block}.denselayer{layer}.{mod[1]}"
        if head.startswith("transition"):
            return f"features.{head}.{mod[1]}"
        return head  # classifier
    if arch.startswith("mobilenet_v3"):
        from dptpu.models.mobilenet_v3 import _LARGE, _SMALL

        table = _LARGE if arch.endswith("large") else _SMALL
        if head == "stem_conv":
            return "features.0.0"
        if head == "stem_bn":
            return "features.0.1"
        if head == "head_conv":
            return f"features.{len(table) + 1}.0"
        if head == "head_bn":
            return f"features.{len(table) + 1}.1"
        if head == "pre_classifier":
            return "classifier.0"
        if head == "classifier":
            return "classifier.3"
        # blocks: torch wraps each stage in a .block Sequential whose
        # indices depend on whether expand and SE exist
        k = int(head[5:])
        kernel, expanded, out, use_se, act, stride = table[k]
        inp = 16 if k == 0 else table[k - 1][2]
        has_expand = expanded != inp
        d = 1 if has_expand else 0  # depthwise position
        se_pos, proj = d + 1, d + 1 + (1 if use_se else 0)
        sub = mod[1]
        m = {"expand": "block.0.0", "expand_bn": "block.0.1",
             "dw": f"block.{d}.0", "dw_bn": f"block.{d}.1",
             "project": f"block.{proj}.0", "project_bn": f"block.{proj}.1"}
        if sub == "se":
            return f"features.{k + 1}.block.{se_pos}.{mod[2]}"
        return f"features.{k + 1}.{m[sub]}"
    if arch == "mobilenet_v2":
        # torchvision Sequential: features.0 stem ConvBNReLU, features.1..17
        # inverted residuals, features.18 head, classifier.1 Linear
        if head == "stem_conv":
            return "features.0.0"
        if head == "stem_bn":
            return "features.0.1"
        if head == "head_conv":
            return "features.18.0"
        if head == "head_bn":
            return "features.18.1"
        if head.startswith("block"):
            k = int(head[5:])
            kind, i = mod[1].split("_")
            i = int(i)
            expand = k != 0  # only the first block runs expand_ratio 1
            if expand:
                sub = {("conv", 0): "conv.0.0", ("bn", 0): "conv.0.1",
                       ("conv", 1): "conv.1.0", ("bn", 1): "conv.1.1",
                       ("conv", 2): "conv.2", ("bn", 2): "conv.3"}[(kind, i)]
            else:
                sub = {("conv", 0): "conv.0.0", ("bn", 0): "conv.0.1",
                       ("conv", 1): "conv.1", ("bn", 1): "conv.2"}[(kind, i)]
            return f"features.{k + 1}.{sub}"
        return "classifier.1"
    if arch == "googlenet":
        # plain dotted join, with torchvision's branchN Sequential indices
        # (branch2_1 -> branch2.1); aux1/aux2 and conv1..3 join directly
        out = ".".join(mod)
        for b in ("branch2", "branch3", "branch4"):
            out = out.replace(f"{b}_", f"{b}.")
        return out
    if arch == "inception_v3":
        return ".".join(mod)  # names mirror torchvision module paths
    if arch.startswith("shufflenet_v2"):
        # torch: conv1/conv5 are Sequential(conv, bn); units are
        # stage{s}.{i} with branch1 = (dw, bn, pw, bn) and branch2 =
        # (pw, bn, relu, dw, bn, pw, bn, relu)
        if head in ("conv1", "conv5"):
            return f"{head}.0"
        if head in ("conv1_bn", "conv5_bn"):
            return f"{head[:5]}.1"
        if head == "fc":
            return "fc"
        stage, unit = head.split("_unit")
        sub = {"branch1_dw": "branch1.0", "branch1_dw_bn": "branch1.1",
               "branch1_pw": "branch1.2", "branch1_pw_bn": "branch1.3",
               "branch2_pw1": "branch2.0", "branch2_pw1_bn": "branch2.1",
               "branch2_dw": "branch2.3", "branch2_dw_bn": "branch2.4",
               "branch2_pw2": "branch2.5", "branch2_pw2_bn": "branch2.6"}[mod[1]]
        return f"{stage}.{unit}.{sub}"
    if arch.startswith("mnasnet"):
        # torch: one flat `layers` Sequential — 0/1 stem conv+bn, 3/4 sep
        # dw+bn, 6/7 sep pw+bn, 8..13 the six stacks of inverted residuals
        # (each block a Sequential named `layers` again), 14/15 head
        flat = {"stem_conv": "layers.0", "stem_bn": "layers.1",
                "sep_dw": "layers.3", "sep_dw_bn": "layers.4",
                "sep_pw": "layers.6", "sep_pw_bn": "layers.7",
                "head_conv": "layers.14", "head_bn": "layers.15",
                "classifier": "classifier.1"}
        if head in flat:
            return flat[head]
        k = int(head[5:])  # block index -> (stack, index-in-stack)
        repeats = (3, 3, 3, 2, 4, 1)
        stack = 0
        while k >= repeats[stack]:
            k -= repeats[stack]
            stack += 1
        sub = {"pw1": "layers.0", "pw1_bn": "layers.1",
               "dw": "layers.3", "dw_bn": "layers.4",
               "pw2": "layers.6", "pw2_bn": "layers.7"}[mod[1]]
        return f"layers.{8 + stack}.{k}.{sub}"
    if arch.startswith("squeezenet"):
        version = arch.split("squeezenet")[1]
        if head == "conv1":
            return "features.0"
        if head.startswith("fire"):
            idx = _SQUEEZE_FIRE_IDX[version][int(head[4:])]
            return f"features.{idx}.{mod[1]}"
        return "classifier.1"  # final_conv
    if arch.startswith("efficientnet"):
        # torch: features.0 stem, features.{s+1}.{i}.block.* stages (the
        # block Sequential's indices depend on expand/kind), features.{S+1}
        # head, classifier.1 Linear
        from dptpu.models.efficientnet import block_table

        stages = block_table(arch[len("efficientnet_"):])
        flat = {"stem_conv": "features.0.0", "stem_bn": "features.0.1",
                "head_conv": f"features.{len(stages) + 1}.0",
                "head_bn": f"features.{len(stages) + 1}.1",
                "classifier": "classifier.1"}
        if head in flat:
            return flat[head]
        si, bi = (int(x) for x in head[len("stage"):].split("_block"))
        kind, e, _, _, _, _ = stages[si][bi]
        sub = mod[1]
        if kind == "fused":
            m = {"fused": "block.0.0", "fused_bn": "block.0.1",
                 "project": "block.1.0", "project_bn": "block.1.1"}
            return f"features.{si + 1}.{bi}.{m[sub]}"
        d = 1 if e != 1 else 0  # depthwise position after optional expand
        if sub == "se":
            return f"features.{si + 1}.{bi}.block.{d + 1}.{mod[2]}"
        m = {"expand": "block.0.0", "expand_bn": "block.0.1",
             "dw": f"block.{d}.0", "dw_bn": f"block.{d}.1",
             "project": f"block.{d + 2}.0", "project_bn": f"block.{d + 2}.1"}
        return f"features.{si + 1}.{bi}.{m[sub]}"
    if arch.startswith("convnext"):
        # torch: features.0 stem (conv, LayerNorm2d), stages at odd
        # features indices with .block Sequential (dw conv 0, LN 2,
        # Linears 3/5) + raw layer_scale, downsamples (LN, conv) at even
        # indices, classifier (LN, Flatten, Linear)
        flat = {"stem_conv": "features.0.0", "stem_norm": "features.0.1",
                "head_norm": "classifier.0", "head": "classifier.2"}
        if head in flat:
            return flat[head]
        if head.startswith("downsample"):
            si = int(head[len("downsample"):head.index("_")])
            return f"features.{2 * si}.{0 if head.endswith('_norm') else 1}"
        si, bi = (int(v) for v in head[len("stage"):].split("_block"))
        base = f"features.{2 * si + 1}.{bi}"
        if len(mod) == 1:
            return base + ".{}"  # raw layer_scale Parameter
        m = {"dw": "block.0", "norm": "block.2",
             "mlp_1": "block.3", "mlp_2": "block.5"}
        return f"{base}.{m[mod[1]]}"
    if arch.startswith("swin"):
        # torch: features.0 patch embed (conv 0, Permute 1, LN 2),
        # stages at odd indices (norm1/norm2, attn with qkv/proj Linears
        # + raw relative_position_bias_table / logit_scale + cpb_mlp
        # Sequential, mlp Linears at 0/3), PatchMerging at even indices,
        # final norm + head
        flat = {"patch_conv": "features.0.0", "patch_norm": "features.0.2",
                "norm": "norm", "head": "head"}
        if head in flat:
            return flat[head]
        if head.startswith("merge"):
            si = int(head[len("merge"):])
            return f"features.{2 * si + 2}.{mod[1]}"
        si, bi = (int(v) for v in head[len("stage"):].split("_block"))
        base = f"features.{2 * si + 1}.{bi}"
        sub = mod[1]
        if sub == "attn":
            if len(mod) == 2:
                return f"{base}.attn.{{}}"  # raw rpb table / logit_scale
            m = {"qkv": "qkv", "proj": "proj",
                 "cpb_mlp_1": "cpb_mlp.0", "cpb_mlp_2": "cpb_mlp.2"}
            return f"{base}.attn.{m[mod[2]]}"
        m = {"norm1": "norm1", "norm2": "norm2",
             "mlp_1": "mlp.0", "mlp_2": "mlp.3"}
        return f"{base}.{m[sub]}"
    if arch == "maxvit_t":
        # torch: stem (two Conv2dNormActivations), blocks.{b}.layers.{l}
        # .layers with MBconv (nested .layers OrderedDict + .proj
        # shortcut) / window_attention / grid_attention (attn_layer 0=LN
        # 1=RelativePositionalMultiHeadAttention, mlp_layer Sequential),
        # classifier (pool, flatten, LN, Linear, Tanh, Linear)
        flat = {"stem_conv": "stem.0.0", "stem_bn": "stem.0.1",
                "stem_conv2": "stem.1.0", "head_norm": "classifier.2",
                "pre_head": "classifier.3", "head": "classifier.5"}
        if head in flat:
            return flat[head]
        b, l = head[len("block"):].split("_layer")
        base = f"blocks.{b}.layers.{l}.layers"
        sub = mod[1]
        if sub == "mbconv":
            mb = f"{base}.MBconv"
            if mod[2] == "proj":
                return f"{mb}.proj.1"  # avg-pool at proj.0 (stride 2)
            if mod[2] == "se":
                return f"{mb}.layers.squeeze_excitation.{mod[3]}"
            m = {"pre_norm": "layers.pre_norm",
                 "conv_a": "layers.conv_a.0", "conv_a_bn": "layers.conv_a.1",
                 "conv_b": "layers.conv_b.0", "conv_b_bn": "layers.conv_b.1",
                 "conv_c": "layers.conv_c"}
            return f"{mb}.{m[mod[2]]}"
        part = {"window_attn": "window_attention",
                "grid_attn": "grid_attention"}[sub]
        if len(mod) == 2:
            return f"{base}.{part}.attn_layer.1.{{}}"  # raw rpb table
        m = {"attn_norm": "attn_layer.0", "to_qkv": "attn_layer.1.to_qkv",
             "merge": "attn_layer.1.merge", "mlp_norm": "mlp_layer.0",
             "mlp_1": "mlp_layer.1", "mlp_2": "mlp_layer.3"}
        return f"{base}.{part}.{m[mod[2]]}"
    if arch.startswith("regnet"):
        # torch: stem Conv2dNormActivation, trunk_output.block{s+1} stages
        # of blocks named "block{s+1}-{i}", BottleneckTransform under .f
        # with a/b/se/c members, head Linear at fc
        flat = {"stem_conv": "stem.0", "stem_bn": "stem.1", "fc": "fc"}
        if head in flat:
            return flat[head]
        si, bi = (int(x) for x in head[len("stage"):].split("_block"))
        base = f"trunk_output.block{si + 1}.block{si + 1}-{bi}"
        sub = mod[1]
        if sub == "se":
            return f"{base}.f.se.{mod[2]}"
        m = {"proj": "proj.0", "proj_bn": "proj.1",
             "a": "f.a.0", "a_bn": "f.a.1", "b": "f.b.0", "b_bn": "f.b.1",
             "c": "f.c.0", "c_bn": "f.c.1"}
        return f"{base}.{m[sub]}"
    raise ValueError(f"no torchvision key mapping for arch {arch!r}")


def torch_key_map(arch: str, variables) -> Dict[str, Tuple[str, Tuple[str, ...], str]]:
    """``{torch_key: (collection, dptpu_path, kind)}`` for every leaf.

    ``kind`` is ``conv`` (4-D kernel, needs OIHW->HWIO), ``dense`` (2-D
    kernel, needs OI->IO) or ``direct``.
    """
    own = model_key_map(arch)
    if own is not None:  # a model that owns its checkpoint's names
        return own(variables)
    out = {}
    for collection in ("params", "batch_stats"):
        tree = variables.get(collection, {})
        flat = jax.tree_util.tree_flatten_with_path(tree)[0]
        for path, leaf in flat:
            names = tuple(p.key for p in path)
            tmod = _torch_module(arch, names[:-1])
            if "{}" in tmod:
                # raw torch Parameters (ViT class_token / pos_embedding)
                # keep their own leaf name inside the "{}" template; all
                # other leaves stay on the strict whitelist
                tleaf = _LEAF_TO_TORCH.get(names[-1], names[-1])
            else:
                tleaf = _LEAF_TO_TORCH[names[-1]]
            if len(names) >= 2 and (
                (arch.startswith("vit_") and names[-2] == "in_proj")
                or (arch.startswith("swin") and names[-2] == "qkv")
            ):
                # fused qkv: torch stores [q|k|v]-major, dptpu stores
                # head-major (vit.py SelfAttention / swin.py _QKVDense
                # docstrings) — the converter permutes in addition to
                # the OI->IO transpose. Kind tag is "vit_qkv" for
                # historical reasons; it covers swin too.
                kind = ("vit_qkv", _qkv_heads(arch, names), names[-1])
            elif names[-1] == "kernel":
                if leaf.ndim == 4:
                    kind = "conv"
                else:
                    chw = _DENSE_CHW.get((arch.split("_bn")[0].rstrip("0123456789"), names[:-1])) \
                        or _DENSE_CHW.get((arch, names[:-1]))
                    kind = ("dense_chw", chw) if chw else "dense"
            elif names[-1] == "layer_scale":
                kind = "layer_scale"  # torch (C,1,1) <-> NHWC (C,)
            else:
                kind = "direct"
            key = tmod.format(tleaf) if "{}" in tmod else f"{tmod}.{tleaf}"
            assert key not in out, f"duplicate torch key {key}"
            out[key] = (collection, names, kind)
    return out


# Linears that consume a FLATTENED conv map: (family-or-arch, module path)
# -> the (C, H, W) the torch weight's input axis factorizes as. Flax
# flattens those maps HWC, torch flattens CHW, so these kernels need a
# spatial permutation on top of the OI->IO transpose.
_DENSE_CHW = {
    ("alexnet", ("classifier_1",)): (256, 6, 6),
    ("vgg", ("classifier_0",)): (512, 7, 7),
    ("googlenet", ("aux1", "fc1")): (128, 4, 4),
    ("googlenet", ("aux2", "fc1")): (128, 4, 4),
}


def _from_torch(arr: np.ndarray, kind) -> np.ndarray:
    arr = np.asarray(arr)
    if kind == "conv":
        return np.transpose(arr, (2, 3, 1, 0))  # OIHW -> HWIO
    if kind == "dense":
        return np.transpose(arr, (1, 0))  # OI -> IO
    if isinstance(kind, tuple) and kind[0] == "dense_chw":
        c, h, w = kind[1]
        o = arr.shape[0]
        # torch (O, C*H*W) -> flax (H*W*C, O): reorder the input axis to
        # the NHWC flatten order before transposing
        return np.transpose(
            arr.reshape(o, c, h, w), (2, 3, 1, 0)
        ).reshape(h * w * c, o)
    if kind == "layer_scale":
        return arr.reshape(-1)  # torch (C,1,1) -> NHWC (C,)
    if kind == "conv1d_dw":
        return np.transpose(arr[:, 0, :], (1, 0))  # (C,1,L) -> (L,C)
    if isinstance(kind, tuple) and kind[0] == "vit_qkv":
        _, heads, leaf = kind
        if leaf == "kernel":
            arr = np.transpose(arr, (1, 0))  # (3h, h) -> (h, 3h) [q|k|v]
        return qkv_permute(arr, heads, to_head_major=True)
    return arr


def _to_torch(arr: np.ndarray, kind) -> np.ndarray:
    arr = np.asarray(arr)
    if kind == "conv":
        return np.transpose(arr, (3, 2, 0, 1))  # HWIO -> OIHW
    if kind == "dense":
        return np.transpose(arr, (1, 0))
    if isinstance(kind, tuple) and kind[0] == "dense_chw":
        c, h, w = kind[1]
        o = arr.shape[-1]
        return np.transpose(
            arr.reshape(h, w, c, o), (3, 2, 0, 1)
        ).reshape(o, c * h * w)
    if kind == "layer_scale":
        return arr.reshape(-1, 1, 1)  # NHWC (C,) -> torch (C,1,1)
    if kind == "conv1d_dw":
        return np.transpose(arr, (1, 0))[:, None, :]  # (L,C) -> (C,1,L)
    if isinstance(kind, tuple) and kind[0] == "vit_qkv":
        _, heads, leaf = kind
        arr = qkv_permute(arr, heads, to_head_major=False)
        if leaf == "kernel":
            return np.transpose(arr, (1, 0))
        return arr
    return arr


def convert_state_dict(arch: str, state_dict: Dict[str, np.ndarray],
                       template_variables, kmap=None):
    """torch-keyed arrays -> dptpu ``{"params", "batch_stats"}`` variables.

    ``template_variables`` (from ``model.init``) fixes the tree structure
    and validates shapes. Raises on missing or mismatched keys so a wrong
    checkpoint fails loudly rather than half-loading. ``kmap`` accepts a
    precomputed ``torch_key_map(arch, template_variables)`` so callers
    that already built one (train/checkpoint.py) skip the rebuild.
    """
    if kmap is None:
        kmap = torch_key_map(arch, template_variables)
    out = {"params": {}, "batch_stats": {}}

    def set_path(tree, names, value):
        for n in names[:-1]:
            tree = tree.setdefault(n, {})
        tree[names[-1]] = value

    missing = [k for k in kmap if k not in state_dict]
    if missing:
        raise KeyError(
            f"state dict for {arch} is missing {len(missing)} keys, e.g. "
            f"{missing[:3]}"
        )
    flat_template = {
        (c, names): leaf
        for c in ("params", "batch_stats")
        for names, leaf in (
            (tuple(p.key for p in path), leaf)
            for path, leaf in jax.tree_util.tree_flatten_with_path(
                template_variables.get(c, {}))[0]
        )
    }
    for key, (collection, names, kind) in kmap.items():
        arr = _from_torch(state_dict[key], kind).astype(np.float32)
        want = flat_template[(collection, names)].shape
        if tuple(arr.shape) != tuple(want):
            raise ValueError(
                f"{key}: converted shape {arr.shape} != expected {want}"
            )
        set_path(out[collection], names, arr)
    return out


# ---------------------------------------------------------------------------
# npz round trip + runtime resolution
# ---------------------------------------------------------------------------

# Layout versioning: fused-qkv columns are stored HEAD-MAJOR (see
# dptpu/models/vit.py SelfAttention / dptpu/models/swin.py _QKVDense).
# npz files and flax checkpoints record the layout marker; files whose
# marker predates a family's head-major switch are [q|k|v]-major for
# that family and get migrated on load. Same shapes either way, so the
# marker is the ONLY way to tell them apart. History: "head_major"
# covered ViT only (early round 4 — swin was still [q|k|v]-major under
# that marker); "head_major2" covers ViT + Swin.
QKV_LAYOUT = "head_major2"
# markers under which a family's qkv leaves are ALREADY head-major
_HEAD_MAJOR_MARKERS = {
    "vit_": ("head_major", "head_major2"),
    "swin": ("head_major2",),
}


def qkv_needs_migration(arch: str, marker) -> bool:
    """True when an artifact with layout ``marker`` (None/"" = unmarked,
    pre-round-4) stores ``arch``'s fused qkv in [q|k|v]-major order and
    must be permuted to head-major on load."""
    for prefix, ok in _HEAD_MAJOR_MARKERS.items():
        if arch.startswith(prefix):
            return marker not in ok
    return False


def _qkv_heads(arch: str, names) -> int:
    """Head count of the fused-qkv leaf at tree path ``names`` — fixed
    per arch for ViT, per STAGE for Swin (the stage index is parsed from
    the ``stage{si}_block{bi}`` path element)."""
    if arch.startswith("vit_"):
        from dptpu.models.vit import _VARIANTS

        return _VARIANTS[arch[len("vit_"):]][2]
    from dptpu.models.swin import _VARIANTS

    stage = next(n for n in names if str(n).startswith("stage"))
    si = int(str(stage)[len("stage"):].split("_block")[0])
    return _VARIANTS[arch[len("swin_"):]][2][si]


def qkv_permute(arr: np.ndarray, heads: int, *, to_head_major: bool):
    """The ONE definition of the qkv column permutation, used by the
    torch converters and the legacy-layout migrations alike.

    The fused projection's output axis (size 3h) factors as
    ``(3, heads, hd)`` in [q|k|v]-major order and ``(heads, 3, hd)`` in
    head-major order; this swaps the two leading factors in whichever
    direction is asked. Works on the kernel's last axis (h, 3h) and the
    bias (3h,)."""
    lead = arr.shape[:-1]
    n3h = arr.shape[-1]
    h = n3h // 3
    a, b = ((3, heads) if to_head_major else (heads, 3))
    ndim = len(lead)
    perm = tuple(range(ndim)) + (ndim + 1, ndim, ndim + 2)
    return arr.reshape(lead + (a, b, h // heads)).transpose(perm).reshape(
        lead + (n3h,)
    )


def save_npz(path: str, variables) -> None:
    flat = {"__meta__/qkv_layout": np.asarray(QKV_LAYOUT)}
    for collection in ("params", "batch_stats"):
        for p, leaf in jax.tree_util.tree_flatten_with_path(
                variables.get(collection, {}))[0]:
            key = collection + "/" + "/".join(k.key for k in p)
            flat[key] = np.asarray(leaf)
    np.savez(path, **flat)


def load_npz(path: str):
    out = {"params": {}, "batch_stats": {}}
    with np.load(path) as data:
        for key in data.files:
            collection, *names = key.split("/")
            if collection == "__meta__":
                continue  # layout markers — read via npz_meta
            tree = out[collection]
            for n in names[:-1]:
                tree = tree.setdefault(n, {})
            tree[names[-1]] = data[key]
    return out


def npz_meta(path: str) -> Dict[str, str]:
    """The ``__meta__/*`` markers of a converted-weights file (empty for
    files written before markers existed)."""
    out = {}
    with np.load(path) as data:
        for key in data.files:
            if key.startswith("__meta__/"):
                out[key[len("__meta__/"):]] = str(data[key])
    return out


def _qkv_to_head_major(arch: str, variables):
    """Migrate a [q|k|v]-major ViT/Swin tree (pre-round-4 conversion) to
    the head-major storage layout. Works on any dict tree whose fused
    qkv leaves sit at ``…/in_proj/{kernel,bias}`` (ViT) or
    ``…/qkv/{kernel,bias}`` (Swin) — the variables dict, a bare params
    tree, or a momentum trace mirroring params."""

    def fix(path, leaf):
        names = tuple(p.key for p in path)
        if len(names) >= 2 and names[-2] in ("in_proj", "qkv"):
            return qkv_permute(
                np.asarray(leaf), _qkv_heads(arch, names),
                to_head_major=True,
            )
        return leaf

    return jax.tree_util.tree_map_with_path(fix, variables)


def weights_search_dirs():
    from dptpu.envknob import env_str

    env = env_str("DPTPU_PRETRAINED_DIR")
    return [env] if env else ["pretrained", "."]


def find_weights(arch: str):
    """Resolve ``<arch>.npz``; None if absent."""
    for d in weights_search_dirs():
        p = os.path.join(d, f"{arch}.npz")
        if os.path.exists(p):
            return p
    return None


def require_weights(arch: str) -> str:
    """``find_weights`` or raise the one canonical instructions error."""
    path = find_weights(arch)
    if path is None:
        raise FileNotFoundError(
            f"--pretrained: no converted weights found for {arch!r} "
            f"(searched {weights_search_dirs()} for {arch}.npz). Convert a "
            f"torchvision checkpoint offline with: python -m "
            f"dptpu.tools.convert_torchvision <ckpt.pth> -a {arch} -o "
            f"pretrained/  (set DPTPU_PRETRAINED_DIR to use another "
            f"directory)"
        )
    return path


def load_pretrained_variables(arch: str, model, input_shape=(1, 224, 224, 3),
                              input_dtype=np.float32):
    """Load converted weights for ``arch`` and validate against ``model``.

    The pytree structure must match the model's own ``init`` exactly
    (num_classes mismatches surface as shape errors here, matching
    torchvision's strict load semantics).
    """
    path = require_weights(arch)
    loaded = load_npz(path)
    if qkv_needs_migration(arch, npz_meta(path).get("qkv_layout")):
        # converted before this family's head-major qkv switch: same
        # shapes, permuted columns — migrate silently-correctly
        loaded = _qkv_to_head_major(arch, loaded)
    # shapes only: nothing is initialised to be thrown away (a
    # half-billion-parameter template would be 2 GB of it)
    template = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), np.zeros(input_shape, input_dtype), train=False
    ))
    t_struct = jax.tree_util.tree_structure(
        {"params": template["params"],
         "batch_stats": template.get("batch_stats", {})}
    )
    l_struct = jax.tree_util.tree_structure(loaded)
    if t_struct != l_struct:
        raise ValueError(
            f"{path} does not match the {arch} parameter tree "
            f"(wrong arch or stale conversion?)"
        )
    for (pa, a), (pb, b) in zip(
        jax.tree_util.tree_flatten_with_path(loaded)[0],
        jax.tree_util.tree_flatten_with_path(
            {"params": template["params"],
             "batch_stats": template.get("batch_stats", {})})[0],
    ):
        if tuple(a.shape) != tuple(b.shape):
            name = "/".join(str(k.key) for k in pa)
            raise ValueError(
                f"{path}: {name} has shape {a.shape}, model wants {b.shape} "
                f"(num_classes mismatch?)"
            )
    return loaded
