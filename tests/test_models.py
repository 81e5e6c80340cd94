"""Model zoo structural parity: parameter counts must equal torchvision's
(the reference's model source, imagenet_ddp.py:108-114), output shapes must
be [batch, num_classes], and BN state must exist exactly where expected."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dptpu.models import create_model, model_names

# Exact torchvision parameter counts (weights + biases + BN affine;
# excluding BN running stats, which live in a separate collection here
# just as they are non-Parameter buffers in torch).
TORCHVISION_PARAM_COUNTS = {
    "resnet18": 11_689_512,
    "resnet34": 21_797_672,
    "resnet50": 25_557_032,
    "resnet101": 44_549_160,
    "resnet152": 60_192_808,
    "alexnet": 61_100_840,
    "vgg11": 132_863_336,
    "vgg11_bn": 132_868_840,
    "vgg13": 133_047_848,
    "vgg13_bn": 133_053_736,
    "vgg16": 138_357_544,
    "vgg16_bn": 138_365_992,
    "vgg19": 143_667_240,
    "vgg19_bn": 143_678_248,
    "densenet121": 7_978_856,
    "densenet161": 28_681_000,
    "densenet169": 14_149_480,
    "densenet201": 20_013_928,
    "squeezenet1_0": 1_248_424,
    "squeezenet1_1": 1_235_496,
    "wide_resnet50_2": 68_883_240,
    "wide_resnet101_2": 126_886_696,
    "resnext50_32x4d": 25_028_904,
    "resnext101_32x8d": 88_791_336,
    "mobilenet_v2": 3_504_872,
    "shufflenet_v2_x0_5": 1_366_792,
    "shufflenet_v2_x1_0": 2_278_604,
    "mnasnet0_5": 2_218_512,
    "mnasnet1_0": 4_383_312,
    "shufflenet_v2_x1_5": 3_503_624,
    "shufflenet_v2_x2_0": 7_393_996,
    "mnasnet0_75": 3_170_208,
    "mnasnet1_3": 6_282_256,
    "mobilenet_v3_large": 5_483_032,
    "mobilenet_v3_small": 2_542_856,
    "googlenet": 6_624_904,
    "efficientnet_b0": 5_288_548,
    "efficientnet_b1": 7_794_184,
    "efficientnet_b2": 9_109_994,
    "efficientnet_b3": 12_233_232,
    "efficientnet_b4": 19_341_616,
    "efficientnet_b5": 30_389_784,
    "efficientnet_b6": 43_040_704,
    "efficientnet_b7": 66_347_960,
    "efficientnet_v2_s": 21_458_488,
    "efficientnet_v2_m": 54_139_356,
    "efficientnet_v2_l": 118_515_272,
    "regnet_x_400mf": 5_495_976,
    "regnet_x_800mf": 7_259_656,
    "regnet_x_1_6gf": 9_190_136,
    "regnet_x_3_2gf": 15_296_552,
    "regnet_x_8gf": 39_572_648,
    "regnet_x_16gf": 54_278_536,
    "regnet_x_32gf": 107_811_560,
    "regnet_y_400mf": 4_344_144,
    "regnet_y_800mf": 6_432_512,
    "regnet_y_1_6gf": 11_202_430,
    "regnet_y_3_2gf": 19_436_338,
    "regnet_y_8gf": 39_381_472,
    "regnet_y_16gf": 83_590_140,
    "regnet_y_32gf": 145_046_770,
    "regnet_y_128gf": 644_812_894,
    "maxvit_t": 30_919_624,  # image-size independent, needs 224-style grid
    "swin_t": 28_288_354,
    "swin_s": 49_606_258,
    "swin_b": 87_768_224,
    "swin_v2_t": 28_351_570,
    "swin_v2_s": 49_737_442,
    "swin_v2_b": 87_930_848,
    "convnext_tiny": 28_589_128,
    "convnext_small": 50_223_688,
    "convnext_base": 88_591_464,
    "convnext_large": 197_767_336,
    # ViT counts are image-size dependent (pos embedding); locked at 224
    "vit_b_16": 86_567_656,
    "vit_b_32": 88_224_232,
    "vit_l_16": 304_326_632,
    "vit_l_32": 306_535_400,
    "vit_h_14": 632_045_800,
}


def _init(name, image=64):
    model = create_model(name)
    variables = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, image, image, 3), jnp.float32)
    )
    return model, variables


def _count(tree):
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))


def _param_count(name, image=64):
    """Parameter count via jax.eval_shape — exact (counts need shapes
    only) and ~100x faster than materializing a 100M-param init on CPU."""
    model = create_model(name)
    shapes = jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        jnp.zeros((1, image, image, 3), jnp.float32),
    )
    return _count(shapes["params"])


@pytest.mark.parametrize("name", sorted(TORCHVISION_PARAM_COUNTS))
def test_param_counts_match_torchvision(name):
    image = (224 if name.startswith(("alexnet", "vgg", "squeezenet", "vit",
                                     "maxvit"))
             else 64)
    assert _param_count(name, image) == TORCHVISION_PARAM_COUNTS[name]


@pytest.mark.parametrize("name,image", [
    ("vgg11_bn", 224), ("mnasnet0_5", 64), ("resnext50_32x4d", 64),
    ("wide_resnet50_2", 64), ("alexnet", 224), ("mobilenet_v3_small", 64),
    ("efficientnet_b0", 64), ("efficientnet_v2_s", 64),
    ("regnet_y_400mf", 64), ("regnet_x_400mf", 64), ("vit_b_32", 64),
    ("convnext_tiny", 64), ("swin_t", 64), ("swin_v2_t", 64),
])
def test_family_concrete_init_and_forward(name, image):
    """One CONCRETE init+forward per family not covered elsewhere:
    eval_shape-based count tests never execute initializers, so a
    value-level init bug (NaN std, concrete-only dtype path) needs this."""
    m = create_model(name, num_classes=5)
    v = m.init(jax.random.PRNGKey(0), jnp.zeros((1, image, image, 3)))
    out = m.apply(v, jnp.zeros((2, image, image, 3)), train=False)
    assert out.shape == (2, 5)
    assert np.isfinite(np.asarray(out)).all()


def test_maxvit_rejects_bad_grid():
    m = create_model("maxvit_t", num_classes=3)
    with pytest.raises(ValueError, match="divisible"):
        jax.eval_shape(
            m.init, jax.random.PRNGKey(0), jnp.zeros((1, 64, 64, 3))
        )


def test_maxvit_forward():
    m = create_model("maxvit_t", num_classes=3)
    v = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 224, 224, 3)))
    out = m.apply(v, jnp.ones((1, 224, 224, 3)), train=False)
    assert out.shape == (1, 3)
    assert np.isfinite(np.asarray(out)).all()


def test_swin_static_helpers():
    from dptpu.models.swin import (
        _coords_table,
        _relative_position_index,
        _shift_mask,
    )

    idx = _relative_position_index(7)
    assert idx.shape == (49, 49) and idx.min() == 0 and idx.max() == 168
    # every self-pair maps to the center of the (2w-1)^2 table
    assert (np.diag(idx) == 6 * 13 + 6).all()
    m = _shift_mask(21, 21, 7, 3, 3)
    assert m.shape == (9, 49, 49)
    assert (m[0] == 0).all()  # interior window: no masking
    assert (m == np.transpose(m, (0, 2, 1))).all()  # pair symmetry
    assert (m[-1] != 0).any()  # corner window crosses regions
    t = _coords_table(8)
    # torchvision normalizes to sign(x)*log2(|8x|+1)/3: max = log2(9)/3
    assert t.shape == (225, 2)
    np.testing.assert_allclose(np.abs(t).max(), np.log2(9.0) / 3, rtol=1e-6)


def test_shufflenet_forward_and_channel_shuffle():
    from dptpu.models.shufflenet import channel_shuffle

    x = jnp.arange(8.0).reshape(1, 1, 1, 8)
    # groups=2: [0..3 | 4..7] interleaves to [0,4,1,5,2,6,3,7]
    np.testing.assert_array_equal(
        np.asarray(channel_shuffle(x)).ravel(), [0, 4, 1, 5, 2, 6, 3, 7]
    )
    m = create_model("shufflenet_v2_x0_5", num_classes=6)
    v = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 64, 64, 3)))
    out = m.apply(v, jnp.zeros((2, 64, 64, 3)), train=False)
    assert out.shape == (2, 6)


def test_mobilenet_v2_param_count_and_forward():
    m = create_model("mobilenet_v2", num_classes=9)
    v = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 64, 64, 3)))
    out = m.apply(v, jnp.zeros((2, 64, 64, 3)), train=False)
    assert out.shape == (2, 9)


def test_squeezenet_ceil_mode_pool_shapes():
    """torchvision squeezenet1_0 feature map is 13x13 at 224 input; the
    ceil-mode pools are what make the 54 -> 27 -> 13 chain work."""
    m = create_model("squeezenet1_0", num_classes=10)
    v = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 224, 224, 3)))
    out = m.apply(v, jnp.zeros((2, 224, 224, 3)), train=False)
    assert out.shape == (2, 10)


def test_densenet_forward_and_bn_state():
    m = create_model("densenet121", num_classes=5)
    v = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 64, 64, 3)))
    assert "batch_stats" in v  # DenseNet is BN-heavy
    out, mutated = m.apply(
        v, jnp.ones((2, 64, 64, 3)), train=True, mutable=["batch_stats"]
    )
    assert out.shape == (2, 5)
    assert np.isfinite(np.asarray(out)).all()


def test_googlenet_inception_aux_param_counts():
    """torchvision's documented inception_v3 count (27,161,264) includes
    the aux head (its default constructor carries it); googlenet's
    documented 6,624,904 excludes aux. Lock both aux trees."""
    import jax as _jax

    def count(name, **kw):
        m = create_model(name, **kw)
        image = 299 if name == "inception_v3" else 64
        shapes = _jax.eval_shape(
            lambda r, x: m.init(r, x), jax.random.PRNGKey(0),
            jnp.zeros((1, image, image, 3)),
        )
        return _count(shapes["params"])

    assert count("inception_v3", aux_logits=True) == 27_161_264
    assert count("inception_v3") == 23_834_568  # minus the aux head
    assert count("googlenet", aux_logits=True) == 13_004_888


def test_googlenet_inception_forward():
    for name, image in (("googlenet", 64), ("inception_v3", 299)):
        m = create_model(name, num_classes=4)
        v = m.init(jax.random.PRNGKey(0), jnp.zeros((1, image, image, 3)))
        out = m.apply(v, jnp.zeros((2, image, image, 3)), train=False)
        assert out.shape == (2, 4)
        assert np.isfinite(np.asarray(out)).all()


def test_registry_surface():
    names = model_names()
    assert names == sorted(names)
    for required in ("resnet18", "resnet50", "resnet152", "alexnet", "vgg16",
                     "densenet121", "densenet201", "squeezenet1_0",
                     "squeezenet1_1"):
        assert required in names


def test_pretrained_without_weights_fails_fast(monkeypatch, tmp_path):
    # no converted weights anywhere -> actionable error naming the converter
    monkeypatch.setenv("DPTPU_PRETRAINED_DIR", str(tmp_path))
    with pytest.raises(FileNotFoundError, match="convert_torchvision"):
        create_model("resnet50", pretrained=True)


def test_resnet_forward_shapes_and_finite():
    model, variables = _init("resnet18")
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 64, 3))
    logits = model.apply(variables, x)
    assert logits.shape == (2, 1000)
    assert bool(jnp.isfinite(logits).all())


def test_resnet_train_mode_updates_batch_stats():
    model, variables = _init("resnet18")
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 64, 64, 3)) + 3.0
    _, mutated = model.apply(variables, x, train=True, mutable=["batch_stats"])
    before = jax.tree_util.tree_leaves(variables["batch_stats"])
    after = jax.tree_util.tree_leaves(mutated["batch_stats"])
    assert any(not np.allclose(b, a) for b, a in zip(before, after))


def test_num_classes_override():
    model = create_model("resnet18", num_classes=10)
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 64, 64, 3)))
    logits = model.apply(variables, jnp.zeros((2, 64, 64, 3)))
    assert logits.shape == (2, 10)


def test_bf16_compute_dtype_keeps_fp32_params():
    model = create_model("resnet18", dtype=jnp.bfloat16)
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 64, 64, 3)))
    kernels = jax.tree_util.tree_leaves(variables["params"])
    assert all(k.dtype == jnp.float32 for k in kernels)
    logits = model.apply(variables, jnp.zeros((2, 64, 64, 3), jnp.bfloat16))
    assert logits.dtype == jnp.bfloat16


def test_dropout_models_need_rng_in_train():
    model, variables = _init("alexnet", image=224)
    x = jnp.zeros((2, 224, 224, 3))
    out = model.apply(
        variables, x, train=True, rngs={"dropout": jax.random.PRNGKey(3)}
    )
    assert out.shape == (2, 1000)
