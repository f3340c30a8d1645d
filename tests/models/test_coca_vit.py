"""ViT + CoCa smoke/shape/gradient tests (reference tests/models coca & vision suites)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from modalities_tpu.loss_functions import NCELoss
from modalities_tpu.models.coca.coca_model import CoCa, TextDecoderConfig
from modalities_tpu.models.vision_transformer.vision_transformer_model import (
    VisionTransformer,
    VisionTransformerConfig,
)


def tiny_vit(n_classes=10):
    return VisionTransformer(
        sample_key="images",
        prediction_key="logits",
        img_size=32,
        n_classes=n_classes,
        n_layer=2,
        n_head=4,
        n_embd=64,
        dropout=0.0,
        patch_size=8,
        patch_stride=8,
        add_cls_token=True,
        bias=True,
    )


def tiny_coca():
    return CoCa(
        prediction_key="logits",
        vision_cls_prediction_key="vision_cls",
        text_cls_prediction_key="text_cls",
        vision_embd_prediction_key="vision_embeddings",
        text_embd_prediction_key="text_embeddings",
        n_vision_queries=4,
        n_pool_head=2,
        bias_attn_pool=False,
        epsilon_attn_pool=1e-5,
        vision_encoder_config=VisionTransformerConfig(
            sample_key="images",
            prediction_key="vision_embeddings",
            img_size=32,
            n_classes=None,
            n_layer=2,
            n_head=2,
            n_embd=64,
            dropout=0.0,
            patch_size=8,
            patch_stride=8,
            add_cls_token=False,
            bias=True,
        ),
        text_decoder_config=TextDecoderConfig(
            sample_key="input_ids",
            prediction_key="logits",
            block_size=16,
            vocab_size=128,
            n_layer_text=2,
            n_layer_multimodal_text=2,
            n_head=2,
            n_embd=64,
            ffn_hidden=128,
            dropout=0.0,
            bias=True,
        ),
    )


def test_vit_classification_shapes():
    model = tiny_vit()
    params = model.init_params(jax.random.PRNGKey(0))
    images = jnp.zeros((2, 32, 32, 3))
    out = model.apply(params, {"images": images})
    assert out["logits"].shape == (2, 10)
    assert model.block_size == 17  # 4x4 patches + cls


def test_vit_encoder_mode_shapes():
    model = tiny_vit(n_classes=None)
    params = model.init_params(jax.random.PRNGKey(0))
    out = model.apply(params, {"images": jnp.zeros((2, 32, 32, 3))})
    assert out["logits"].shape == (2, 17, 64)


def test_coca_forward_shapes():
    model = tiny_coca()
    params = model.init_params(jax.random.PRNGKey(0))
    images = jnp.zeros((2, 32, 32, 3))
    text = jnp.zeros((2, 16), dtype=jnp.int32)
    out = model.apply(params, {"images": images, "input_ids": text})
    assert out["logits"].shape == (2, 16, 128)
    assert out["vision_cls"].shape == (2, 64)
    assert out["text_cls"].shape == (2, 64)


@pytest.mark.slow  # ~21 s; coca family — test_coca_forward_shapes keeps the
# CoCa forward contract in tier-1 (grad/train machinery is pinned model-agnostically
# by tests/training/test_train_step.py::test_loss_decreases_dp)
def test_coca_trains_with_nce_plus_ce():
    """Captioning CE + contrastive NCE both produce finite grads (CoCa loss recipe)."""
    import optax

    model = tiny_coca()
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    images = jnp.asarray(rng.normal(size=(4, 32, 32, 3)), jnp.float32)
    text = jnp.asarray(rng.integers(0, 128, (4, 17)), jnp.int32)
    nce = NCELoss(prediction_key1="vision_cls", prediction_key2="text_cls", is_asymmetric=False)

    def loss_fn(p):
        out = model.apply(p, {"images": images, "input_ids": text[:, :-1]})
        ce = optax.softmax_cross_entropy_with_integer_labels(
            out["logits"].astype(jnp.float32), text[:, 1:]
        ).mean()
        return ce + nce(out, {})

    loss, grads = jax.value_and_grad(loss_fn)(params)
    assert np.isfinite(float(loss))
    norms = [float(jnp.abs(g).max()) for g in jax.tree.leaves(grads)]
    assert all(np.isfinite(n) for n in norms)
    assert any(n > 0 for n in norms)


def test_coca_collator():
    from modalities_tpu.models.coca.coca_model import CoCaCollateFn

    collate = CoCaCollateFn(
        sample_keys=["images", "input_ids"],
        target_keys=[],
        text_sample_key="input_ids",
        text_target_key="target_ids",
    )
    batch = [
        {"images": np.zeros((8, 8, 3)), "input_ids": np.arange(10)},
        {"images": np.ones((8, 8, 3)), "input_ids": np.arange(10, 20)},
    ]
    out = collate(batch)
    assert out.samples["images"].shape == (2, 8, 8, 3)
    assert out.samples["input_ids"].shape == (2, 9)
    np.testing.assert_array_equal(out.targets["target_ids"][0], np.arange(1, 10))
