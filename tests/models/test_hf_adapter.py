"""HF adapter save/load + forward parity."""

import jax
import numpy as np
import pytest
import torch

from modalities_tpu.models.huggingface_adapters.hf_adapter import HFModelAdapter
from tests.models.test_gpt2_model import tiny_gpt2


def test_adapter_roundtrip(tmp_path):
    from flax.core import meta

    model = tiny_gpt2("pytorch_flash")
    params = meta.unbox(model.init_params(jax.random.PRNGKey(3)))
    adapter = HFModelAdapter(model, params)
    adapter.save_pretrained(tmp_path / "export", verify=True)
    reloaded = HFModelAdapter.from_pretrained(tmp_path / "export")
    tokens = np.arange(16, dtype=np.int64).reshape(1, 16) % 128
    jax_logits = np.asarray(adapter(tokens.astype(np.int32)).logits)
    with torch.no_grad():
        torch_logits = reloaded(torch.from_numpy(tokens)).logits.float().numpy()
    np.testing.assert_allclose(jax_logits, torch_logits, rtol=2e-2, atol=2e-2)
