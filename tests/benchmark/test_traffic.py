"""The traffic generator: the same seed gives the same traffic, another seed the same
amount of work in another order, and a seed past 2**31 is a seed like any other."""

import importlib.util
import json
import pickle
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
BIG = 2**31 + 12345


def generator(name):
    spec = importlib.util.spec_from_file_location(name, REPO / "benchmark" / "traffic" / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def mix(name):
    return json.loads((REPO / "benchmark" / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("seed", [3, BIG])
def test_packed_documents_writes_the_container_the_program_reads(tmp_path, seed):
    gen = generator("packed_documents")
    params = {**mix("packed-4k"), "sequences": 16}
    written = gen.generate(params, seed, tmp_path / "a.pbin", vocab_size=50304, sequence_length=128)
    gen.generate(params, seed, tmp_path / "b.pbin", vocab_size=50304, sequence_length=128)
    gen.generate(params, seed + 1, tmp_path / "c.pbin", vocab_size=50304, sequence_length=128)
    a, b, c = ((tmp_path / f"{n}.pbin").read_bytes() for n in "abc")
    assert a == b and a != c and written["tokens"] == 16 * 128 + 1
    data_len, token_bytes = int.from_bytes(a[:8], "little"), int.from_bytes(a[8:12], "little")
    assert token_bytes == 2 and data_len == 2 * written["tokens"]
    tokens = np.frombuffer(a[12 : 12 + data_len], dtype="<u2")
    index = pickle.loads(a[12 + data_len :])
    assert sum(n for _, n in index) == data_len and index[0][0] == 0
    ends = [(o + n) // 2 - 1 for o, n in index]
    assert (tokens[ends] == 50303).all() and (np.delete(tokens, ends) < 50303).all()
    lengths = lambda raw: sorted(n for _, n in pickle.loads(raw[12 + data_len :]))  # noqa: E731
    assert lengths(a) == lengths(c), "every seed packs the same set of document lengths"

    from modalities_tpu.dataloader.dataset_factory import DatasetFactory

    dataset = DatasetFactory.get_packed_mem_map_dataset_continuous(
        raw_data_path=tmp_path / "a.pbin", sequence_length=128, sample_key="input_ids", reuse_last_target=True)
    assert len(dataset) == 16
    np.testing.assert_array_equal(np.asarray(dataset[1]["input_ids"]), tokens[128 : 128 + 129])
