"""Training traffic: a corpus of documents packed into one token stream, written from
the seed in the `.pbin` container the program's `packed_mem_map_dataset_continuous`
reads (8 bytes data length, 4 bytes token size, little-endian token ids, pickled index
of (offset, length) byte spans — the source framework's own byte format).

Documents have log-normally distributed lengths; every run of a mix writes the same
multiset of lengths (drawn from the mix's own `size_seed`), in an order and with tokens
drawn from the run's seed. Tokens are uniform over the vocabulary without the
end-of-document id, which closes each document. Every row a step sees differs.
"""

from __future__ import annotations

import pickle
from pathlib import Path

import numpy as np


def document_lengths(params: dict, total_tokens: int) -> np.ndarray:
    """Document lengths (each counts its closing end-of-document token) that sum to
    exactly `total_tokens`: the same for every seed."""
    rng = np.random.default_rng(int(params["size_seed"]))
    lengths = []
    left = total_tokens
    while left > 0:
        n = int(np.clip(rng.lognormal(np.log(params["doc_len_median"]), params["doc_len_sigma"]),
                        params["doc_len_min"], params["doc_len_max"]))
        n = min(n, left)
        lengths.append(n)
        left -= n
    return np.asarray(lengths, dtype=np.int64)


def generate(params: dict, seed: int, dst: Path, *, vocab_size: int, sequence_length: int) -> dict:
    """Write `dst` (a .pbin) holding `params["sequences"]` rows of `sequence_length` + 1
    tokens. Returns what was written, for the run's log."""
    total = int(params["sequences"]) * sequence_length + 1  # continuous rows share one token
    rng = np.random.default_rng(int(seed))
    lengths = rng.permutation(document_lengths(params, total))
    eod = vocab_size - 1
    tokens = rng.integers(0, eod, size=total, dtype=np.int64)
    ends = np.cumsum(lengths) - 1
    tokens[ends] = eod
    token_bytes = 2 if vocab_size <= 2**16 else 4
    data = tokens.astype("<u2" if token_bytes == 2 else "<u4").tobytes()
    starts = np.concatenate([[0], np.cumsum(lengths)[:-1]])
    index = [(int(s) * token_bytes, int(n) * token_bytes) for s, n in zip(starts, lengths)]
    dst = Path(dst)
    dst.parent.mkdir(parents=True, exist_ok=True)
    with open(dst, "wb") as f:
        f.write(len(data).to_bytes(8, "little"))
        f.write(token_bytes.to_bytes(4, "little"))
        f.write(data)
        f.write(pickle.dumps(index))
    return {"tokens": total, "documents": len(lengths), "bytes": len(data)}
