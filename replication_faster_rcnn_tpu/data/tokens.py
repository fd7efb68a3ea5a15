"""Rows of tokens for the sequence model: documents packed end to end.

A sample is one row of ``cfg.seq_len`` token ids. The documents, each ending
in the end-of-document id, are laid end to end in a seeded order and the
stream is cut into rows: a row's tail is filled by the head of the next
document, which goes on in the next row. There is no padding and nothing
marks a document's start: a row is one causal stream. The packing is done
here, a row at a time as the loader asks for it (under its ``data/build``
span), from the documents' lengths alone.

The documents come from ``<cfg.root_dir>/documents.npz`` (``ids``: every
document's ids one after another, int32; ``lengths``: their lengths;
``order_seed``: the seed of the packing order), or, with no ``root_dir``, are
drawn from a seed (`seeded_documents`): lengths log-normal, ids by Zipf's law
over the ``id_rows`` vocabulary rows held here, id 0 the end of a document.
"""

from __future__ import annotations

import os
from typing import Dict, Tuple

import numpy as np

from replication_faster_rcnn_tpu.config import DataConfig

DOCUMENTS_FILE = "documents.npz"
END_OF_DOCUMENT = 0


def seeded_documents(
    seed: int, n_tokens: int, id_rows: int, median: float, sigma: float, longest: int
) -> Tuple[np.ndarray, np.ndarray]:
    """(ids, lengths) of documents that hold ``n_tokens`` tokens or a few
    more: lengths log-normal about ``median``, cut at ``longest``; ids of rank
    r = 1..id_rows-1 with probability ~ 1/r; each document's last id 0."""
    rng = np.random.RandomState(seed % (2**32))
    lengths = []
    while sum(lengths) < n_tokens:
        draw = np.exp(rng.normal(np.log(median), sigma, 256))
        lengths.extend(np.clip(np.rint(draw), 2, longest).astype(np.int64))
    lengths = np.asarray(lengths, np.int64)
    lengths = lengths[: int(np.searchsorted(np.cumsum(lengths), n_tokens)) + 1]
    p = 1.0 / np.arange(1, id_rows, dtype=np.float64)
    ids = 1 + rng.choice(id_rows - 1, size=int(lengths.sum()), p=p / p.sum()).astype(np.int32)
    ids[np.cumsum(lengths) - 1] = END_OF_DOCUMENT
    return ids, lengths


class TokenDataset:
    """Rows of ``cfg.seq_len`` tokens cut from the packed documents."""

    def __init__(self, cfg: DataConfig, split: str = "train", id_rows: int = 256, length: int = 64) -> None:
        self.seq_len = int(cfg.seq_len)
        if cfg.root_dir:
            with np.load(os.path.join(cfg.root_dir, DOCUMENTS_FILE)) as f:
                ids, lengths, order_seed = f["ids"], f["lengths"], int(f["order_seed"])
        else:
            order_seed = {"train": 0, "val": 1 << 20, "test": 2 << 20}.get(split, 0)
            ids, lengths = seeded_documents(
                order_seed, length * self.seq_len, id_rows, max(2.0, self.seq_len / 12), 1.2, self.seq_len
            )
        self.ids = np.asarray(ids, np.int32)
        lengths = np.asarray(lengths, np.int64)
        self.order = np.random.RandomState(order_seed % (2**32)).permutation(len(lengths))
        firsts = np.concatenate([[0], np.cumsum(lengths)[:-1]])
        self.first = firsts[self.order]  # where each document of the stream starts in `ids`
        self.ends = np.cumsum(lengths[self.order])  # and where it ends in the stream
        self.length = int(self.ends[-1]) // self.seq_len

    def __len__(self) -> int:
        return self.length

    def __getitem__(self, idx: int) -> Dict[str, np.ndarray]:
        if not 0 <= idx < self.length:
            raise IndexError(idx)
        lo, hi = idx * self.seq_len, (idx + 1) * self.seq_len
        row = np.empty((self.seq_len,), np.int32)
        doc = int(np.searchsorted(self.ends, lo, side="right"))
        at = lo
        while at < hi:
            start = int(self.ends[doc - 1]) if doc else 0
            take = min(hi, int(self.ends[doc])) - at
            src = int(self.first[doc]) + at - start
            row[at - lo : at - lo + take] = self.ids[src : src + take]
            at += take
            doc += 1
        return {"tokens": row}
