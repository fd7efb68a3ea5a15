"""The data's side of the toy configuration: rows of tokens in one file.

`make` writes `rows.npy`, int32 [n_rows, row_len + 1] drawn from the seed; a
sample is one row: its first `row_len` tokens, and as targets the same row
one place on. It imports nothing of the program.

  feed_row_gap  rows of the batches that are no row of the file (tokens and
                targets together); 0 where the loader served the file
"""

import os

import numpy as np


def make(root, seed, mix):
    os.makedirs(root, exist_ok=True)
    rows = np.random.RandomState(seed % (2**32)).randint(0, mix["vocab"], (mix["n_rows"], mix["row_len"] + 1))
    np.save(os.path.join(root, "rows.npy"), rows.astype(np.int32))
    return {"seed": seed, "n_rows": mix["n_rows"], "row_len": mix["row_len"]}


def overrides(root):
    return {"data.rows_file": os.path.join(root, "rows.npy")}


def batch_spec(sizes, batch):
    t = int(sizes["data.seq_len"])
    return {"tokens": ((batch, t), np.int32), "targets": ((batch, t), np.int32)}


def notes(record):
    return {"data_rows": record["n_rows"]}


def numbers(root, batches, sizes):
    known = {row.tobytes() for row in np.load(os.path.join(root, "rows.npy"))}
    strange, at = 0, ""
    for b, batch in enumerate(batches):
        for r, (tokens, targets) in enumerate(zip(batch["tokens"], batch["targets"])):
            whole = np.concatenate([tokens, targets[-1:]]).astype(np.int32)
            if whole.tobytes() not in known or not (tokens[1:] == targets[:-1]).all():
                strange, at = strange + 1, at or f"batch {b} row {r}"
    return {"feed_row_gap": {"value": float(strange), "at": at}}
