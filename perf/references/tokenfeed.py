"""The data's side of `trinity_mini_ep8`: documents of seeded token ids, and
the packed rows rebuilt plainly from them.

`make` writes `<root>/documents.npz` from the seed and the mix: document
lengths log-normal (`doc_median`, `doc_sigma`), cut at `doc_longest`; ids by
Zipf's law (`zipf_exponent`) over ranks 1 .. `id_rows` - 1, rank r the id r;
every document's last id 0, the end of a document; `order_seed`, the seed of
the order the data set packs them in. The program's data set lays them end to
end in that order and cuts the stream into rows of `row_len` tokens, a row's
tail filled by the head of the next document: no padding, no mask between
documents. `numbers` does the same plainly, the whole stream at once, and
looks each row the loader served up in it. It imports nothing of the program.

  feed_token_gap  rows of the batches that are no row of the rebuilt stream
                  (or hold an id outside the rows held); 0 where the loader
                  served the packing
"""

import os

import numpy as np

FILE = "documents.npz"


def make(root, seed, mix):
    os.makedirs(root, exist_ok=True)
    rng = np.random.RandomState(seed % (2**32))
    want = int(mix["n_rows"]) * int(mix["row_len"])
    lengths = []
    while sum(lengths) < want:
        draw = np.exp(rng.normal(np.log(mix["doc_median"]), mix["doc_sigma"], 256))
        lengths.extend(int(v) for v in np.clip(np.rint(draw), 2, mix["doc_longest"]))
    ends = np.cumsum(lengths)
    lengths = np.asarray(lengths[: int(np.searchsorted(ends, want)) + 1], np.int64)
    ranks = np.arange(1, int(mix["id_rows"]), dtype=np.float64)
    p = ranks ** -float(mix["zipf_exponent"])
    ids = 1 + rng.choice(len(ranks), size=int(lengths.sum()), p=p / p.sum()).astype(np.int32)
    ids[np.cumsum(lengths) - 1] = 0
    np.savez(os.path.join(root, FILE), ids=ids, lengths=lengths, order_seed=np.int64(seed % (2**32)))
    return {
        "seed": seed, "documents": int(len(lengths)), "tokens": int(lengths.sum()),
        "row_len": int(mix["row_len"]), "median_document": float(np.median(lengths)),
    }


def overrides(root):
    return {"data.dataset": "tokens", "data.root_dir": root}


def batch_spec(sizes, batch):
    return {"tokens": ((batch, int(sizes["data.seq_len"])), np.int32)}


def notes(record):
    return {
        "documents": record["documents"], "median_document_tokens": record["median_document"],
        "tokens_per_sample": record["row_len"],
        "sample": "one packed row: the cell's rate in samples a second times tokens_per_sample is tokens a second",
    }


def rebuilt_rows(root, row_len):
    """Every row of the packing, [n_rows, row_len], the plain way: the
    documents in their seeded order joined into one stream, cut into rows."""
    with np.load(os.path.join(root, FILE)) as f:
        ids, lengths, order_seed = f["ids"], f["lengths"], int(f["order_seed"])
    documents = np.split(ids, np.cumsum(lengths)[:-1])
    order = np.random.RandomState(order_seed).permutation(len(documents))
    stream = np.concatenate([documents[i] for i in order])
    n = len(stream) // row_len
    return stream[: n * row_len].reshape(n, row_len)


def numbers(root, batches, sizes, in_place=None):
    """`in_place` puts a fault in the loader's place, for the controls: any
    value shifts every served row by one token."""
    row_len, id_rows = int(sizes["data.seq_len"]), int(sizes["lm.vocab_rows"])
    known = {row.tobytes() for row in rebuilt_rows(root, row_len).astype(np.int32)}
    strange, at = 0, ""
    for b, batch in enumerate(batches):
        for r, row in enumerate(np.asarray(batch["tokens"])):
            row = np.roll(row, 1) if in_place else row
            ok = row.astype(np.int32).tobytes() in known and 0 <= row.min() and row.max() < id_rows
            if not ok:
                strange, at = strange + 1, at or f"batch {b} row {r}"
    return {"feed_token_gap": {"value": float(strange), "at": at}}
