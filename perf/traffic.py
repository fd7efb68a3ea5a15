"""The one traffic generator. A mix is a data file of parameters; the
configuration's data module (its `feed_reference`) makes the run's data set
from the seed and the mix, and this hands the window loop an iterator of
what `Trainer.train_one_batch` takes.

Mix parameters (`mixes/<name>.json`):
  feed            "loader": the trainer's own loader over the data set,
                  epoch after epoch (whatever it does on the host, staged
                  by the trainer);
                  "staged": `staged_batches` batches drawn from the same
                  loader, put on the device once during set-up the way the
                  trainer stages them, and cycled.
  overrides       dotted program config keys this traffic sets.
  the rest        the data set's own, read by the data module's `make`.
"""

from __future__ import annotations

from typing import Any, Dict, Iterator, List

import numpy as np


def epochs(loader) -> Iterator[Dict[str, np.ndarray]]:
    """The trainer's loader, epoch after epoch, as `Trainer.train` walks it."""
    epoch = 0
    while True:
        loader.set_epoch(epoch)
        yield from loader
        epoch += 1


class Feed:
    """What the window loop draws from: `next(feed)` gives the keyword
    arguments of one `train_one_batch` call. `first_host_batches` are the
    host copies of the first batches, kept for the reference."""

    def __init__(self, trainer, mix: Dict[str, Any], keep: int) -> None:
        self.kind = mix["feed"]
        self._it = epochs(trainer.loader)
        self.first_host_batches: List[Dict[str, np.ndarray]] = []
        self._keep = keep
        self._staged: List[Dict[str, Any]] = []
        self._i = 0
        if self.kind == "staged":
            import jax

            host = [next(self._it) for _ in range(int(mix["staged_batches"]))]
            self.first_host_batches = host[:keep]
            self._staged = [trainer._stage_batch(b, wait=True) for b in host]
            jax.block_until_ready(self._staged)
            self.close()
        elif self.kind != "loader":
            raise ValueError(f"unknown feed {self.kind!r}")

    def __iter__(self):
        return self

    def __next__(self) -> Dict[str, Any]:
        if self.kind == "staged":
            batch = self._staged[self._i % len(self._staged)]
            self._i += 1
            return {"staged": batch}
        batch = next(self._it)
        if len(self.first_host_batches) < self._keep:
            self.first_host_batches.append(batch)
        return {"batch": batch}

    def close(self) -> None:
        """Stop the loader's producer thread and its pool."""
        it, self._it = self._it, iter(())
        close = getattr(it, "close", None)
        if close is not None:
            close()

    def free(self) -> None:
        self._staged = []
