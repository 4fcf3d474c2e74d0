"""The one generator of training traffic: rows of token ids.

A traffic file (``bench/traffic/<name>.json``) gives the batch, the sequence
length and the token-frequency law; this module turns it into batches.
Batch ``k`` is a pure function of (seed, k), so the program and the
reference see the same rows, and a restarted stream replays them exactly.

Token ids follow a Zipf law over the configuration's vocabulary (the file's
``zipf_exponent`` and the ``zipf_source`` it is taken from), with the ranks
shuffled by the seed so that no id is special.  The program trains on
tokens and labels alone, with no document mask, so the only thing in a row
that shapes the chip's work is how often ids repeat: the embedding
gradient's scatter-add.  Rows are ``seq_len + 1`` tokens, with no padding;
every seed gives the same shapes, so the work per step does not depend on
the seed.
"""

from __future__ import annotations

import numpy as np


class ZipfTokens:
    """Batches of Zipf-distributed token rows for one (traffic, config,
    seed)."""

    def __init__(self, traffic: dict, config: dict, seed: int):
        self.batch = int(traffic["batch"])
        self.seq_len = int(traffic["seq_len"])
        self.seed = int(seed)
        rng = np.random.default_rng(np.random.SeedSequence([self.seed, 0]))
        self.ids = rng.permutation(int(config["vocab_size"])).astype(np.int32)
        w = 1.0 / np.arange(1, len(self.ids) + 1) ** float(
            traffic["zipf_exponent"])
        self.cdf = np.cumsum(w / w.sum())

    def batch_at(self, step: int) -> dict:
        """``{"tokens", "labels"}`` int32 arrays of shape (batch, seq_len)."""
        rng = np.random.default_rng(
            np.random.SeedSequence([self.seed, 1, int(step)]))
        ranks = np.searchsorted(
            self.cdf, rng.random(self.batch * (self.seq_len + 1)),
            side="right").clip(0, len(self.ids) - 1)
        rows = self.ids[ranks].reshape(self.batch, self.seq_len + 1)
        return {"tokens": np.ascontiguousarray(rows[:, :-1]),
                "labels": np.ascontiguousarray(rows[:, 1:])}
