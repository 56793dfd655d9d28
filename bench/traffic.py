"""The one traffic generator: batches of token sequences with ScaDLES stream
rates, made from a traffic file's parameters and the run's seed.

A traffic file (``bench/traffic/<name>.json``) holds only data::

    {"rows": 2, "seq_len": 2048, "determinism": 0.8,
     "streams": {"dist": "S1", "devices": 8, "weights": "per_sample"}}

- ``rows`` x ``seq_len`` tokens per step, every row full (no padding).  Each
  row is a chain of planted bigrams: token t+1 follows the seed's bigram
  table with probability ``determinism`` and is uniform otherwise (the
  process of ``repro.data.synthetic.TokenData``, copied so that the program
  cannot move it, and drawn in bulk so that it costs the host little).
- ``streams``: ``devices`` streaming devices whose rates are drawn once per
  run from the paper's Table I distribution ``dist`` (a copy of
  ``repro.core.streams.TABLE_I`` / ``StreamDist.sample``).
  ``weights: "per_sample"`` maps every row to a random device each step and
  gives it the Eqn 4a weight r_dev / sum(r) over the batch
  (``sample_weights``, as ``repro.launch.train --scadles`` does);
  ``"per_device"`` splits the rows evenly over the devices in order and
  hands over the rates themselves (``rates``, as the DDP step takes them).

Every seed gives the same sizes; the seed changes only the values.  The same
seed gives the same batches in the same order, so the reference can draw the
first steps again.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import numpy as np

SQRT3 = 3.0 ** 0.5

# Paper Table I: (kind, mean, std) in samples/s; a floor of 12 samples/s on
# the slowest device, as in repro.core.streams.StreamDist.
TABLE_I = {
    "S1": ("uniform", 38.0, 24.0),
    "S2": ("uniform", 300.0, 112.0),
    "S1p": ("normal", 64.0, 24.0),
    "S2p": ("normal", 256.0, 28.0),
}
MIN_RATE = 12.0


def sample_rates(rng: np.random.Generator, dist: str, n: int) -> np.ndarray:
    kind, mean, std = TABLE_I[dist]
    if kind == "uniform":
        r = rng.uniform(mean - SQRT3 * std, mean + SQRT3 * std, size=n)
    else:
        r = rng.normal(mean, std, size=n)
    return np.maximum(np.round(r), MIN_RATE).astype(np.int64)


def _seed_words(seed: int, stream: int):
    """Independent numpy streams from one seed of any size."""
    return [int(seed) & 0xFFFFFFFF, (int(seed) >> 32) & 0xFFFFFFFF, stream]


@dataclasses.dataclass
class Traffic:
    """Batches for one run: ``batch(step)`` must be called for steps 0, 1,
    2, ... in order."""
    spec: dict
    vocab_size: int
    seed: int

    def __post_init__(self):
        s = self.spec
        self.rows = int(s["rows"])
        self.seq_len = int(s["seq_len"])
        self.determinism = float(s.get("determinism", 0.8))
        st = s["streams"]
        self.n_streams = int(st["devices"])
        self.weighting = st["weights"]
        if self.weighting not in ("per_sample", "per_device"):
            raise ValueError(f"unknown stream weighting {self.weighting!r}")
        if self.weighting == "per_device" and self.rows % self.n_streams:
            raise ValueError("rows must split evenly over the devices")
        table_rng = np.random.default_rng(_seed_words(self.seed, 0))
        self.table = table_rng.integers(0, self.vocab_size,
                                        size=self.vocab_size,
                                        dtype=np.int32)
        self.rates = sample_rates(np.random.default_rng(
            _seed_words(self.seed, 1)), st["dist"], self.n_streams)
        self.rng = np.random.default_rng(_seed_words(self.seed, 2))
        self.next_step = 0

    @property
    def tokens_per_step(self) -> int:
        return self.rows * self.seq_len

    def batch(self, step: int) -> Dict[str, np.ndarray]:
        if step != self.next_step:
            raise ValueError(f"batch {step} asked for, {self.next_step} due")
        self.next_step += 1
        rng, b, s = self.rng, self.rows, self.seq_len
        fresh = rng.integers(0, self.vocab_size, size=(b, s + 1),
                             dtype=np.int32)
        follow = rng.random((b, s)) < self.determinism
        # token t is table[token t-1] where it follows, else fresh; a pass
        # fixes one more token of every run of followers, so the chain is
        # done when a pass changes nothing (a run is ~5 tokens long)
        toks = fresh.copy()
        while True:
            nxt = np.where(follow, self.table[toks[:, :-1]], fresh[:, 1:])
            if np.array_equal(nxt, toks[:, 1:]):
                break
            toks[:, 1:] = nxt
        out = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
        if self.weighting == "per_sample":
            dev = rng.integers(0, self.n_streams, size=b)
            w = self.rates[dev].astype(np.float64)
            out["sample_weights"] = (w / w.sum()).astype(np.float32)
        else:
            out["rates"] = self.rates.astype(np.float32)
        return out
