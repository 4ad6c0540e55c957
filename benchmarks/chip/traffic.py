"""The one traffic generator: turns a mix's parameter file and a seed into
requests and arrival times.

A mix (``traffic/<name>.json``) states its loop (``closed``: a fixed number
of clients, each sending its next request when the last one completes),
the engine rows and ``max_len`` it needs, and its prompt and output length
distributions: ``uniform`` over ``lo``..``hi``, or ``lognormal``
(``median``, ``sigma``), rounded and clipped to ``lo``..``hi``.  A mix may
state ``requests``, a whole number of blocks: the window then submits
exactly that many, whatever its length in seconds.

Every seed gets the same multiset of (prompt, output) length pairs, so
two seeds differ in order and token ids, not in the amount of work.
Lengths come in blocks of ``block`` requests: each block holds the prompt
and the output distributions' quantiles at ``(i + 0.5) / block``, paired
by one permutation that no seed changes, and the seed shuffles the pairs
within the block.  A mix that states ``requests`` shuffles them the same
way for every seed: the order of lengths decides which requests share a
dispatch, and so how many dispatches the window takes, and a counted
window exists to give every seed the same work.  Such a cell measures one
schedule.  Token ids are uniform over the vocabulary, drawn per request
from ``(seed, request index)``.
"""
from __future__ import annotations

import json
import statistics
from dataclasses import dataclass
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent


def load_mix(name: str, root: Path = HERE) -> dict:
    path = root / "traffic" / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    mix = json.loads(path.read_text())
    if mix.get("loop") != "closed":
        raise ValueError(f"traffic mix {name!r}: the only loop is closed")
    n = mix.get("requests")
    if n is not None and (n < 1 or n % mix["block"]):
        raise ValueError(f"traffic mix {name!r}: requests must be a whole "
                         f"number of blocks of {mix['block']}")
    return mix


def quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths: the distribution's quantiles at (i + 0.5) / n,
    rounded and clipped to [lo, hi]."""
    p = (np.arange(n) + 0.5) / n
    if dist["dist"] == "uniform":
        # integers lo..hi inclusive, equally likely
        raw = dist["lo"] + p * (dist["hi"] - dist["lo"] + 1) - 0.5
    elif dist["dist"] == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(x) for x in p])
        raw = dist["median"] * np.exp(dist["sigma"] * z)
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(raw), dist["lo"], dist["hi"]).astype(np.int64)


@dataclass(frozen=True)
class Request:
    index: int
    prompt: np.ndarray      # (plen,) int32
    max_new: int


class Traffic:
    """Requests of one mix under one seed, made on demand by index."""

    def __init__(self, mix: dict, vocab: int, seed: int) -> None:
        self.mix = mix
        self.vocab = int(vocab)
        self.seed = int(seed)
        self.block = int(mix["block"])
        self._plens = quantiles(mix["prompt"], self.block)
        # the same pairs of lengths for every seed
        self._outs = quantiles(mix["output"], self.block)[
            np.random.default_rng(0).permutation(self.block)]
        # a counted window serves one order of lengths for every seed
        self._order_seed = 0 if "requests" in mix else self.seed
        self._orders: dict[int, np.ndarray] = {}

    def _order(self, blk: int) -> np.ndarray:
        if blk not in self._orders:
            rng = np.random.default_rng([self._order_seed, 0, blk])
            self._orders[blk] = rng.permutation(self.block)
        return self._orders[blk]

    def lengths(self, i: int) -> tuple[int, int]:
        blk, j = divmod(i, self.block)
        k = self._order(blk)[j]
        return int(self._plens[k]), int(self._outs[k])

    def request(self, i: int) -> Request:
        plen, out = self.lengths(i)
        rng = np.random.default_rng([self.seed, 1, i])
        prompt = rng.integers(0, self.vocab, plen, dtype=np.int32)
        return Request(i, prompt, out)
