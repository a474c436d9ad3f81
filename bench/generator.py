"""The one traffic generator: turns a traffic file and a seed into requests.

A traffic file (``bench/traffic/<name>.json``) holds parameters only:

* ``loop``: ``"closed"`` (``clients`` callers, each sends its next request
  once its previous one has finished) or ``"open"`` (arrivals at
  ``rate_per_s``, Poisson, whether or not the server keeps up);
* ``prompt_tokens``: ``values`` with ``weights`` (a categorical mix);
* ``new_tokens``: ``{"loguniform": [lo, hi]}``;
* ``shared_prefix_tokens`` (optional): one document of that many tokens that
  every prompt starts with;
* ``block``: the size of the blocks the sequence is drawn in.

Every seed gets the same work: the sizes and the arrivals come from the
traffic file alone, and the seed draws only the token ids (and, in
``bench/weights.py``, the weights). The sequence is cut into blocks of
``block`` requests; each block holds exactly ``weight * block`` prompts of
each length, the ``block`` evenly spaced quantiles of the log-uniform
answer length, and (open loop) the ``block`` evenly spaced quantiles of the
exponential gap between arrivals, each block in its own fixed shuffled
order. So a window of a few tens of requests sees the same sizes and
arrivals on every seed, and runs on different seeds differ by their
content, not by their amount of work.
"""

from __future__ import annotations

import dataclasses
import json
import pathlib

import numpy as np

TRAFFIC_DIR = pathlib.Path(__file__).resolve().parent / "traffic"


@dataclasses.dataclass(frozen=True)
class Planned:
    """One request as the generator plans it."""

    index: int
    prompt: np.ndarray  # (prompt_len,) int32, the shared prefix included
    new_tokens: int
    gap_s: float  # open loop: time since the previous arrival


def load(name: str) -> dict:
    return json.loads((TRAFFIC_DIR / f"{name}.json").read_text())


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent numpy streams of one seed (any size of integer)."""
    return np.random.default_rng([int(seed), stream])


def block_counts(values: list[int], weights: list[float], block: int
                 ) -> list[int]:
    """Prompts of each length in one block; the weights must divide it."""
    counts = [w * block for w in weights]
    if any(abs(c - round(c)) > 1e-9 for c in counts) or \
            round(sum(counts)) != block:
        raise ValueError(f"weights {weights} do not split a block of "
                         f"{block} into whole requests")
    return [int(round(c)) for c in counts]


def quantiles(block: int) -> np.ndarray:
    return (np.arange(block) + 0.5) / block


def loguniform(lo: int, hi: int, q: np.ndarray) -> np.ndarray:
    return np.rint(lo * (hi / lo) ** q).astype(np.int64)


ORDER_STREAM = 0  # the fixed stream that shuffles every block


def sizes(traffic: dict, n_blocks: int):
    """(prompt lengths, new tokens, gaps) for ``n_blocks`` blocks."""
    block = int(traffic["block"])
    pt = traffic["prompt_tokens"]
    counts = block_counts(pt["values"], pt["weights"], block)
    base_lens = np.repeat(np.asarray(pt["values"], np.int64), counts)
    lo, hi = traffic["new_tokens"]["loguniform"]
    base_new = loguniform(lo, hi, quantiles(block))
    rate = traffic.get("rate_per_s")
    base_gap = (-np.log1p(-quantiles(block)) / rate if rate
                else np.zeros(block))
    rng = np.random.default_rng(ORDER_STREAM)
    lens, new, gaps = [], [], []
    for _ in range(n_blocks):
        lens.append(rng.permutation(base_lens))
        new.append(rng.permutation(base_new))
        gaps.append(rng.permutation(base_gap))
    return np.concatenate(lens), np.concatenate(new), np.concatenate(gaps)


def shared_prefix(traffic: dict, seed: int, vocab: int) -> np.ndarray:
    n = int(traffic.get("shared_prefix_tokens", 0))
    return rng_for(seed, 1).integers(0, vocab, size=n).astype(np.int32)


class Planner:
    """This seed's sequence of requests, made block by block on demand."""

    def __init__(self, traffic: dict, seed: int, vocab: int):
        self.traffic = traffic
        self.seed = seed
        self.vocab = vocab
        self.block = int(traffic["block"])
        self.doc = shared_prefix(traffic, seed, vocab)
        self._sizes = None

    def _grow(self, n_blocks: int) -> None:
        if self._sizes is None or len(self._sizes[0]) < n_blocks * self.block:
            self._sizes = sizes(self.traffic, n_blocks)

    def get(self, i: int) -> Planned:
        self._grow(2 * (i // self.block + 1))
        lens, new, gaps = self._sizes
        own = rng_for(self.seed, 2 + 2 * i).integers(
            0, self.vocab, size=int(lens[i])).astype(np.int32)
        return Planned(i, np.concatenate([self.doc, own]), int(new[i]),
                       float(gaps[i]))


def max_context(traffic: dict) -> int:
    """Longest prompt plus answer the traffic can send."""
    return (int(traffic.get("shared_prefix_tokens", 0))
            + max(traffic["prompt_tokens"]["values"])
            + int(traffic["new_tokens"]["loguniform"][1]))
