"""The one traffic generator: every mix is a data file it reads.

A mix fixes the work, and the seed its order and the token ids (and,
in the harness, the weights). The pool of requests is made of blocks
of `block` requests, every block the same set of (prompt, output)
length pairs:

* prompt lengths: the block's stratified quantiles of the mix's
  log-normal `prompt` law (median, sigma), each served at the nearest,
  by ratio, of the `lengths` the mix lists (the program compiles a
  prefill per prompt length and accepts only some);
* output lengths: the block's stratified quantiles of the log-normal
  `output` law, rounded and clipped to [min, max] and to the context
  budget less the prompt, paired with the prompts by one fixed
  shuffle, the same for every mix and seed. They are exact, since
  random weights give no end-of-sequence token;
* order: the seed permutes the pairs within each block, so every seed
  serves the same sizes in an order of its own, and a run of whole
  blocks the same work;
* token ids: uniform over the vocabulary, per request from the seed;
* arrivals (open loop only): each block's stratified quantiles of a
  gamma inter-arrival law with the mix's mean rate and coefficient of
  variation, permuted by the seed, summed into due times.

Closed loop: `clients` callers each send their next request the
moment their last one completed (no think time). Open loop: request k
is due at `due[k]` seconds after the clients start, whether or not the
server keeps up. Either way a request is timed from when it was due.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np
from scipy import special, stats


@dataclass
class Request:
    index: int
    prompt: np.ndarray          # (S,) int32
    max_new: int
    due: float = None           # open loop: seconds after the start


def _quantiles(n: int) -> np.ndarray:
    """Midpoints of n bins of equal probability."""
    return (np.arange(n) + 0.5) / n


def _lognormal(law: dict, q: np.ndarray) -> np.ndarray:
    return law["median"] * np.exp(law["sigma"] * special.ndtri(q))


def block_pairs(mix: dict) -> tuple:
    """(prompt lengths, output lengths) of one block, in a fixed
    order."""
    n = int(mix["block"])
    q = _quantiles(n)
    law = mix["prompt"]
    lengths = np.asarray(sorted(law["lengths"]), float)
    x = _lognormal(law, q)
    near = np.abs(np.log(x[:, None] / lengths[None, :])).argmin(1)
    prompts = lengths[near].astype(int)
    out = mix["output"]
    o = np.rint(_lognormal(out, q)).astype(int)
    o = o[np.random.default_rng(0).permutation(n)]
    cap = np.minimum(out["max"], int(mix["engine"]["ctx_budget"]) - prompts)
    return prompts, np.clip(o, out["min"], cap)


class Traffic:
    """Seeded schedule of one traffic mix for one vocabulary."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix, self.seed, self.vocab = mix, int(seed), int(vocab)
        rng = np.random.default_rng([self.seed, 0])
        prompts, outputs = block_pairs(mix)
        n = len(prompts)
        blocks = -(-int(mix["pool"]) // n)
        order = np.concatenate([rng.permutation(n) + b * n
                                for b in range(blocks)])
        self.prompt_lens = np.tile(prompts, blocks)[order]
        self.max_new = np.tile(outputs, blocks)[order]
        self.closed = mix["loop"] == "closed"
        self.due = None
        if not self.closed:
            arr = mix["arrivals"]
            cv, rate = float(arr["cv"]), float(arr["rate_per_s"])
            shape = 1.0 / cv ** 2
            gaps = stats.gamma.ppf(_quantiles(n), shape,
                                   scale=1.0 / (rate * shape))
            self.due = np.cumsum(np.concatenate(
                [rng.permutation(gaps) for _ in range(blocks)]))

    @property
    def pool(self) -> int:
        return len(self.prompt_lens)

    @property
    def shapes(self) -> list:
        """Every prompt length the mix sends, shortest first."""
        return sorted(set(block_pairs(self.mix)[0].tolist()))

    def request(self, k: int) -> Request:
        rng = np.random.default_rng([self.seed, 1, k])
        S = int(self.prompt_lens[k])
        prompt = rng.integers(0, self.vocab, S).astype(np.int32)
        return Request(k, prompt, int(self.max_new[k]),
                       None if self.closed else float(self.due[k]))
