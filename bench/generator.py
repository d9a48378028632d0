"""The one traffic generator: turns a mix file (``bench/traffic/<mix>.json``)
into the requests of one run.

A mix states its kind and its sizes.  The one kind today is ``closed``: a
deep queue, the run keeping at least ``queue`` requests waiting behind
the ``b_max`` slots (offline batch jobs).

Prompt lengths are drawn from ``prompt_lens`` with ``prompt_probs``;
output lengths are lognormal around ``out_median`` with ``out_sigma``,
rounded and clipped to ``[out_min, out_max]``.  The sizes are drawn once
from the mix's ``shape_seed``, so every run seed serves the same multiset
of work; the run seed shuffles the order within consecutive blocks of
``b_max`` requests (so each block holds the same sizes) and draws the
prompt tokens.

With ``stagger`` the first ``b_max`` requests, which set-up admits
together, keep only ``(i + 1) / b_max`` of their outputs, so their slots
free one by one as in a queue that has run for a while: the window opens
at steady state instead of on a batch that started in lock-step.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List

import numpy as np

KINDS = ("closed",)


@dataclass
class Item:
    rid: int
    prompt: np.ndarray          # (s,) int32
    max_new: int


def _sizes(mix: dict):
    """(prompt lengths, output lengths) fixed by the mix alone."""
    rng = np.random.default_rng(int(mix.get("shape_seed", 0)))
    n = int(mix["requests"])
    plens = rng.choice(np.asarray(mix["prompt_lens"], np.int64), size=n,
                       p=np.asarray(mix["prompt_probs"], np.float64))
    z = rng.standard_normal(n)
    outs = np.rint(float(mix["out_median"]) * np.exp(float(mix["out_sigma"]) * z))
    outs = np.clip(outs, int(mix["out_min"]), int(mix["out_max"])).astype(np.int64)
    if mix.get("stagger"):
        b = int(mix["b_max"])
        share = np.arange(1, b + 1)[:len(outs)]
        outs[:b] = np.maximum(1, -(-outs[:b] * share // b))
    return plens, outs


def _block_shuffle(n: int, block: int, rng) -> np.ndarray:
    order = np.arange(n)
    for lo in range(0, n, block):
        rng.shuffle(order[lo:lo + block])
    return order


def make(mix: dict, vocab: int, seed: int) -> List[Item]:
    """The run's requests, in submission order."""
    if mix["kind"] not in KINDS:
        raise ValueError(f"traffic kind {mix['kind']!r} not in {KINDS}")
    plens, outs = _sizes(mix)
    rng = np.random.default_rng(int(seed))
    block = int(mix["b_max"])
    order = _block_shuffle(len(plens), block, rng)
    items = []
    for i, k in enumerate(order):
        prompt = rng.integers(0, vocab, int(plens[k])).astype(np.int32)
        items.append(Item(i, prompt, int(outs[k])))
    return items


def warm_items(mix: dict, vocab: int, seed: int) -> List[Item]:
    """One short request per prompt length of the mix, so that set-up
    compiles every prefill shape the window will use."""
    rng = np.random.default_rng(int(seed) + 1)
    return [Item(-1 - i, rng.integers(0, vocab, int(s)).astype(np.int32), 2)
            for i, s in enumerate(sorted(set(mix["prompt_lens"])))]
