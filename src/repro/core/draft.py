"""Device-resident draft models for speculative decoding.

The dominant cost of offloaded decode is streaming the whole layer
stack over the link once per generated token.  Speculative decoding
(SpecOffload's framing rendered on this codebase) amortizes that: a
small draft model whose weights live ENTIRELY on the device proposes
``k`` cheap tokens, then the streamed target scores all ``k+1``
positions in one ragged decode step — one trip through the layer stack
buys up to ``k+1`` emitted tokens.  Greedy accept/reject makes the
output stream *bit-identical* to non-speculative greedy decode for any
proposal stream, good or bad; the draft's quality only moves the
acceptance length (and therefore the speedup), never the tokens.

``ResidentDraft`` is the real draft: a registry architecture built
through the same ``models`` facade the resident serving engine uses,
with its own device KV cache slaved to the target's slot positions.
It never truncates its cache on rejection — rejected rows sit beyond
the live position, masked by decode attention (``kv_pos <= pos``) and
overwritten by the next proposal pass, the same value-invisibility
argument the tiered KV store's padding relies on.

``accept_length``/``accepted_tokens`` are the pure accept/reject
kernel both engines (and the hypothesis property suite) share — any
drift between engines would otherwise silently fork the semantics.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import Dist, build_model
from repro.models import transformer as T

__all__ = ["ResidentDraft", "accept_length", "accepted_tokens"]


def accept_length(draft: Sequence[int], target: Sequence[int]) -> int:
    """Greedy accept rule: the number of leading draft proposals that
    match the target's per-position greedy choices.  ``draft`` carries
    the k proposals; ``target[i]`` is the target's argmax at the
    position whose input was ``draft[i-1]`` (``target[0]``'s input is
    the current token), so proposal ``i`` is sound iff every earlier
    proposal matched AND ``draft[i] == target[i]``."""
    a = 0
    k = len(draft)
    while a < k and int(draft[a]) == int(target[a]):
        a += 1
    return a


def accepted_tokens(draft: Sequence[int], target: Sequence[int]):
    """The tokens one verify pass emits: the ``a`` accepted proposals
    plus the target's bonus token at the first divergence (or after the
    last proposal) — ``target[:a+1]``.  Token-for-token equal to what
    ``a+1`` sequential non-speculative greedy steps would emit."""
    a = accept_length(draft, target)
    return [int(t) for t in target[:a + 1]]


class ResidentDraft:
    """A fully device-resident greedy draft model.

    The draft holds its own parameters and KV cache on the device and
    is *slaved* to the engine's slot state: ``prefill_slot``/
    ``prefill_batch`` admit prompts, ``propose(tokens, pos, k)`` runs
    ``k`` ragged decode steps from the engine's per-slot positions and
    returns the proposals.  The engine never feeds accepted tokens back
    separately — proposal rows double as the draft's cache rows, and
    rejected rows are overwritten by the next pass (masked until then).
    """

    def __init__(self, cfg: ModelConfig, *, b_max: int, max_len: int,
                 seed: int = 0):
        self.cfg = cfg
        self.b_max = b_max
        self.max_len = max_len
        self.dist = Dist.local()
        self.model = build_model(cfg)
        self.params = T.served_params(
            cfg, self.model.init(jax.random.PRNGKey(seed), jnp.float32))
        self.caches = self.model.init_cache(b_max, max_len)
        m, dist = self.model, self.dist

        def decode(params, tok, pos, caches):
            return m.decode_step(params, {"token": tok, "pos": pos},
                                 caches, dist)
        self._decode = jax.jit(decode, donate_argnums=(3,))

        def prefill1(params, toks):
            return m.prefill(params, {"tokens": toks}, dist, max_len)
        self._prefill = jax.jit(prefill1)

    # ---- cache plumbing (same pat/rem layout as the resident engine) -----
    @staticmethod
    def _batch_axis(path) -> int:
        head = str(getattr(path[0], "key", getattr(path[0], "idx", path[0])))
        return 1 if head == "pat" else 0

    def _scatter_slot(self, slot: int, cache1):
        flat_big, treedef = jax.tree_util.tree_flatten_with_path(self.caches)
        flat_one = treedef.flatten_up_to(cache1)
        out = []
        for (path, big), one in zip(flat_big, flat_one):
            ax = self._batch_axis(path)
            idx = [slice(None)] * big.ndim
            idx[ax] = slice(slot, slot + 1)
            out.append(big.at[tuple(idx)].set(one.astype(big.dtype)))
        self.caches = jax.tree_util.tree_unflatten(treedef, out)

    # ---- admission -------------------------------------------------------
    def prefill_slot(self, slot: int, prompt: np.ndarray) -> None:
        """Admit one prompt into ``slot`` (the serving path)."""
        _, cache1 = self._prefill(self.params,
                                  jnp.asarray(prompt, jnp.int32)[None])
        self._scatter_slot(slot, cache1)

    def prefill_batch(self, tokens: np.ndarray) -> None:
        """Admit a full uniform batch (the ``PipelinedLM`` path);
        ``tokens`` is ``(b_max, s)``."""
        assert tokens.shape[0] == self.b_max, tokens.shape
        _, caches = self._prefill(self.params,
                                  jnp.asarray(tokens, jnp.int32))
        self.caches = jax.tree_util.tree_map(
            lambda one, big: one.astype(big.dtype), caches, self.caches)

    # ---- proposal --------------------------------------------------------
    def propose(self, tokens, pos, k: int) -> np.ndarray:
        """Run ``k`` greedy draft steps from the engine's state:
        ``tokens`` (b_max,) are the last emitted tokens (not yet in any
        cache), ``pos`` (b_max,) the target's per-slot positions.  Step
        ``t`` feeds the previous token at position ``pos + t``.
        Returns the proposals, ``(b_max, k)`` int32."""
        cur = jnp.asarray(np.asarray(tokens, np.int32))[:, None]
        base = np.asarray(pos, np.int32)
        out = np.zeros((self.b_max, int(k)), np.int32)
        for t in range(int(k)):
            nt, self.caches = self._decode(
                self.params, cur, jnp.asarray(base + t), self.caches)
            out[:, t] = np.asarray(nt)
            cur = nt[:, None]
        return out
