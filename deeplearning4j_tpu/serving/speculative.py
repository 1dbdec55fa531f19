"""Speculative-decoding draft sources for the generation engine.

Plain continuous-batching decode advances every stream ONE token per
model dispatch — the memory-bandwidth-bound regime speculative decoding
attacks: a cheap DRAFTER proposes ``k`` tokens per stream, the engine
scores all of them (plus one bonus position) in a single chunked
"verify-once" forward over the paged KV cache
(`ops.paged_attention.paged_attention_chunk`), and rejection sampling
keeps the output distribution exactly the baseline's.

This module owns the draft side of that split: `DraftSource` is the
pluggable contract (``draft(history, k) -> up to k proposed tokens``),
with two implementations —

- `NGramDrafter` (default, ``"ngram"``) — self-drafting prompt-lookup:
  the longest n-gram suffix of the stream's history (prompt + generated
  tokens) is matched against its most recent earlier occurrence and the
  tokens that followed it are proposed.  Zero model cost, zero state,
  pure host numpy; it shines exactly where real decoding does — copy
  runs, repeated entities, structured output — and greedy decode's
  tendency to settle into repeating patterns makes it the honest
  default for the committed CPU bench.
- `ModelDrafter` (``"model"``) — the two-model configuration: a small
  zoo model decodes ``k`` tokens greedily (one bucketed forward per
  draft token, compiled once per `flags.bucket_length` bucket, so the
  drafter's compiled-program set is bounded the same way the engine's
  is).  Greedy drafting is deterministic, which the engine's
  rejection-sampling parity contract relies on.

Drafts are PROPOSALS, never outputs: the engine samples the target
model's token at every chunk position with the baseline ``fold_in`` key
schedule and emits the accepted prefix plus that sample — a drafter
returning garbage (see the ``serving.draft`` fault site's ``corrupt``
kind) costs acceptance, never correctness.

Knobs (read by `GenerationConfig` resolution, overridable per request):
``DL4J_TPU_SPEC_K`` (draft length; 0 disables) and
``DL4J_TPU_SPEC_DRAFTER`` (``ngram`` | ``model``).
"""

from __future__ import annotations

import logging
import os

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.ops.generation import (
    _act_dtype,
    _head_logits,
    _plan,
    prompt_forward,
)
from deeplearning4j_tpu.runtime.flags import bucket_length

log = logging.getLogger("deeplearning4j_tpu")

ENV_SPEC_K = "DL4J_TPU_SPEC_K"
ENV_SPEC_DRAFTER = "DL4J_TPU_SPEC_DRAFTER"

DRAFTER_NAMES = ("ngram", "model")

_EMPTY = np.zeros(0, np.int32)


class DraftSource:
    """The pluggable drafter contract.

    ``draft(history, k)`` returns UP TO ``k`` proposed continuation
    tokens (int32, possibly empty) for a stream whose full token
    history (prompt + everything generated so far, including the token
    the next step will process) is ``history``.  Must be deterministic
    for a given history — the engine's byte-parity contract samples the
    target model at every position regardless, but a deterministic
    drafter keeps acceptance measurements reproducible.  Called from
    the engine thread BETWEEN dispatches; implementations must not
    block on anything slower than a small host computation or a single
    bounded device call.
    """

    name = "none"

    def draft(self, history: np.ndarray, k: int) -> np.ndarray:
        raise NotImplementedError


class NGramDrafter(DraftSource):
    """Self-drafting prompt-lookup (assisted-generation style): find
    an earlier occurrence of the longest n-gram suffix of the history
    and propose the tokens that followed it — preferring the most
    recent occurrence that still has a full k-token continuation."""

    name = "ngram"

    def __init__(self, max_n: int = 3, min_n: int = 1):
        if min_n < 1 or max_n < min_n:
            raise ValueError(f"need 1 <= min_n <= max_n, got "
                             f"[{min_n}, {max_n}]")
        self.max_n = int(max_n)
        self.min_n = int(min_n)

    def draft(self, history: np.ndarray, k: int) -> np.ndarray:
        h = np.asarray(history, np.int32).reshape(-1)
        n_hist = h.shape[0]
        if k <= 0 or n_hist < 2:
            return _EMPTY
        for n in range(min(self.max_n, n_hist - 1), self.min_n - 1, -1):
            suffix = h[n_hist - n:]
            # windows over h[:-1]: the suffix's own occurrence is
            # excluded, every earlier one is a candidate
            win = np.lib.stride_tricks.sliding_window_view(h[:-1], n)
            hits = np.nonzero((win == suffix).all(axis=1))[0]
            if hits.size:
                # most recent occurrence with a FULL k-token
                # continuation; an occurrence butting against the end
                # of history would propose a truncated draft (cyclic
                # tails hit this every step)
                full = hits[hits + n + k <= n_hist]
                i = int(full[-1] if full.size else hits[-1])
                return h[i + n: i + n + k].copy()
        return _EMPTY


class ModelDrafter(DraftSource):
    """Two-model drafting: a small zoo model greedily decodes ``k``
    tokens from the history.  One bucketed full forward per draft token
    — no KV cache of its own — compiled once per bucket, so a serving
    life adds a bounded handful of drafter programs."""

    name = "model"

    def __init__(self, model, quantum: int = 16):
        if model.params is None:
            model.init()
        self.model = model
        self._quantum = int(quantum)
        self._fns: dict = {}
        self._stack = _plan(model)

    def _fn(self, t_b: int):
        fn = self._fns.get(t_b)
        if fn is not None:
            return fn
        stack = self._stack

        @jax.jit
        def last_greedy(params, toks_pad, true_len):
            x, _ = prompt_forward(stack, params, toks_pad,
                                  _act_dtype(self.model))
            logits = _head_logits(stack, params, x[0, true_len - 1])
            return jnp.argmax(logits).astype(jnp.int32)

        self._fns[t_b] = last_greedy
        return last_greedy

    def draft(self, history: np.ndarray, k: int) -> np.ndarray:
        toks = np.asarray(history, np.int32).reshape(-1)
        if k <= 0 or toks.shape[0] < 1:
            return _EMPTY
        pos = self._stack.pos
        if (pos is not None and pos.learned
                and toks.shape[0] + k > pos.max_length):
            return _EMPTY                 # would overflow the draft PE
        out = []
        for _ in range(k):
            n = toks.shape[0]
            t_b = bucket_length(n, self._quantum)
            pad = np.zeros((1, t_b), np.int32)
            pad[0, :n] = toks
            nxt = int(self._fn(t_b)(self.model.params, pad, np.int32(n)))
            out.append(nxt)
            toks = np.append(toks, np.int32(nxt))
        return np.asarray(out, np.int32)


def make_drafter(name: str, *, draft_model=None) -> DraftSource:
    """Resolve a drafter by knob value (`DL4J_TPU_SPEC_DRAFTER` /
    `GenerationConfig.spec_drafter`)."""
    name = (name or "ngram").strip().lower()
    if name in ("ngram", "prompt_lookup", "lookup"):
        return NGramDrafter()
    if name == "model":
        if draft_model is None:
            raise ValueError(
                "drafter 'model' needs a draft model "
                "(GenerationConfig.spec_draft_model)"
            )
        return ModelDrafter(draft_model)
    raise ValueError(
        f"unknown drafter {name!r} (one of {DRAFTER_NAMES})"
    )


def spec_k_from_env(default: int = 0) -> int:
    """`DL4J_TPU_SPEC_K` as an int (0 = speculative decode off)."""
    raw = os.environ.get(ENV_SPEC_K, "").strip()
    if not raw:
        return default
    try:
        k = int(raw)
    except ValueError:
        log.warning("bad %s=%r (want an int); speculative decode off",
                    ENV_SPEC_K, raw)
        return default
    return max(0, k)


def drafter_from_env(default: str = "ngram") -> str:
    return os.environ.get(ENV_SPEC_DRAFTER, "").strip().lower() or default
