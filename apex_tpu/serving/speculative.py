"""Speculative decoding: a small draft GPT proposes, the target verifies.

Per round the draft model autoregressively proposes ``num_tokens``
tokens (one cheap decode step each), then the target model scores the
current token plus every proposal in ONE chunked forward
(:meth:`~apex_tpu.models.gpt.GPTModel.decode_chunk`) — γ+1 target
logits for the latency of a single wide step.

Acceptance rule (exact-match verification): the token at stream index
``i`` is ALWAYS ``sample(target_logits_i, fold_in(seed, i))`` — the
identical function of the identical logits and key the non-speculative
engine uses.  A proposal is "accepted" simply when it equals that
canonical token, letting the round keep consuming the already-computed
target logits for later positions; on the first mismatch the canonical
token replaces it and the round ends.  Speculation therefore changes
only HOW MANY target positions get evaluated per device round — never
what the stream emits — so greedy and seeded outputs are token-identical
to the non-speculative engine by construction (the property
``tests/test_serving.py`` asserts).  This is the deterministic special case of
the Leviathan et al. rejection sampler: with the per-request
``(seed, token-index)`` stream there is exactly one canonical token per
index, and matching it is the only acceptance that preserves the
stream.  The draft samples its proposals with the same params, seed and
indices, which maximizes the match rate under stochastic sampling.

Rejected proposals leave stale KV in the pool past the accepted point;
those positions sit beyond every valid length (masked) and are
overwritten when decoding reaches them.
"""

from __future__ import annotations

import dataclasses
from typing import Any


@dataclasses.dataclass
class SpeculativeConfig:
    """Draft-model bundle for :class:`~apex_tpu.serving.PagedInferenceEngine`.

    ``model``/``params``: the draft GPT (same vocab as the target;
    typically far fewer layers/heads).  ``num_tokens``: proposals per
    round (γ) — each round costs γ draft steps + one (γ+1)-wide target
    chunk, and emits between 1 and γ+1 stream tokens.
    """
    model: Any
    params: Any
    num_tokens: int = 3

    def __post_init__(self):
        if self.num_tokens < 1:
            raise ValueError("num_tokens must be >= 1")

    def validate_against(self, target_model) -> None:
        if self.model.cfg.vocab_size != target_model.cfg.vocab_size:
            raise ValueError(
                "draft and target models must share a vocabulary "
                f"({self.model.cfg.vocab_size} != "
                f"{target_model.cfg.vocab_size})")
        self.model._check_decode_supported()
