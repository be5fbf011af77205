"""Useful operations of a served decoder, counted from shapes.

Useful means what the served requests need: real rows and tokens only,
attention over each token's valid context (causal), one row of output-head
logits per generated token over the real vocabulary.  Padded rows, masked
positions and padded vocabulary rows are never counted, so the count is
the same for the same work whatever computes it.  A multiply-add is two
operations; norms, rotary, softmax and sampling are left out (under 1% at
these widths).  The architecture's module gives the per-token terms: the
weights a token multiplies, and the operations per position it attends to.
"""
from __future__ import annotations

from chipbench import arch


def token_flops(spec, context: int) -> int:
    """One token through every layer, attending to ``context`` positions
    (itself included): weight matmuls, scores and weighted values."""
    mod = arch.of(spec)
    return 2 * mod.token_weights(spec) + mod.attention_ops(spec) * context


def head_flops(spec) -> int:
    """One row of output-head logits over the real vocabulary."""
    return 2 * spec.d_model * spec.vocab


def prefill_flops(spec, prompt_len: int) -> int:
    """A prompt's tokens, causal (token i attends to i + 1 positions), and
    the logits of its last position, which give the first served token."""
    mod = arch.of(spec)
    p = prompt_len
    return (2 * mod.token_weights(spec) * p
            + mod.attention_ops(spec) * p * (p + 1) // 2 + head_flops(spec))


def decode_flops(spec, prompt_len: int, n_out: int) -> int:
    """The decode steps after the first served token: the step that gives
    token j (j >= 1) runs token j - 1 at position prompt_len + j - 1, which
    attends to prompt_len + j positions."""
    return sum(token_flops(spec, prompt_len + j) + head_flops(spec)
               for j in range(1, n_out))


def request_flops(spec, prompt_len: int, n_out: int) -> int:
    return prefill_flops(spec, prompt_len) + decode_flops(spec, prompt_len,
                                                          n_out)
