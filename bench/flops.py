"""Operations and bytes the training round needs, from the configuration
and the mix alone (never from the compiled program).

The plane size (``param_count``) and the model FLOPs per token
(``flops_per_token``) come from the configuration's plain reference
module, the one its ``reference`` key names (``bench/ref``): a
configuration with a block of its own brings its own counts.

A round of LT-ADMM with the SVRG anchor on ``A`` agents processes
``A * (m_local + 2 * tau * batch_size) * T`` tokens: one anchor pass
over the agent's ``m_local`` sequences, then two minibatch gradients
(at the iterate and at the anchor) per local step.
"""
from __future__ import annotations

from bench import ref


def param_count(c) -> int:
    """Every parameter of the model: the length of the packed plane."""
    return ref.module(c).param_count(c)


def flops_per_token(c, seq_len) -> int:
    return ref.module(c).flops_per_token(c, seq_len)


def tokens_per_round(train) -> int:
    per_agent = train["m_local"] + 2 * train["tau"] * train["batch_size"]
    return train["agents"] * per_agent * train["seq_len"]


def flops_per_round(c, train) -> int:
    return tokens_per_round(train) * flops_per_token(c, train["seq_len"])


def messages_per_round(train) -> int:
    """Quantized messages per round: one x broadcast per agent and one
    z message per directed edge."""
    from bench.ref.ltadmm import neighbours

    nbrs = neighbours(train["topology"], train["agents"])
    return train["agents"] + sum(len(n) for n in nbrs)


def quantize_bytes_per_round(c, train) -> int:
    """HBM bytes the quantizer must move per round: each message's
    float32 plane read once and its ``bits``-bit codes written once."""
    n = param_count(c)
    return messages_per_round(train) * n * (4 * 8 + train["bits"]) // 8
