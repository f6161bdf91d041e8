"""Operations and bytes the training round needs, from the configuration
and the mix alone (never from the compiled program).

Model FLOPs follow the PaLM convention (Chowdhery et al. 2022, appendix
B): ``6 * P + 12 * L * d_q * T`` per token, where ``P`` counts every
matrix-product parameter, the tied unembedding included, ``d_q`` is the
query width (heads times head size) and ``T`` the sequence length.
Recomputation (remat) is not counted.

A round of LT-ADMM with the SVRG anchor on ``A`` agents processes
``A * (m_local + 2 * tau * batch_size) * T`` tokens: one anchor pass
over the agent's ``m_local`` sequences, then two minibatch gradients
(at the iterate and at the anchor) per local step.
"""
from __future__ import annotations


def _attn_params(c):
    d, h, kh, dh = (c["hidden_size"], c["num_attention_heads"],
                    c["num_key_value_heads"], c["head_dim"])
    return d * h * dh * 2 + d * kh * dh * 2  # wq, wo, wk, wv


def matmul_params(c) -> int:
    """Parameters that enter a matrix product, tied unembedding once."""
    per_layer = _attn_params(c) + 3 * c["hidden_size"] * c["intermediate_size"]
    return c["num_hidden_layers"] * per_layer + c["vocab_size"] * c["hidden_size"]


def param_count(c) -> int:
    """Every parameter of the model: matrix weights and norm scales."""
    n = matmul_params(c)
    if c["norm"] == "rms":
        n += (2 * c["num_hidden_layers"] + 1) * c["hidden_size"]
    if c["qk_norm"]:
        n += 2 * c["num_hidden_layers"] * c["head_dim"]
    return n


def flops_per_token(c, seq_len) -> int:
    d_q = c["num_attention_heads"] * c["head_dim"]
    return 6 * matmul_params(c) + 12 * c["num_hidden_layers"] * d_q * seq_len


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
