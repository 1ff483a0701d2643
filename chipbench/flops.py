"""Operations the decoder's equations need, counted from the configuration's
shapes and the tokens actually processed (never from a compiled program).

A multiply-add is two operations.  Per token and layer the projections
take ``d*(H*hd) + 2*d*(K*hd) + (H*hd)*d`` and the SwiGLU MLP ``3*d*f``
multiply-adds; a query attending ``n`` positions takes ``2*H*hd*n`` for
``q k^T`` and for ``p v``.  The head is ``d*vocab`` (the true vocabulary).
Norms, RoPE and the softmax are left out: they are below 1% at these sizes.
"""

from __future__ import annotations


def layer_matmul_params(cfg) -> int:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    return (d * cfg.n_heads * hd + 2 * d * cfg.n_kv_heads * hd
            + cfg.n_heads * hd * d + 3 * d * cfg.d_ff)


def head_params(cfg) -> int:
    return cfg.d_model * cfg.vocab


def attention_flops(cfg, n_keys: int) -> int:
    """One query attending ``n_keys`` positions, over all layers."""
    return 4 * cfg.n_layers * cfg.n_heads * cfg.resolved_head_dim * n_keys


def decode_token_flops(cfg, n_keys: int) -> int:
    """One decoded token that attends ``n_keys`` positions (itself included),
    with its logits."""
    return (2 * (cfg.n_layers * layer_matmul_params(cfg) + head_params(cfg))
            + attention_flops(cfg, n_keys))

