"""The operation counts against counts made by hand, matrix by matrix, at
a reduced size."""

from chipbench import flops
from chipbench.tests.helpers import reduced_cell
from chipbench import cells


def _cfg():
    return cells.arch_config(reduced_cell("minicpm-2b.decode-backlog").conf)


def _hand_layer_matmuls(c):
    d, hd = c.d_model, c.resolved_head_dim
    mats = [(d, c.n_heads * hd), (d, c.n_kv_heads * hd), (d, c.n_kv_heads * hd),
            (c.n_heads * hd, d), (d, c.d_ff), (d, c.d_ff), (c.d_ff, d)]
    return sum(2 * k * n for k, n in mats)   # one token through each matrix


def test_reduced_sizes_are_what_the_hand_count_assumes():
    c = _cfg()
    assert (c.d_model, c.n_heads, c.n_kv_heads, c.resolved_head_dim, c.d_ff,
            c.vocab) == (64, 4, 4, 16, 128, 512)


def test_decode_token_flops_by_hand():
    c = _cfg()
    n_keys = 37
    attn = 0
    for _ in range(c.n_layers):
        for _ in range(c.n_heads):
            attn += 2 * c.resolved_head_dim * n_keys   # q . k for every key
            attn += 2 * c.resolved_head_dim * n_keys   # p * v for every key
    want = c.n_layers * _hand_layer_matmuls(c) + 2 * c.d_model * c.vocab + attn
    assert flops.decode_token_flops(c, n_keys) == want


def test_peaks_are_keyed_by_device_kind():
    import pytest

    from chipbench import peaks

    assert peaks.peak("TPU v5 lite", "bf16_flops") == 197e12
    with pytest.raises(KeyError, match="cpu"):
        peaks.peak("cpu", "bf16_flops")
