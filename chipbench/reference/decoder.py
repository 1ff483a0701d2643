"""Plain float32 reference of the decoder-only LM, written from the
configuration file and the layer equations, importing nothing of the
program:

    x = E[tokens]
    per layer:  h = rmsnorm(x) * g1;  q, k, v = h Wq, h Wk, h Wv
                q, k = rope(q), rope(k)       (pairs (2i, 2i+1), theta)
                a = softmax(q k^T / sqrt(hd) + causal) v   (kv head = head // (H/K))
                x = x + a Wo
                h = rmsnorm(x) * g2;  x = x + (silu(h Wg) * (h Wi)) Wout
    logits = (rmsnorm(x) * gf) W_head         (W_head = E^T when tied)

Matrix products run at ``highest`` precision.  It reads the benchmark's
weights (bfloat16 values, computed in float32) layer by layer inside one
scan, with attention in blocks of queries, so that it fits one chip beside
the weights.  ``quant="fp8"`` is the control: every matrix product's
operands rounded to float8 e4m3 (per-tensor scale), accumulated in float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
FP8_MAX = 448.0


def _quantizer(quant):
    if quant is None:
        return lambda x: x
    if quant != "fp8":
        raise ValueError(f"unknown quantization {quant!r}")

    def q(x):
        s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
        return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s

    return q


def make_logits_fn(conf: dict, quant=None, q_block: int = 512):
    """``fn(params, tokens (S,), out_pos (P,)) -> logits (P, vocab)`` f32,
    jitted; ``conf`` is a configuration file's dict."""
    d, n_h = conf["hidden_size"], conf["num_attention_heads"]
    n_kv = conf["num_key_value_heads"]
    hd = conf.get("head_dim") or d // n_h
    eps, theta, vocab = conf["rms_norm_eps"], conf["rope_theta"], conf["vocab_size"]
    rep = n_h // n_kv
    tied = conf["tie_word_embeddings"]
    q = _quantizer(quant)

    def mm(x, w):
        return jnp.dot(q(x), q(w.astype(F32)))

    def norm(x, g):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g.astype(F32)

    def rope(x, pos):
        freqs = theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
        ang = pos[:, None].astype(F32) * freqs
        c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.stack([x1 * c - x2 * s, x1 * s + x2 * c], -1).reshape(x.shape)

    def attention(qh, kh, vh):
        s_len = qh.shape[0]
        kq, vq = q(kh), q(vh)
        outs = []
        for b0 in range(0, s_len, q_block):
            qb = qh[b0:b0 + q_block]
            nb = qb.shape[0]
            sc = jnp.einsum("bkrd,skd->krbs", q(qb.reshape(nb, n_kv, rep, hd)), kq)
            sc = sc / np.sqrt(hd)
            mask = (b0 + jnp.arange(nb))[:, None] >= jnp.arange(s_len)[None, :]
            p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
            outs.append(jnp.einsum("krbs,skd->bkrd", q(p), vq).reshape(nb, n_h * hd))
        return jnp.concatenate(outs, 0)

    def layer(x, lp):
        s_len = x.shape[0]
        pos = jnp.arange(s_len)
        at = lp["attn"]
        h = norm(x, lp["attn_norm"]["norm_scale"])
        qh = rope(mm(h, at["wq"]).reshape(s_len, n_h, hd), pos)
        kh = rope(mm(h, at["wk"]).reshape(s_len, n_kv, hd), pos)
        vh = mm(h, at["wv"]).reshape(s_len, n_kv, hd)
        x = x + mm(attention(qh, kh, vh), at["wo"])
        h = norm(x, lp["ffn_norm"]["norm_scale"])
        mlp = lp["mlp"]
        x = x + mm(jax.nn.silu(mm(h, mlp["w_gate"])) * mm(h, mlp["w_in"]), mlp["w_out"])
        return x, None

    def fn(params, tokens, out_pos):
        with jax.default_matmul_precision("highest"):
            x = q(params["embed"]["tokens"][tokens].astype(F32))
            x, _ = jax.lax.scan(layer, x, params["layers"])
            x = norm(x, params["final_norm"]["norm_scale"])[out_pos]
            if tied:
                return mm(x, params["embed"]["tokens"][:vocab].T)
            return mm(x, params["lm_head"][:, :vocab])

    return jax.jit(fn)
