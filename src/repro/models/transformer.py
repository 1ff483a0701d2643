"""Decoder-only transformer LM: dense (granite / minicpm / glm4 / phi4),
MoE (dbrx / qwen3-moe), and VLM (phi-3-vision: backbone + patch-embed stub).

Layers are stacked on a leading axis and applied with ``jax.lax.scan`` (keeps
HLO size O(1) in depth -- essential for the 94-layer qwen3 dry-run) with an
optional per-layer remat policy.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from jax.ad_checkpoint import checkpoint_name

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.parallel.sharding import lshard


@dataclasses.dataclass(frozen=True)
class ModelOptions:
    """Implementation knobs (hillclimbing targets; defaults are the faithful
    baseline configuration)."""

    attn_impl: str = "xla"        # "xla" | "chunked" (O(s) memory)
    attn_chunk: int = 1024
    remat: bool = True            # checkpoint each scanned layer
    remat_policy: str = "full"    # "full" | "save_tp_outputs" (keep the
                                  # post-all-reduce attn/mlp outputs so the
                                  # recompute pass re-does math, not comm)
    scan_layers: bool = True
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    moe_capacity_factor: float = 0.0   # 0 -> use config value

    @property
    def pdt(self):
        return jnp.dtype(self.param_dtype)

    @property
    def cdt(self):
        return jnp.dtype(self.compute_dtype)


class DecoderLM:
    """Functional LM; all state in explicit param/cache pytrees."""

    def __init__(self, cfg: ArchConfig, opts: ModelOptions | None = None):
        if cfg.family not in ("dense", "moe", "vlm"):
            raise ValueError(f"DecoderLM does not serve family {cfg.family!r}")
        self.cfg = cfg
        self.opts = opts or ModelOptions()

    # ------------------------------------------------------------------ init
    def _init_layer(self, key):
        cfg, pdt = self.cfg, self.opts.pdt
        k_attn, k_ffn = jax.random.split(key)
        p = {
            "attn": L.init_attention(
                k_attn, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                cfg.resolved_head_dim, dtype=pdt,
            ),
            "attn_norm": L.init_rmsnorm(cfg.d_model, pdt),
            "ffn_norm": L.init_rmsnorm(cfg.d_model, pdt),
        }
        if cfg.is_moe:
            p["moe"] = L.init_moe(k_ffn, cfg.d_model, cfg.n_experts, cfg.expert_ff, pdt)
        else:
            p["mlp"] = L.init_mlp(k_ffn, cfg.d_model, cfg.d_ff, pdt)
        return p

    def init(self, key) -> dict:
        cfg, pdt = self.cfg, self.opts.pdt
        k_emb, k_layers, k_head, k_patch = jax.random.split(key, 4)
        layer_keys = jax.random.split(k_layers, cfg.n_layers)
        params = {
            "embed": {"tokens": L.dense_init(k_emb, (cfg.padded_vocab, cfg.d_model), dtype=pdt)},
            "layers": jax.vmap(self._init_layer)(layer_keys),
            "final_norm": L.init_rmsnorm(cfg.d_model, pdt),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = L.dense_init(k_head, (cfg.d_model, cfg.padded_vocab), dtype=pdt)
        if cfg.family == "vlm":
            # modality frontend STUB: a single adapter projecting precomputed
            # patch embeddings into the backbone space.
            params["patch_proj"] = L.dense_init(k_patch, (cfg.d_model, cfg.d_model), dtype=pdt)
        return params

    # --------------------------------------------------------------- forward
    def _layer_fwd(self, lp, x, positions, aux_in):
        cfg = self.cfg
        h = L.attention_fwd(
            lp["attn"], L.rmsnorm(lp["attn_norm"], x, cfg.norm_eps), positions,
            n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.resolved_head_dim, rope_theta=cfg.rope_theta,
            causal=True, attn_impl=self.opts.attn_impl, chunk=self.opts.attn_chunk,
        )
        h = checkpoint_name(h, "attn_out")  # post-TP-all-reduce tensor
        x = x + h
        x = lshard(x, "batch", "seq_sp", "embed")
        normed = L.rmsnorm(lp["ffn_norm"], x, cfg.norm_eps)
        if cfg.is_moe:
            cf = self.opts.moe_capacity_factor or cfg.capacity_factor
            h, aux = L.moe_fwd(lp["moe"], normed, top_k=cfg.top_k,
                               capacity_factor=cf, return_aux=True)
            aux_in = aux_in + aux
        else:
            h = L.mlp_fwd(lp["mlp"], normed)
        h = checkpoint_name(h, "mlp_out")   # post-TP-all-reduce tensor
        x = lshard(x + h, "batch", "seq_sp", "embed")
        return x, aux_in

    def _run_layers(self, params, x, positions):
        aux0 = jnp.zeros((), jnp.float32)

        policy = None
        if self.opts.remat_policy == "save_tp_outputs":
            policy = jax.checkpoint_policies.save_only_these_names(
                "attn_out", "mlp_out")

        def body(carry, lp):
            x, aux = carry
            fn = self._layer_fwd
            if self.opts.remat:
                fn = jax.checkpoint(fn, prevent_cse=False, policy=policy)
            x, aux = fn(lp, x, positions, aux)
            return (x, aux), None

        if self.opts.scan_layers:
            (x, aux), _ = jax.lax.scan(body, (x, aux0), params["layers"])
        else:
            n = self.cfg.n_layers
            aux = aux0
            for i in range(n):
                lp = jax.tree.map(lambda a: a[i], params["layers"])
                (x, aux), _ = body((x, aux), lp)
        return x, aux

    def embed(self, params, tokens):
        cdt = self.opts.cdt
        x = params["embed"]["tokens"].astype(cdt)[tokens]
        return lshard(x, "batch", "seq", "embed")

    def logits(self, params, x):
        cdt = self.opts.cdt
        head = (
            params["embed"]["tokens"].T if self.cfg.tie_embeddings else params["lm_head"]
        ).astype(cdt)
        out = jnp.einsum("bsd,dv->bsv", x, head)
        if self.cfg.padded_vocab != self.cfg.vocab:
            # mask padding entries so the softmax ignores them
            valid = jnp.arange(self.cfg.padded_vocab) < self.cfg.vocab
            out = jnp.where(valid[None, None, :], out, -1e30)
        return lshard(out, "batch", "seq", "vocab")

    def forward(self, params, batch) -> tuple[jax.Array, jax.Array]:
        """batch: {"tokens": (b,s) int32 [, "patches": (b,P,d)]} ->
        (logits (b,s,V), moe aux loss)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        x = self.embed(params, tokens)
        if cfg.family == "vlm":
            patches = batch["patches"].astype(self.opts.cdt)
            prefix = jnp.einsum("bpd,de->bpe", patches, params["patch_proj"].astype(self.opts.cdt))
            x = jnp.concatenate([prefix, x], axis=1)
            x = lshard(x, "batch", "seq", "embed")
        positions = jnp.arange(x.shape[1])[None, :]
        x, aux = self._run_layers(params, x, positions)
        x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        if cfg.family == "vlm":
            x = x[:, self.cfg.n_patches:, :]  # score only token positions
        return self.logits(params, x), aux

    def loss(self, params, batch) -> tuple[jax.Array, dict]:
        logits, aux = self.forward(params, batch)
        labels = batch["labels"]
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        mask = (labels >= 0).astype(jnp.float32)
        safe = jnp.maximum(labels, 0)
        nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
        denom = jnp.maximum(mask.sum(), 1.0)
        ce = (nll * mask).sum() / denom
        total = ce + 0.01 * aux
        return total, {"ce": ce, "aux": aux, "tokens": denom}

    # ----------------------------------------------------------------- serve
    def init_cache(self, batch: int, max_len: int) -> dict:
        cfg = self.cfg
        kv = L.init_kv_cache(batch, max_len, cfg.n_kv_heads, cfg.resolved_head_dim,
                             dtype=self.opts.cdt)
        return {
            "kv": jax.tree.map(
                lambda a: jnp.broadcast_to(a, (cfg.n_layers,) + a.shape), kv
            ),
            "index": jnp.zeros((), jnp.int32),
        }

    def cache_axes(self) -> dict:
        """Logical axis names for every cache leaf (drives sharding)."""
        kv = ("layers", "batch", "kv_seq", "kv_heads", "head_dim")
        return {"kv": {"k": kv, "v": kv}, "index": ()}

    # ---------------------------------------------------------- paged serve
    def init_paged_cache(self, n_pages: int, page_size: int) -> dict:
        """Paged KV pool (DESIGN.md §7): {"k","v"} of shape (n_layers,
        n_pages, page_size, K*hd).  Page bookkeeping (free list, block
        tables) lives in :class:`repro.serve.kv_cache.PagedKVCache`."""
        cfg = self.cfg
        kv = L.init_paged_kv(n_pages, page_size, cfg.n_kv_heads,
                             cfg.resolved_head_dim, dtype=self.opts.cdt)
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.n_layers,) + a.shape), kv
        )

    def _paged_layer_stack(self, params, x, attn_fn, pages):
        """Scan the layer stack carrying the whole pool, so each layer's
        writes land in place: ``attn_fn(layer_params, normed_x, pages,
        layer) -> (h, new_pages)``."""
        cfg = self.cfg

        def body(carry, inp):
            x, pg = carry
            lp, layer = inp
            h, pg = attn_fn(lp, L.rmsnorm(lp["attn_norm"], x, cfg.norm_eps), pg, layer)
            x = x + h
            normed = L.rmsnorm(lp["ffn_norm"], x, cfg.norm_eps)
            if cfg.is_moe:
                cf = self.opts.moe_capacity_factor or cfg.capacity_factor
                h = L.moe_fwd(lp["moe"], normed, top_k=cfg.top_k, capacity_factor=cf)
            else:
                h = L.mlp_fwd(lp["mlp"], normed)
            return (x + h, pg), None

        layers = jnp.arange(cfg.n_layers, dtype=jnp.int32)
        (x, pages), _ = jax.lax.scan(body, (x, pages), (params["layers"], layers))
        x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        return self.logits(params, x), pages

    def decode_step_paged(self, params, pages, block_tables, lengths, tokens,
                          active) -> tuple[jax.Array, dict]:
        """Continuous-batching decode: one token per lane against the paged
        cache.  ``tokens`` (b, 1); ``block_tables`` (b, max_blocks);
        ``lengths``/``active`` (b,).  Returns (logits (b, 1, V), new pages)."""
        cfg = self.cfg
        x = self.embed(params, tokens)
        attn = lambda lp, normed, pg, layer: L.attention_decode_paged(
            lp["attn"], normed, pg, layer, block_tables, lengths, active,
            n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.resolved_head_dim, rope_theta=cfg.rope_theta,
        )
        return self._paged_layer_stack(params, x, attn, pages)

    def prefill_paged(self, params, pages, block_table, length, tokens
                      ) -> tuple[jax.Array, dict]:
        """Prefill one sequence (tokens (1, S) padded, true length
        ``length``), scattering its KV into pages.  Returns (logits (1, S, V),
        new pages); the caller samples from position ``length - 1``."""
        cfg = self.cfg
        x = self.embed(params, tokens)
        attn = lambda lp, normed, pg, layer: L.attention_prefill_paged(
            lp["attn"], normed, pg, layer, block_table, length,
            n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.resolved_head_dim, rope_theta=cfg.rope_theta,
        )
        return self._paged_layer_stack(params, x, attn, pages)

    def decode_step(self, params, cache, tokens) -> tuple[jax.Array, dict]:
        """One-token decode: tokens (b, 1) -> (logits (b, 1, V), new cache)."""
        cfg = self.cfg
        x = self.embed(params, tokens)
        index = cache["index"]

        def body(x, inp):
            lp, kvc = inp
            h, kvc = L.attention_decode(
                lp["attn"], L.rmsnorm(lp["attn_norm"], x, cfg.norm_eps), kvc, index,
                n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                head_dim=cfg.resolved_head_dim, rope_theta=cfg.rope_theta,
            )
            x = x + h
            normed = L.rmsnorm(lp["ffn_norm"], x, cfg.norm_eps)
            if cfg.is_moe:
                cf = self.opts.moe_capacity_factor or cfg.capacity_factor
                h = L.moe_fwd(lp["moe"], normed, top_k=cfg.top_k, capacity_factor=cf)
            else:
                h = L.mlp_fwd(lp["mlp"], normed)
            return x + h, kvc

        x, kv = jax.lax.scan(body, x, (params["layers"], cache["kv"]))
        x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        return self.logits(params, x), {"kv": kv, "index": index + 1}
