"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp ref oracles,
swept over shapes and dtypes, plus hypothesis property tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # property tests need it; skip, not error
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import ops, ref
from repro.configs import ARCHS
from repro.kernels.flash_attention import flash_attention as fa_pallas
from repro.kernels.paged_decode import paged_decode_attention as pd_pallas
from repro.kernels.rmsnorm import rmsnorm as rms_pallas
from repro.kernels.ssd_chunk import ssd_chunk_scan as ssd_pallas


def rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


TOL = {jnp.float32: (1e-5, 1e-5), jnp.bfloat16: (2e-2, 2e-2)}


class TestFlashAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize(
        "b,hq,hkv,sq,skv,hd,bq,bk",
        [
            (2, 4, 2, 64, 64, 32, 32, 32),
            (1, 8, 1, 128, 128, 64, 64, 32),   # MQA
            (2, 4, 4, 96, 96, 32, 32, 32),     # MHA, non-pow2 seq
            (1, 2, 2, 32, 128, 32, 32, 64),    # cross-length (prefix cache)
        ],
    )
    def test_matches_ref_causal(self, dtype, b, hq, hkv, sq, skv, hd, bq, bk):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = rand(ks[0], (b, hq, sq, hd), dtype)
        k = rand(ks[1], (b, hkv, skv, hd), dtype)
        v = rand(ks[2], (b, hkv, skv, hd), dtype)
        out = fa_pallas(q, k, v, causal=True, block_q=bq, block_k=bk, interpret=True)
        expect = ref.flash_attention_ref(q, k, v, causal=True)
        rtol, atol = TOL[dtype]
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(expect, np.float32),
            rtol=rtol, atol=atol,
        )

    def test_non_causal(self):
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = rand(ks[0], (1, 2, 64, 32), jnp.float32)
        k = rand(ks[1], (1, 2, 64, 32), jnp.float32)
        v = rand(ks[2], (1, 2, 64, 32), jnp.float32)
        out = fa_pallas(q, k, v, causal=False, block_q=32, block_k=32, interpret=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref.flash_attention_ref(q, k, v, causal=False)),
            rtol=1e-5, atol=1e-5,
        )

    def test_causal_mask_is_exact(self):
        """Future tokens must have exactly zero influence."""
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        q = rand(ks[0], (1, 2, 64, 32), jnp.float32)
        k = rand(ks[1], (1, 2, 64, 32), jnp.float32)
        v = rand(ks[2], (1, 2, 64, 32), jnp.float32)
        out1 = fa_pallas(q, k, v, causal=True, block_q=32, block_k=32, interpret=True)
        # perturb the last key/value: only the last query may change
        k2 = k.at[:, :, -1].add(100.0)
        v2 = v.at[:, :, -1].add(100.0)
        out2 = fa_pallas(q, k2, v2, causal=True, block_q=32, block_k=32, interpret=True)
        np.testing.assert_allclose(
            np.asarray(out1[:, :, :-1]), np.asarray(out2[:, :, :-1]), rtol=1e-6, atol=1e-6
        )

    @given(
        sq=st.sampled_from([32, 64, 96]),
        hd=st.sampled_from([16, 32]),
        group=st.sampled_from([1, 2, 4]),
    )
    @settings(max_examples=10, deadline=None)
    def test_property_rows_sum_to_one(self, sq, hd, group):
        """With v = all-ones, output must be exactly ones (softmax rows sum
        to 1) for every causal block pattern."""
        ks = jax.random.split(jax.random.PRNGKey(3), 2)
        q = rand(ks[0], (1, 2 * group, sq, hd), jnp.float32)
        k = rand(ks[1], (1, 2, sq, hd), jnp.float32)
        v = jnp.ones((1, 2, sq, hd), jnp.float32)
        out = fa_pallas(q, k, v, causal=True, block_q=32, block_k=32, interpret=True)
        np.testing.assert_allclose(np.asarray(out), 1.0, rtol=1e-5, atol=1e-5)


class TestRMSNorm:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("shape", [(4, 128), (2, 16, 256), (512,), (3, 5, 7, 64)])
    def test_matches_ref(self, dtype, shape):
        ks = jax.random.split(jax.random.PRNGKey(0), 2)
        x = rand(ks[0], shape, dtype)
        scale = rand(ks[1], (shape[-1],), jnp.float32) + 1.0
        out = rms_pallas(x, scale, interpret=True, block_rows=64)
        expect = ref.rmsnorm_ref(x, scale)
        rtol, atol = TOL[dtype]
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(expect, np.float32),
            rtol=rtol, atol=atol,
        )

    @given(rows=st.integers(1, 64), d=st.sampled_from([32, 128]))
    @settings(max_examples=15, deadline=None)
    def test_property_unit_rms(self, rows, d):
        """With scale=1, output rows have RMS 1 (up to eps)."""
        x = jax.random.normal(jax.random.PRNGKey(rows), (rows, d)) * 5.0
        out = rms_pallas(x, jnp.ones((d,)), interpret=True, block_rows=16)
        rms = np.sqrt(np.mean(np.asarray(out) ** 2, axis=-1))
        np.testing.assert_allclose(rms, 1.0, rtol=1e-3)


class TestSSDChunk:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("b,H,s,P,N,chunk", [
        (2, 2, 64, 16, 8, 16),
        (1, 4, 128, 32, 16, 32),
        (2, 1, 32, 8, 8, 32),   # single chunk
    ])
    def test_matches_ref(self, dtype, b, H, s, P, N, chunk):
        ks = jax.random.split(jax.random.PRNGKey(0), 5)
        x = rand(ks[0], (b, H, s, P), dtype)
        B = rand(ks[1], (b, H, s, N), dtype) * 0.5
        C = rand(ks[2], (b, H, s, N), dtype) * 0.5
        dt = jax.nn.softplus(rand(ks[3], (b, H, s), jnp.float32))
        loga = -jax.nn.softplus(rand(ks[4], (b, H, s), jnp.float32))  # decay < 1
        y_pl, S_pl = ssd_pallas(x, B, C, dt, loga, chunk=chunk, interpret=True)
        y_rf, S_rf = ops.ssd_chunk_scan(x, B, C, dt, loga, chunk=chunk, impl="ref")
        rtol, atol = TOL[dtype]
        np.testing.assert_allclose(
            np.asarray(y_pl, np.float32), np.asarray(y_rf, np.float32),
            rtol=rtol, atol=max(atol, 1e-4),
        )
        np.testing.assert_allclose(
            np.asarray(S_pl), np.asarray(S_rf), rtol=1e-4, atol=1e-4
        )

    def test_matches_model_time_scan(self):
        """SSD chunk kernel == per-timestep recurrence (ground truth)."""
        b, H, s, P, N = 1, 2, 24, 8, 4
        ks = jax.random.split(jax.random.PRNGKey(7), 5)
        x = rand(ks[0], (b, H, s, P), jnp.float32)
        B = rand(ks[1], (b, H, s, N), jnp.float32) * 0.5
        C = rand(ks[2], (b, H, s, N), jnp.float32) * 0.5
        dt = jax.nn.softplus(rand(ks[3], (b, H, s), jnp.float32))
        loga = -jax.nn.softplus(rand(ks[4], (b, H, s), jnp.float32))
        y_pl, S_pl = ssd_pallas(x, B, C, dt, loga, chunk=8, interpret=True)
        # per-step recurrence
        S = np.zeros((b, H, P, N), np.float32)
        ys = np.zeros((b, H, s, P), np.float32)
        xn, Bn, Cn = map(np.asarray, (x, B, C))
        dtn, logan = np.asarray(dt), np.asarray(loga)
        for t in range(s):
            a = np.exp(logan[:, :, t])[..., None, None]
            S = a * S + dtn[:, :, t][..., None, None] * np.einsum(
                "bhp,bhn->bhpn", xn[:, :, t], Bn[:, :, t]
            )
            ys[:, :, t] = np.einsum("bhpn,bhn->bhp", S, Cn[:, :, t])
        np.testing.assert_allclose(np.asarray(y_pl), ys, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(S_pl), S, rtol=1e-4, atol=1e-4)


def _paged_case(H, K, hd, ps, *, b=7, n_layers=2, n_pages=40, max_blocks=10,
                seed=0):
    """Pool and lanes for one paged decode call: lengths 0, ps-1, ps, ps+1,
    the table's maximum, an inactive lane, a live lane with an unallocated
    (-1) block inside its range, and physical page ids shuffled."""
    rng = np.random.default_rng(seed)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    pool = (n_layers, n_pages, ps, K * hd)
    k_pool = rand(ks[0], pool, jnp.bfloat16)
    v_pool = rand(ks[1], pool, jnp.bfloat16)
    q = rand(ks[2], (b, H, hd), jnp.bfloat16)
    lengths = np.array([0, ps - 1, ps, ps + 1, max_blocks * ps - 1, 9, 3 * ps],
                       np.int32)[:b]
    active = np.array([True] * 5 + [False, True])[:b]
    table = np.full((b, max_blocks), -1, np.int32)
    free = list(rng.permutation(n_pages))
    for i in range(b):
        if active[i]:
            for j in range(-(-(int(lengths[i]) + 1) // ps)):
                table[i, j] = free.pop()
    table[6, 1] = -1
    return (q, k_pool, v_pool, jnp.int32(n_layers - 1), jnp.asarray(table),
            jnp.asarray(lengths), jnp.asarray(active))


#: decoder-served configurations (every DecoderLM family)
SERVED = sorted(n for n, c in ARCHS.items() if c.family in ("dense", "moe", "vlm"))


class TestPagedDecode:
    @pytest.mark.parametrize("H,K,hd,ps", [
        (36, 36, 64, 16),    # minicpm-2b: MHA 36 x 64
        (24, 8, 128, 16),    # phi4-mini: GQA 24/8 x 128
        (64, 4, 128, 16),    # qwen3-moe: GQA 64/4 x 128
        (32, 32, 96, 16),    # phi-3-vision: MHA 32 x 96
        (32, 2, 128, 32),    # glm4-9b widths, 32-token pages (4 a block)
    ], ids=["minicpm-2b", "phi4-mini", "qwen3-moe", "phi-3-vision", "glm4-page32"])
    def test_matches_xla_path(self, H, K, hd, ps):
        """The kernel (interpret mode) against the XLA gather path that
        ``attention_decode_paged`` runs off the TPU, lane by lane; inactive
        lanes are zeros on both."""
        args = _paged_case(H, K, hd, ps)
        out = pd_pallas(*args, interpret=True)
        expect = ops.paged_decode_attention(*args, impl="ref")
        assert out.shape == expect.shape == (args[0].shape[0], H * hd)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(expect, np.float32),
            rtol=2e-2, atol=2e-2)
        assert not np.asarray(out[5]).any()

    @pytest.mark.parametrize("arch", SERVED)
    def test_tpu_dispatch_picks_the_kernel(self, arch, monkeypatch):
        """On a TPU backend every served config's pool (16-token pages)
        takes the kernel; pages that are not whole tiles take the XLA path,
        as does every other backend."""
        cfg = ARCHS[arch]
        width = cfg.n_kv_heads * cfg.resolved_head_dim
        assert ops.paged_decode_impl(16, width) == "ref"
        monkeypatch.setattr(ops, "_on_tpu", lambda: True)
        assert ops.paged_decode_impl(16, width) == "pallas"
        assert ops.paged_decode_impl(8, width) == "ref"
        assert ops.paged_decode_impl(16, width + 64) == "ref"


class TestOpsDispatch:
    def test_auto_falls_back_to_ref_on_cpu(self):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = rand(ks[0], (1, 2, 32, 16), jnp.float32)
        k = rand(ks[1], (1, 2, 32, 16), jnp.float32)
        v = rand(ks[2], (1, 2, 32, 16), jnp.float32)
        out = ops.flash_attention(q, k, v)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref.flash_attention_ref(q, k, v)), rtol=1e-6
        )

    def test_pallas_needs_a_tpu(self):
        x = jnp.ones((8, 128), jnp.float32)
        with pytest.raises(RuntimeError, match="needs a TPU"):
            ops.rmsnorm(x, jnp.ones((128,)), impl="pallas")

    def test_interpret_only_when_asked(self):
        x = rand(jax.random.PRNGKey(0), (8, 128), jnp.float32)
        out = ops.rmsnorm(x, jnp.ones((128,)), impl="interpret")
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref.rmsnorm_ref(x, jnp.ones((128,)))),
            rtol=1e-5, atol=1e-5)
