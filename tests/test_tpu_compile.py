"""Compile-only tests for a described TPU v5e chip (nothing runs): the Pallas
kernels at published widths, and the serve engine's paged decode step for
minicpm-2b, which must fit one chip's memory.

The TPU compiler is installed with JAX, so these compile for a chip that is
described, not attached.  The topology is described inside a fixture (only
the worker that runs this file loads the TPU library), and the persistent
compilation cache is off around the compiles (what they would write cannot
be read back without a chip).
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.ssd_chunk import ssd_chunk_scan
from repro.models import ModelOptions, build_model
from repro.serve import EngineConfig
from repro.serve.engine import engine_steps

#: a v5e chip has 16 GiB of HBM; leave room for what XLA reserves
DECODE_BUDGET_BYTES = 15 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_topology_is_v5e(topo):
    assert len(topo.devices) == 4
    assert "v5" in topo.devices[0].device_kind.lower()


@pytest.mark.parametrize("hq,hkv,hd", [
    (36, 36, 64),     # minicpm-2b: MHA, 36 heads x 64
    (24, 8, 128),     # phi4-mini-3.8b: GQA, 24 q / 8 kv heads x 128
], ids=["minicpm-2b", "phi4-mini"])
def test_flash_attention_compiles(one_chip, hq, hkv, hd):
    s = 2048
    compiled = _compile(
        lambda q, k, v: flash_attention(q, k, v, causal=True),
        _on(one_chip, (1, hq, s, hd)), _on(one_chip, (1, hkv, s, hd)),
        _on(one_chip, (1, hkv, s, hd)))
    assert "tpu_custom_call" in compiled.as_text()


def test_rmsnorm_compiles(one_chip):
    d = get_config("minicpm-2b").d_model
    compiled = _compile(lambda x, w: rmsnorm(x, w),
                        _on(one_chip, (4096, d)), _on(one_chip, (d,), jnp.float32))
    assert "tpu_custom_call" in compiled.as_text()


def test_ssd_scan_compiles_at_zamba2_widths(one_chip):
    cfg = get_config("zamba2-2.7b")
    H = cfg.ssm_heads
    P = cfg.ssm_expand * cfg.d_model // H
    N = cfg.ssm_state
    s = 2048
    compiled = _compile(
        lambda x, B, C, dt, la: ssd_chunk_scan(x, B, C, dt, la, chunk=128),
        _on(one_chip, (1, H, s, P)), _on(one_chip, (1, H, s, N)),
        _on(one_chip, (1, H, s, N)), _on(one_chip, (1, H, s), jnp.float32),
        _on(one_chip, (1, H, s), jnp.float32))
    assert "tpu_custom_call" in compiled.as_text()


def test_minicpm_paged_decode_step_fits_one_chip(one_chip):
    """The engine's decode program at chip_smoke's settings: bf16 weights,
    512 pages of 16 tokens, 8 lanes of 64 blocks."""
    cfg = get_config("minicpm-2b")
    model = build_model(cfg, ModelOptions(param_dtype="bfloat16",
                                          compute_dtype="bfloat16"))
    ec = EngineConfig(max_batch=8, page_size=16, n_pages=512, max_blocks=64)
    place = lambda tree: jax.tree.map(
        lambda a: _on(one_chip, a.shape, a.dtype), tree)
    params = place(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    pages = place(jax.eval_shape(lambda: model.init_paged_cache(
        ec.n_pages, ec.page_size)))
    decode, _ = engine_steps(model)
    compiled = decode.lower(
        params, pages,
        _on(one_chip, (ec.max_batch, ec.max_blocks), jnp.int32),
        _on(one_chip, (ec.max_batch,), jnp.int32),
        _on(one_chip, (ec.max_batch, 1), jnp.int32),
        _on(one_chip, (ec.max_batch,), jnp.bool_),
    ).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < DECODE_BUDGET_BYTES, (mem.argument_size_in_bytes,
                                        mem.temp_size_in_bytes)
