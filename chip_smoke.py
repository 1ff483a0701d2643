#!/usr/bin/env python3
"""Chip smoke test: drive the system's main paths once on a TPU at
minicpm-2b's published widths (random weights from a fixed seed), and check
what comes out.

    python chip_smoke.py               # one chip: serve 16 requests
    python chip_smoke.py --four-chips  # four chips: train on the Arnold
                                       # mesh and on the plain mesh

* Default (one chip): ``ServeEngine`` serves 16 seeded requests (prompts of
  64-512 tokens, 32-64 new tokens) with bfloat16 parameters and a pool of
  512 pages x 16 tokens.  Every request must end with its ``max_new_tokens``
  tokens, all in the vocabulary, and every page must be free afterwards.
  For one prompt, the paged prefill's last-position logits must match the
  plain (non-paged) forward within ``PREFILL_LOGIT_RTOL``.
* ``--four-chips``: only the training path.  ``repro.launch.train`` trains
  minicpm-2b at global batch 4 x 1024 for a few steps, on the ``--arnold``
  mesh and on the plain (data=2, model=2) mesh, from the same seed.  Losses
  must be finite and the two runs must agree within ``LOSS_ATOL``.

Earlier lines print phase times, compile time and peak device memory.  The
last line is ``{"ok": true, "device": {...}}`` only when every phase passed
on a TPU; otherwise the script exits non-zero and prints no such line.  The
phases are functions of their config, so ``tests/test_chip_smoke.py``
rehearses them on the CPU at reduced sizes.
"""

from __future__ import annotations

import argparse
import json
import math
import pathlib
import shutil
import sys
import time
import traceback

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))
#: checkpoints of the training phase (deleted after each run)
OUT_DIR = ROOT / "chip_smoke_out"

#: bf16 tolerance of the paged prefill against the plain forward: max
#: |difference| of the last position's logits over max |plain logit|
PREFILL_LOGIT_RTOL = 5e-2
#: largest |loss difference| allowed between the Arnold and the plain mesh
LOSS_ATOL = 2e-2

SERVE_PROMPT_LENS = (64, 128, 200, 384, 512)
SERVE_NEW_TOKENS = (32, 48, 64)


def peak_bytes() -> list[int]:
    """``peak_bytes_in_use`` of every device, where the backend reports it."""
    import jax

    return [(d.memory_stats() or {}).get("peak_bytes_in_use", -1)
            for d in jax.devices()]


def serve_phase(cfg, engine_cfg, *, n_requests: int = 16,
                prompt_lens=SERVE_PROMPT_LENS, new_tokens=SERVE_NEW_TOKENS,
                seed: int = 0) -> dict:
    """Serve ``n_requests`` seeded requests of model ``cfg`` through
    ``ServeEngine`` and check them; returns the phase's numbers."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import ModelOptions, build_model
    from repro.serve import (LengthMixture, LoadGenConfig, ServeEngine,
                             generate_requests)

    model = build_model(cfg, ModelOptions(param_dtype="bfloat16",
                                          compute_dtype="bfloat16"))
    t0 = time.perf_counter()
    params = jax.block_until_ready(jax.jit(model.init)(jax.random.PRNGKey(seed)))
    init_s = time.perf_counter() - t0

    requests = generate_requests(LoadGenConfig(
        seed=seed, n_requests=n_requests, rate_rps=100.0,
        prompt_mix=LengthMixture(tuple((n, 1.0) for n in prompt_lens)),
        response_mix=LengthMixture(tuple((n, 1.0) for n in new_tokens)),
        vocab=cfg.vocab,
    ))
    engine = ServeEngine(model, params, engine_cfg)
    t0 = time.perf_counter()
    results, stats = engine.run(requests)
    run_s = time.perf_counter() - t0

    assert len(results) == len(requests), (len(results), len(requests))
    for req, res in zip(requests, results):
        assert res.request_id == req.request_id
        assert res.finish_reason == "length", (req.request_id, res.finish_reason)
        assert len(res.tokens) == req.max_new_tokens, (
            req.request_id, len(res.tokens), req.max_new_tokens)
        assert all(0 <= t < cfg.vocab for t in res.tokens), req.request_id
    engine.cache.allocator.assert_all_free()

    # the paged prefill (prompt padded to its bucket, KV scattered into the
    # engine's pages) against the plain forward over the same tokens, for
    # the prompt its bucket pads most, so the comparison covers padding
    prompt = max((r.prompt for r in requests),
                 key=lambda p: engine_cfg.prefill_bucket(len(p)) - len(p))
    n = len(prompt)
    padded = np.zeros((1, engine_cfg.prefill_bucket(n)), np.int32)
    padded[0, :n] = prompt
    cache = engine.cache
    cache.ensure_capacity(0, n)
    paged, cache.pages = jax.jit(model.prefill_paged, donate_argnums=(1,))(
        params, cache.pages, cache.lane_table(0), jnp.int32(n), jnp.asarray(padded))
    cache.release(0)
    cache.allocator.assert_all_free()
    plain, _ = jax.jit(model.forward)(params, {"tokens": jnp.asarray(padded[:, :n])})
    a = np.asarray(paged[0, n - 1, :cfg.vocab], np.float32)
    b = np.asarray(plain[0, n - 1, :cfg.vocab], np.float32)
    rel = float(np.max(np.abs(a - b)) / max(float(np.max(np.abs(b))), 1e-30))
    assert np.isfinite(a).all() and np.isfinite(b).all()
    assert rel <= PREFILL_LOGIT_RTOL, (rel, PREFILL_LOGIT_RTOL)

    return {
        "requests": len(results),
        "tokens": stats.tokens_generated,
        "init_s": init_s,
        "warmup_incl_compile_s": run_s - stats.elapsed_s,
        "window_s": stats.elapsed_s,
        "decode_steps": stats.decode_steps,
        "prefill_prompt_len": n,
        "prefill_logit_rel_err": rel,
        "prefill_argmax_equal": bool(np.argmax(a) == np.argmax(b)),
    }


def train_phase(arch: str, *, full: bool, steps: int = 6, global_batch: int = 4,
                seq_len: int = 1024, devices: int = 4,
                out_dir: pathlib.Path = OUT_DIR) -> dict:
    """Train ``arch`` through ``repro.launch.train`` on the Arnold mesh and
    on the plain 2x2 mesh from the same seed; check and return the losses."""
    from repro.launch import train

    losses, times = {}, {}
    for name, extra in (("arnold", ["--arnold"]), ("plain", [])):
        ckpt = out_dir / f"ckpt_{name}"
        shutil.rmtree(ckpt, ignore_errors=True)
        argv = ["--arch", arch, "--steps", str(steps),
                "--global-batch", str(global_batch), "--seq-len", str(seq_len),
                "--devices", str(devices), "--mesh-shape", "2x2",
                "--log-every", "1", "--ckpt-every", str(steps),
                "--ckpt-dir", str(ckpt), "--seed", "0"]
        argv += ["--full"] if full else []
        t0 = time.perf_counter()
        losses[name] = train.run(argv + extra)
        times[name] = time.perf_counter() - t0
        shutil.rmtree(ckpt, ignore_errors=True)
        print(f"train[{name}]: {times[name]:.1f} s, losses {losses[name]}",
              flush=True)

    for name, ls in losses.items():
        assert len(ls) == steps, (name, ls)
        assert all(math.isfinite(x) for x in ls), (name, ls)
    diff = max(abs(a - b) for a, b in zip(losses["arnold"], losses["plain"]))
    assert diff <= LOSS_ATOL, (diff, losses)
    return {"losses": losses, "max_loss_diff": diff, "run_s": times}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip training phase")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    need = 4 if args.four_chips else 1
    if platform != "tpu" or len(devices) < need:
        print(f"chip_smoke: needs {need} TPU chip(s); JAX sees "
              f"{len(devices)} {platform} device(s)", file=sys.stderr)
        return 1
    print(f"device: {devices[0].device_kind} x {len(devices)}", flush=True)

    try:
        from repro.configs import get_config
        from repro.launch.compile_cache import enable_compile_cache
        from repro.serve import EngineConfig

        print(f"compile cache: {enable_compile_cache()}", flush=True)
        cfg = get_config("minicpm-2b")
        t0 = time.perf_counter()
        if args.four_chips:
            phase, out = "train", train_phase(cfg.name, full=True)
        else:
            phase, out = "serve", serve_phase(cfg, EngineConfig(
                max_batch=8, page_size=16, n_pages=512, max_blocks=64))
        out["phase_s"] = time.perf_counter() - t0
        out["peak_bytes_in_use"] = peak_bytes()
        print(f"{phase} phase: {json.dumps(out)}", flush=True)
    except Exception:  # noqa: BLE001 - any failed phase fails the run
        traceback.print_exc()
        return 1

    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
