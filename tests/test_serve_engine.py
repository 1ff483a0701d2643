"""Continuous-batching engine tests (DESIGN.md §7.2): fast smoke on the
default tier, batched-vs-sequential token equivalence, mid-flight admission
under lane pressure, EOS early stop, page recycling, and the engine's host
spans and counters."""

import gc
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import ModelOptions, build_model
from repro.serve import EngineConfig, GenerationRequest, ServeEngine

CFG = EngineConfig(max_batch=4, page_size=8, n_pages=32, max_blocks=4)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = get_config("glm4-9b").reduced()
    model = build_model(cfg, ModelOptions(compute_dtype="float32", remat=False))
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _requests(cfg, n, seed=0, max_new=(4, 8), prompt_len=(3, 10)):
    rng = np.random.default_rng(seed)
    return [
        GenerationRequest(
            request_id=i,
            prompt=tuple(int(t) for t in rng.integers(
                0, cfg.vocab, int(rng.integers(*prompt_len)))),
            max_new_tokens=int(rng.integers(*max_new)),
        )
        for i in range(n)
    ]


def test_engine_smoke(tiny_model):
    """Fast default-tier smoke: <= 8 requests, tiny config."""
    cfg, model, params = tiny_model
    engine = ServeEngine(model, params, CFG)
    requests = _requests(cfg, 6)
    results, stats = engine.run(requests)

    assert len(results) == 6
    for res, req in zip(results, requests):
        assert res.request_id == req.request_id
        assert len(res.tokens) == req.max_new_tokens
        assert all(0 <= t < cfg.vocab for t in res.tokens)
        assert len(res.token_times_s) == len(res.tokens)
        assert res.token_times_s == sorted(res.token_times_s)
        assert res.arrival_s <= res.admitted_s <= res.finished_s
    # exact token accounting: everything counted was generated in-window
    assert stats.tokens_generated == sum(r.max_new_tokens for r in requests)
    assert stats.elapsed_s > 0 and stats.tokens_per_s > 0
    # pages recycled: allocator ends fully free
    engine.cache.allocator.assert_all_free()
    assert engine.cache.allocator.n_free == CFG.n_pages


def _sequential_reference(model, params, prompt, n_tokens):
    """Greedy decode one sequence at a time via the dense cache path."""
    step = jax.jit(model.decode_step, donate_argnums=(1,))
    cache = model.init_cache(1, 32)
    logits = None
    for t in prompt:
        logits, cache = step(params, cache, jnp.full((1, 1), t, jnp.int32))
    tokens = [int(jnp.argmax(logits[0, -1]))]
    while len(tokens) < n_tokens:
        logits, cache = step(
            params, cache, jnp.full((1, 1), tokens[-1], jnp.int32))
        tokens.append(int(jnp.argmax(logits[0, -1])))
    return tokens


def test_continuous_batching_matches_sequential_decode(tiny_model):
    """The paged batched engine must produce exactly the tokens the dense
    one-at-a-time decode produces -- per-lane math is batch-invariant."""
    cfg, model, params = tiny_model
    engine = ServeEngine(model, params, EngineConfig(
        max_batch=3, page_size=8, n_pages=24, max_blocks=4))
    requests = _requests(cfg, 4, seed=7, max_new=(3, 7))
    results, _ = engine.run(requests)
    for res in results:
        ref = _sequential_reference(
            model, params, list(res.prompt), len(res.tokens))
        assert res.tokens == ref, f"request {res.request_id} diverged"


def test_mid_flight_admission_under_lane_pressure(tiny_model):
    """More requests than lanes: later requests join as earlier ones evict,
    never exceeding max_batch, and all pages still recycle."""
    cfg, model, params = tiny_model
    config = EngineConfig(max_batch=2, page_size=8, n_pages=16, max_blocks=4)
    engine = ServeEngine(model, params, config)
    results, stats = engine.run(_requests(cfg, 5, seed=3))
    assert len(results) == 5
    assert max(stats.occupancy) <= 2
    assert stats.peak_pages_in_use <= config.n_pages
    engine.cache.allocator.assert_all_free()


def test_oversized_request_rejected(tiny_model):
    cfg, model, params = tiny_model
    engine = ServeEngine(model, params, CFG)  # max context 32
    with pytest.raises(ValueError, match="max context"):
        engine.submit(GenerationRequest(
            request_id=0, prompt=(1,) * 20, max_new_tokens=20))
    # fits the per-lane context but not the whole pool: reject at submit
    # rather than hang in admission forever
    small_pool = ServeEngine(model, params, EngineConfig(
        max_batch=2, page_size=8, n_pages=3, max_blocks=4))
    with pytest.raises(ValueError, match="never be admitted"):
        small_pool.submit(GenerationRequest(
            request_id=0, prompt=(1,) * 16, max_new_tokens=16))


def test_eos_stops_early(tiny_model):
    cfg, model, params = tiny_model
    probe = ServeEngine(model, params, CFG)
    [free_run], _ = probe.run(_requests(cfg, 1, seed=1, max_new=(6, 7)))
    assert len(free_run.tokens) >= 3

    eos = free_run.tokens[2]  # force a stop at the third generated token
    engine = ServeEngine(model, params, CFG)
    req = GenerationRequest(
        request_id=0, prompt=free_run.prompt,
        max_new_tokens=len(free_run.tokens), eos_id=eos)
    [res], _ = engine.run([req])
    assert res.finish_reason == "eos"
    assert res.tokens == free_run.tokens[:3]
    engine.cache.allocator.assert_all_free()


def test_prefill_only_request(tiny_model):
    """max_new_tokens=1 finishes at prefill without any decode tick."""
    cfg, model, params = tiny_model
    engine = ServeEngine(model, params, CFG)
    [res], stats = engine.run([GenerationRequest(
        request_id=0, prompt=(5, 6, 7), max_new_tokens=1)])
    assert len(res.tokens) == 1
    assert stats.prefills == 1 and stats.decode_steps == 0
    engine.cache.allocator.assert_all_free()


def test_deadline_timeout_mid_decode(tiny_model):
    """A lane past its deadline is evicted with reason="timeout", keeps its
    partial tokens, and gives every page back the same run."""
    cfg, model, params = tiny_model
    engine = ServeEngine(model, params, CFG)
    doomed = GenerationRequest(request_id=0, prompt=(1, 2, 3),
                               max_new_tokens=20, deadline_s=1e-6)
    healthy = GenerationRequest(request_id=1, prompt=(4, 5, 6),
                                max_new_tokens=4)
    results, stats = engine.run([doomed, healthy])

    assert stats.timeouts == 1
    by_id = {r.request_id: r for r in results}
    t = by_id[0]
    assert t.finish_reason == "timeout"
    assert 1 <= t.n_generated < doomed.max_new_tokens  # admitted, cut short
    assert by_id[1].finish_reason == "length"
    assert by_id[1].n_generated == 4
    engine.cache.allocator.assert_all_free()
    assert engine.cache.allocator.n_free == CFG.n_pages


def test_deadline_timeout_while_queued(tiny_model):
    """A request that can never be admitted before its deadline resolves
    with zero tokens instead of waiting forever; the freed commitment is
    reusable the same tick."""
    cfg, model, params = tiny_model
    blockers = [
        GenerationRequest(request_id=i, prompt=(1, 2, 3), max_new_tokens=20)
        for i in range(CFG.max_batch)  # hold every lane for many ticks
    ]
    queued = GenerationRequest(request_id=CFG.max_batch, prompt=(7, 8),
                               max_new_tokens=4, deadline_s=1e-6)
    engine = ServeEngine(model, params, CFG)
    results, stats = engine.run(blockers + [queued])

    assert stats.timeouts == 1
    timed_out = [r for r in results if r.finish_reason == "timeout"]
    assert len(timed_out) == 1
    assert timed_out[0].request_id == queued.request_id
    assert timed_out[0].n_generated == 0  # never admitted
    for r in results:
        if r.request_id != queued.request_id:
            assert r.finish_reason == "length" and r.n_generated == 20
    engine.cache.allocator.assert_all_free()


def test_deadline_must_be_positive():
    with pytest.raises(ValueError):
        GenerationRequest(request_id=0, prompt=(1,), max_new_tokens=1,
                          deadline_s=0.0)


# ------------------------------------------------------ host spans, counters
TICK_LEAVES = ("engine.tick.prepare", "engine.tick.dispatch",
               "engine.tick.wait", "engine.tick.commit")
ADMIT_LEAVES = ("engine.admit.prefill", "engine.admit.wait")
LEAVES = ("engine.evict",) + ADMIT_LEAVES + TICK_LEAVES


def test_leaf_spans_count_ticks_and_admissions(tiny_model):
    cfg, model, params = tiny_model
    engine = ServeEngine(model, params, CFG)
    _, stats = engine.run(_requests(cfg, 6, seed=11))
    assert stats.decode_steps > 0 and stats.prefills == 6
    for name in TICK_LEAVES:
        assert stats.host[name][0] == stats.decode_steps, name
    for name in ADMIT_LEAVES:
        assert stats.host[name][0] == stats.prefills, name
    # one eviction pass per turn of the loop, at least one per tick
    assert stats.host["engine.evict"][0] >= stats.decode_steps


def test_leaf_spans_sum_within_the_window(tiny_model):
    cfg, model, params = tiny_model
    engine = ServeEngine(model, params, CFG)
    _, stats = engine.run(_requests(cfg, 6, seed=12))
    assert set(LEAVES) <= set(stats.host)
    leaves = [stats.host[name][1] for name in LEAVES]
    assert all(s > 0 for s in leaves)
    assert sum(leaves) <= stats.elapsed_s


def test_compiles_counted_in_the_window(tiny_model):
    """A warmed run compiles nothing after its clock starts; one whose
    warm-up skips the prefill buckets compiles them inside the run."""
    cfg, model, params = tiny_model
    requests = _requests(cfg, 4, seed=13)
    _, warmed = ServeEngine(model, params, CFG).run(requests)
    assert warmed.compiles == 0 and warmed.compile_s == 0.0

    cold = ServeEngine(model, params, CFG)
    cold._warmup = lambda reqs: ServeEngine._warmup(cold, [])  # decode only
    _, stats = cold.run(_requests(cfg, 4, seed=13))
    assert stats.compiles >= 1 and stats.compile_s > 0
    # the compile happened inside the prefill dispatch
    assert stats.host["engine.admit.prefill"][1] >= stats.compile_s


def test_forced_collection_counts_under_engine_gc(tiny_model):
    cfg, model, params = tiny_model
    engine = ServeEngine(model, params, CFG)
    evict = engine._evict_timeouts

    def evict_and_collect():
        gc.collect()
        evict()

    engine._evict_timeouts = evict_and_collect
    _, stats = engine.run(_requests(cfg, 4, seed=14))
    calls, seconds = stats.host["engine.gc"]
    # gc.collect() runs one collection per generation it collects
    assert calls >= stats.host["engine.evict"][0]
    assert 0 < seconds <= stats.elapsed_s


def _listeners():
    from jax._src import monitoring

    return list(gc.callbacks), list(monitoring.get_event_time_span_listeners())


def test_hooks_removed_after_run_and_after_a_raise(tiny_model):
    cfg, model, params = tiny_model
    before = _listeners()
    ServeEngine(model, params, CFG).run(_requests(cfg, 2, seed=15))
    assert _listeners() == before

    engine = ServeEngine(model, params, CFG)
    inside = []

    def broken_tick():
        inside.append(_listeners())
        raise RuntimeError("tick failed")

    engine._decode_tick = broken_tick
    with pytest.raises(RuntimeError, match="tick failed"):
        engine.run(_requests(cfg, 2, seed=15))
    hooks, listeners = inside[0]
    assert len(hooks) == len(before[0]) + 1
    assert len(listeners) == len(before[1]) + 1
    assert _listeners() == before


def test_spans_land_on_the_profilers_host_plane(tiny_model, tmp_path):
    from jax.profiler import ProfileData

    cfg, model, params = tiny_model
    engine = ServeEngine(model, params, CFG)
    requests = _requests(cfg, 4, seed=16)
    with jax.profiler.trace(str(tmp_path)):
        engine.run(requests)
    [path] = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    names = {e.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events}
    assert set(LEAVES) <= names
