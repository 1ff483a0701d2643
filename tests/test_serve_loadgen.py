"""Load-generator tests (DESIGN.md §7.3): seeded determinism, Poisson
arrival statistics, mixture sampling, report math, and (slow tier) a full
load-gen benchmark run through a real engine."""

import dataclasses

import numpy as np
import pytest

from repro.serve import (
    GenerationResult,
    LengthMixture,
    LoadGenConfig,
    ServeReport,
    generate_requests,
)
from repro.serve.engine import EngineStats


class TestGenerateRequests:
    def test_same_seed_same_workload(self):
        a = generate_requests(LoadGenConfig(seed=42, n_requests=20))
        b = generate_requests(LoadGenConfig(seed=42, n_requests=20))
        assert a == b

    def test_different_seed_differs(self):
        a = generate_requests(LoadGenConfig(seed=0, n_requests=20))
        b = generate_requests(LoadGenConfig(seed=1, n_requests=20))
        assert a != b

    def test_poisson_arrivals_monotone_and_rate_shaped(self):
        cfg = LoadGenConfig(seed=0, n_requests=400, rate_rps=50.0)
        reqs = generate_requests(cfg)
        arr = np.array([r.arrival_s for r in reqs])
        assert (np.diff(arr) >= 0).all() and arr[0] > 0
        mean_gap = float(np.diff(np.concatenate([[0.0], arr])).mean())
        assert 1 / 50.0 / 2 < mean_gap < 1 / 50.0 * 2

    def test_lengths_come_from_mixtures(self):
        cfg = LoadGenConfig(
            seed=3, n_requests=50,
            prompt_mix=LengthMixture(((4, 1.0), (6, 1.0))),
            response_mix=LengthMixture(((2, 1.0),)),
        )
        reqs = generate_requests(cfg)
        assert {len(r.prompt) for r in reqs} <= {4, 6}
        assert {r.max_new_tokens for r in reqs} == {2}

    def test_tokens_within_vocab(self):
        reqs = generate_requests(LoadGenConfig(seed=0, n_requests=10, vocab=32))
        assert all(0 <= t < 32 for r in reqs for t in r.prompt)

    def test_bad_mixture_rejected(self):
        with pytest.raises(ValueError):
            LengthMixture(())
        with pytest.raises(ValueError):
            LengthMixture(((0, 1.0),))


def _result(rid, arrival, admitted, times):
    return GenerationResult(
        request_id=rid, prompt=(1, 2), tokens=[0] * len(times),
        arrival_s=arrival, admitted_s=admitted, finished_s=times[-1],
        token_times_s=list(times),
    )


class TestServeReport:
    def test_metrics_from_synthetic_run(self):
        # two requests: token cadence 10 ms and 20 ms, TTFT 5 ms and 30 ms
        results = [
            _result(0, 0.0, 0.001, [0.005, 0.015, 0.025]),
            _result(1, 0.01, 0.02, [0.04, 0.06]),
        ]
        stats = EngineStats(decode_steps=3, prefills=2, tokens_generated=5,
                            elapsed_s=0.1, occupancy=[1, 2, 1])
        report = ServeReport.from_run(results, stats)
        assert report.total_tokens == 5
        assert report.tokens_per_s == pytest.approx(50.0)
        assert report.goodput_tokens_per_s == pytest.approx(50.0)
        assert report.ttft_p50_ms == pytest.approx(17.5)  # median of 5, 30
        assert report.per_token_p50_ms == pytest.approx(10.0)  # 10,10,20 ms
        assert report.e2e_p50_ms == pytest.approx(37.5)  # 25 ms, 50 ms
        assert report.mean_batch_occupancy == pytest.approx(4 / 3)

    def test_timed_out_request_is_not_completed(self):
        # request 1 was evicted past its deadline after 2 of its tokens
        results = [
            _result(0, 0.0, 0.001, [0.005, 0.015, 0.025]),
            dataclasses.replace(_result(1, 0.01, 0.02, [0.04, 0.06]),
                                finish_reason="timeout"),
        ]
        stats = EngineStats(decode_steps=3, prefills=2, tokens_generated=5,
                            timeouts=1, elapsed_s=0.1, occupancy=[1, 2, 1])
        report = ServeReport.from_run(results, stats)
        assert report.n_requests == 2 and report.n_completed == 1
        assert report.total_tokens == 5
        assert report.tokens_per_s == pytest.approx(50.0)
        assert report.goodput_tokens_per_s == pytest.approx(30.0)

    def test_report_round_trips_to_dict(self):
        report = ServeReport.from_run([], EngineStats())
        d = report.to_dict()
        assert set(d) == {f.name for f in dataclasses.fields(ServeReport)}
        assert "tok/s" in report.summary()


@pytest.mark.slow
def test_loadgen_benchmark_end_to_end():
    """Full seeded load-gen benchmark against a real engine (slow tier):
    Poisson arrivals admitted mid-flight, report populated, pages freed."""
    import jax

    from repro.configs import get_config
    from repro.models import ModelOptions, build_model
    from repro.serve import EngineConfig, ServeEngine, run_benchmark

    cfg = get_config("glm4-9b").reduced()
    model = build_model(cfg, ModelOptions(compute_dtype="float32", remat=False))
    params = model.init(jax.random.PRNGKey(0))
    engine = ServeEngine(model, params, EngineConfig(
        max_batch=4, page_size=8, n_pages=48, max_blocks=8))
    load = LoadGenConfig(seed=0, n_requests=12, rate_rps=100.0, vocab=cfg.vocab)
    report = run_benchmark(engine, generate_requests(load))

    assert report.n_completed == 12
    assert report.total_tokens == sum(
        r.max_new_tokens for r in generate_requests(load))
    assert report.tokens_per_s > 0
    assert report.per_token_p99_ms >= report.per_token_p50_ms >= 0
    assert report.e2e_p99_ms >= report.e2e_p50_ms > 0
    assert 1.0 <= report.mean_batch_occupancy <= 4.0
    engine.cache.allocator.assert_all_free()
