"""The serve cell: the program's ``ServeEngine`` over one chip.

Set-up makes the weights from the seed, builds the engine and lets
``ServeEngine.run`` warm up the decode step and the prefill buckets of the
cell's own prompts.  The window opens when warm-up ends (the engine's clock
zero) and the requests' arrival times count from there; it closes at the
end of the first decode tick that ends after ``seconds``.  The backlog's
requests all carry ``seconds`` as deadline, so ``run`` returns soon after.

With a trace, the profiler records that window; the benchmark opens host
spans around the engine's calls so that idle gaps can be named.  After the
window, the engine's pages are freed and the plain reference checks a
sample of the finished requests (``check.py``).
"""

from __future__ import annotations

import dataclasses
import shutil
import tempfile
import time

from chipbench import cells, check, trace as tr


@dataclasses.dataclass
class Run:
    """What the metric readers read."""

    cfg: object                 # the program's ArchConfig
    seconds: float
    setup_s: float
    results: list               # GenerationResult, by request id
    stats: object               # EngineStats
    close_s: float              # engine clock at the window's close
    trace: tr.Summary | None
    device_kind: str


def _spanned(name, fn):
    import jax

    def call(*args, **kwargs):
        with jax.profiler.TraceAnnotation(name):
            return fn(*args, **kwargs)

    return call


def make_engine(model, params, engine_cfg, seconds: float, trace_dir):
    """The program's engine, with the benchmark's window hooks and spans."""
    import jax
    from repro.serve import ServeEngine

    class Engine(ServeEngine):
        traced_from = None  # perf_counter when the trace started
        close_s = None      # engine clock when the traced window closed

        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            self._decode = _spanned("program.decode", self._decode)
            self._prefill = _spanned("program.prefill", self._prefill)

        def _warmup(self, requests):
            super()._warmup(requests)
            if trace_dir:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                self.traced_from = time.perf_counter()
                jax.profiler.start_trace(trace_dir, profiler_options=opts)

        def _admit_arrivals(self):
            with jax.profiler.TraceAnnotation("engine.admit"):
                super()._admit_arrivals()

        def _decode_tick(self):
            with jax.profiler.TraceAnnotation("engine.tick"):
                super()._decode_tick()
            if self.close_s is None and self.now() >= seconds:
                self.close()

        def close(self):
            if self.close_s is None:
                self.close_s = self.now()
                if trace_dir:
                    jax.profiler.stop_trace()

    return Engine(model, params, engine_cfg)


def peak_memory() -> int:
    import jax

    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.local_devices())


def run(cell: cells.Cell, seed: int, seconds: float, trace: bool,
        t_start: float) -> dict:
    """One run of a serve cell; returns the result line's dict."""
    import jax
    from repro.models import ModelOptions, build_model
    from repro.serve import EngineConfig

    from chipbench import traffic as gen
    from chipbench.weights import make_params

    cfg = cells.arch_config(cell.conf)
    model = build_model(cfg, ModelOptions(param_dtype="bfloat16",
                                          compute_dtype="bfloat16"))
    params = jax.block_until_ready(make_params(cfg, seed))
    requests = gen.requests(cell.traffic, cfg.vocab, seed, seconds)
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    try:
        engine = make_engine(model, params, EngineConfig(**cell.traffic["engine"]),
                             seconds, trace_dir)
        results, stats = engine.run(requests)
        engine.close()
        setup_s = engine._t0 - t_start   # the engine's clock zero opens the window
        summary = None
        if trace:
            window_s = engine._t0 + engine.close_s - engine.traced_from
            summary = tr.reduce(tr.extract(trace_dir), window_s * 1e9)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    device = {"platform": jax.devices()[0].platform,
              "kind": jax.devices()[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak_memory()}
    close_s = engine.close_s
    del engine  # frees the page pool before the reference runs

    run_ = Run(cfg=cfg, seconds=seconds, setup_s=setup_s,
               results=results, stats=stats, close_s=close_s, trace=summary,
               device_kind=device["kind"])
    checks = verify(cell, params, requests, results, seed)
    metrics = cells.read_metrics(cell.per_layer if trace else cell.end_to_end, run_)
    # the unadmitted backlog was never due; a request admitted is served
    attempted = sum(1 for r in results if r.tokens)
    out = {
        "correct": all(c["pass"] for c in checks.values()),
        "attempted": attempted, "failed": 0,
        "metrics": metrics, "device": device,
    }
    if summary is not None:
        out["device"].update(busy_s=summary.busy_s, window_s=summary.window_s)
        out["breakdown"] = {"device_ops": [list(x) for x in summary.device_ops],
                            "idle_gaps": [list(x) for x in summary.idle_gaps]}
    out["checks"] = checks
    return out


def _check(value, limit, rule: str) -> dict:
    ok = limit is not None and (value <= limit if rule == "<=" else value >= limit)
    return {"value": value, "limit": limit, "rule": rule, "pass": bool(ok)}


def verify(cell, params, requests, results, seed: int, control: bool = False) -> dict:
    """The numbers compared, each with its limit."""
    conf, spec = cell.conf, cell.traffic
    eng = spec["engine"]
    max_new = {r.request_id: r.max_new_tokens for r in requests}
    chosen = check.sample(results, seed, spec["check"]["sample_tokens"])
    checks = {
        "bad_results": _check(check.bad_results(results, max_new, conf["vocab_size"]),
                              0, "<="),
        "sampled_requests": _check(len(chosen), 1, ">="),
    }
    if chosen:
        gaps = check.logit_gaps(
            params, conf, chosen, seq_len=eng["max_blocks"] * eng["page_size"],
            n_out=spec["output_tokens"]["max"], control=control)
        checks["max_logit_gap"] = _check(gaps["max_logit_gap"],
                                         spec["check"]["max_logit_gap"], "<=")
        checks["sampled_tokens"] = _check(gaps["tokens"], 1, ">=")
        if control:
            checks["control_max_logit_gap"] = _check(
                gaps["control_max_logit_gap"], spec["check"]["max_logit_gap"], "<=")
    return checks

