"""Continuous-batching generation engine (DESIGN.md §7.2).

One engine drives one model replica.  It keeps a fixed set of ``max_batch``
*lanes*; every decode step runs all lanes through one jitted
``decode_step_paged`` call (inactive lanes masked), so requests join and
leave the batch mid-flight with no recompilation:

* **admission** -- a request is admitted when a lane is free *and* its
  worst-case page demand (``ceil((prompt + max_new) / page_size)``) fits in
  the uncommitted page pool.  Pages are committed logically at admission but
  allocated physically on demand (prefill pages up front, one page whenever
  decode crosses a page boundary), so the free list can never run dry
  mid-flight -- the deadlock-free variant of vLLM-style paging.
* **prefill** -- each admitted prompt runs one ``prefill_paged`` call,
  padded to a power-of-two bucket to bound jit retraces; its KV is scattered
  straight into the lane's pages and the first output token is sampled from
  the last prompt position.
* **decode** -- one batched greedy step per tick over every active lane,
  each lane at its own length (per-lane RoPE positions and masks).
* **eviction** -- a lane finishing (length budget, EOS, or a blown
  ``deadline_s``) releases its pages back to the free list the same tick,
  and the lane is immediately re-admittable.  Deadline eviction also covers
  requests still queued: they resolve with ``finish_reason="timeout"`` and
  zero tokens instead of waiting forever.

Per-lane computation is independent of batch composition, so the engine
produces token-for-token the same output as one-at-a-time dense decode --
the equivalence property tests pin down, and what makes seeded load-gen
runs reproducible even though batching is timing-dependent.

Token accounting: the engine clock starts *after* jit warm-up
(:meth:`ServeEngine.run` warms the decode step and every prefill bucket it
will need), and every counted token is timestamped inside the measured
window -- fixing the warm-up-token bug of the old fixed-batch demo.

Host time inside the window is split by leaf spans (:mod:`repro.serve.spans`)
that do not overlap: ``engine.evict``; per admission ``engine.admit.prefill``
(page commitment, padding, prefill dispatch) and ``engine.admit.wait`` (the
first token); per tick ``engine.tick.prepare`` (page growth, inputs, the
block-table upload), ``engine.tick.dispatch`` (the decode call with its
host-to-device copies and any compile), ``engine.tick.wait`` (the output)
and ``engine.tick.commit`` (stats, lanes, finishing).  ``EngineStats.host``
holds their calls and seconds, beside the compiles and the ``engine.gc``
pauses counted in the same window.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.serve.kv_cache import PagedCacheConfig, PagedKVCache
from repro.serve.request import GenerationRequest, GenerationResult
from repro.serve.spans import Spans


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Capacity knobs; defaults suit CPU smoke runs of reduced configs."""

    max_batch: int = 8          # lanes = max concurrent sequences
    page_size: int = 16         # tokens per KV page
    n_pages: int = 96           # shared page pool (all lanes, per layer)
    max_blocks: int = 8         # block-table length; max ctx = blocks * page
    min_prefill_bucket: int = 8

    def cache_config(self) -> PagedCacheConfig:
        return PagedCacheConfig(
            n_pages=self.n_pages, page_size=self.page_size,
            max_batch=self.max_batch, max_blocks=self.max_blocks,
        )

    def prefill_bucket(self, n: int) -> int:
        """Smallest power-of-two bucket >= n (bounds jit retraces)."""
        b = self.min_prefill_bucket
        while b < n:
            b *= 2
        return b


@dataclasses.dataclass
class EngineStats:
    """Counters over the measured window (clock starts after warm-up)."""

    decode_steps: int = 0
    prefills: int = 0
    tokens_generated: int = 0   # every token timestamped inside the window
    timeouts: int = 0           # requests evicted past their deadline_s
    elapsed_s: float = 0.0
    occupancy: list[int] = dataclasses.field(default_factory=list)
    peak_pages_in_use: int = 0
    # host spans (name -> [calls, seconds]), XLA compiles and their seconds,
    # from the clock's start to the end of run()
    host: dict[str, list] = dataclasses.field(default_factory=dict)
    compiles: int = 0
    compile_s: float = 0.0

    @property
    def tokens_per_s(self) -> float:
        return self.tokens_generated / self.elapsed_s if self.elapsed_s else 0.0

    @property
    def mean_occupancy(self) -> float:
        return float(np.mean(self.occupancy)) if self.occupancy else 0.0


@dataclasses.dataclass
class _Lane:
    request: GenerationRequest
    admitted_s: float
    length: int                 # tokens materialized in the KV cache
    last_token: int             # fed to the next decode step
    tokens: list[int]
    token_times: list[float]
    committed_blocks: int


def engine_steps(model):
    """The engine's two device programs for ``model``, jitted with the page
    pool donated: ``(decode, prefill)``, each returning greedy tokens and
    the new pages."""

    def decode_fn(params, pages, tables, lengths, tokens, active):
        logits, pages = model.decode_step_paged(
            params, pages, tables, lengths, tokens, active
        )
        return jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32), pages

    def prefill_fn(params, pages, table, length, tokens):
        logits, pages = model.prefill_paged(params, pages, table, length, tokens)
        last = jnp.take(logits[0], length - 1, axis=0)
        return jnp.argmax(last).astype(jnp.int32), pages

    return (jax.jit(decode_fn, donate_argnums=(1,)),
            jax.jit(prefill_fn, donate_argnums=(1,)))


class ServeEngine:
    """Continuous-batching engine over one ``DecoderLM`` replica."""

    def __init__(self, model, params, config: EngineConfig | None = None):
        self.model = model
        self.params = params
        self.config = config or EngineConfig()
        self.cache = PagedKVCache(model, self.config.cache_config())
        self._lanes: list[Optional[_Lane]] = [None] * self.config.max_batch
        self._pending: deque[GenerationRequest] = deque()  # future arrivals
        self._waiting: deque[GenerationRequest] = deque()  # arrived, unadmitted
        self._committed_blocks = 0
        self._t0: Optional[float] = None
        self.stats = EngineStats()
        self.results: list[GenerationResult] = []
        self._spans = Spans()
        self._decode, self._prefill = engine_steps(model)

    # ------------------------------------------------------------------ clock
    def now(self) -> float:
        if self._t0 is None:
            raise RuntimeError("clock not started (call run())")
        return time.perf_counter() - self._t0

    # -------------------------------------------------------------- admission
    def submit(self, request: GenerationRequest) -> None:
        cap = self.cache.config.max_context
        if request.worst_case_tokens > cap:
            raise ValueError(
                f"request {request.request_id}: prompt + max_new = "
                f"{request.worst_case_tokens} exceeds max context {cap}"
            )
        need = self.cache.config.blocks_for(request.worst_case_tokens)
        if need > self.config.n_pages:
            raise ValueError(
                f"request {request.request_id}: needs {need} pages, pool has "
                f"{self.config.n_pages} -- it could never be admitted"
            )
        self._pending.append(request)

    def _free_lane(self) -> Optional[int]:
        for i, lane in enumerate(self._lanes):
            if lane is None:
                return i
        return None

    def _can_admit(self, request: GenerationRequest) -> bool:
        need = self.cache.config.blocks_for(request.worst_case_tokens)
        return self._committed_blocks + need <= self.config.n_pages

    def _admit(self, request: GenerationRequest, lane_id: int) -> None:
        """Grant a lane + page commitment, then prefill the prompt."""
        cfg = self.config
        prompt = list(request.prompt)
        with self._spans.span("engine.admit.prefill"):
            admitted = self.now()
            need = self.cache.config.blocks_for(request.worst_case_tokens)
            self._committed_blocks += need
            self.cache.ensure_capacity(lane_id, len(prompt))

            bucket = cfg.prefill_bucket(len(prompt))
            padded = np.zeros((1, bucket), np.int32)
            padded[0, : len(prompt)] = prompt
            first, self.cache.pages = self._prefill(
                self.params, self.cache.pages, self.cache.lane_table(lane_id),
                jnp.int32(len(prompt)), jnp.asarray(padded),
            )
        with self._spans.span("engine.admit.wait"):
            first = int(jax.block_until_ready(first))
        t = self.now()
        self.stats.prefills += 1
        self.stats.tokens_generated += 1
        lane = _Lane(
            request=request, admitted_s=admitted, length=len(prompt),
            last_token=first, tokens=[first], token_times=[t],
            committed_blocks=need,
        )
        self._lanes[lane_id] = lane
        if self._is_finished(lane, first):
            self._finish(lane_id, t, reason=self._reason(lane, first))

    def _admit_arrivals(self) -> None:
        now = self.now()
        while self._pending and self._pending[0].arrival_s <= now:
            self._waiting.append(self._pending.popleft())
        while self._waiting:
            lane_id = self._free_lane()
            if lane_id is None or not self._can_admit(self._waiting[0]):
                break
            self._admit(self._waiting.popleft(), lane_id)

    def _evict_timeouts(self) -> None:
        """Evict every past-deadline request with ``reason="timeout"``: an
        active lane gives its pages back to the pool the same tick (and is
        immediately re-admittable); an arrived-but-unadmitted request leaves
        the waiting queue with whatever it has -- nothing."""
        t = self.now()

        def expired(req: GenerationRequest) -> bool:
            return (req.deadline_s is not None
                    and t >= req.arrival_s + req.deadline_s)

        for i, lane in enumerate(self._lanes):
            if lane is not None and expired(lane.request):
                self.stats.timeouts += 1
                self._finish(i, t, reason="timeout")
        kept: deque[GenerationRequest] = deque()
        while self._waiting:
            req = self._waiting.popleft()
            if expired(req):
                self.stats.timeouts += 1
                self.results.append(GenerationResult(
                    request_id=req.request_id, prompt=req.prompt, tokens=[],
                    arrival_s=req.arrival_s, admitted_s=t, finished_s=t,
                    token_times_s=[], finish_reason="timeout",
                ))
            else:
                kept.append(req)
        self._waiting = kept

    # ----------------------------------------------------------------- decode
    @staticmethod
    def _is_finished(lane: _Lane, token: int) -> bool:
        req = lane.request
        return len(lane.tokens) >= req.max_new_tokens or token == req.eos_id

    @staticmethod
    def _reason(lane: _Lane, token: int) -> str:
        return "eos" if token == lane.request.eos_id else "length"

    def _finish(self, lane_id: int, t: float, reason: str) -> None:
        lane = self._lanes[lane_id]
        self.cache.release(lane_id)
        self._committed_blocks -= lane.committed_blocks
        self._lanes[lane_id] = None
        self.results.append(GenerationResult(
            request_id=lane.request.request_id, prompt=lane.request.prompt,
            tokens=lane.tokens, arrival_s=lane.request.arrival_s,
            admitted_s=lane.admitted_s, finished_s=t,
            token_times_s=lane.token_times, finish_reason=reason,
        ))

    def _decode_tick(self) -> None:
        active_ids = [i for i, l in enumerate(self._lanes) if l is not None]
        if not active_ids:
            return
        span = self._spans.span
        with span("engine.tick.prepare"):
            nb = self.config.max_batch
            tokens = np.zeros((nb, 1), np.int32)
            lengths = np.zeros(nb, np.int32)
            active = np.zeros(nb, bool)
            for i in active_ids:
                lane = self._lanes[i]
                # the incoming token is written at position `length`
                self.cache.ensure_capacity(i, lane.length + 1)
                tokens[i, 0] = lane.last_token
                lengths[i] = lane.length
                active[i] = True
            tables = self.cache.device_block_tables()
        with span("engine.tick.dispatch"):
            out, self.cache.pages = self._decode(
                self.params, self.cache.pages, tables,
                jnp.asarray(lengths), jnp.asarray(tokens), jnp.asarray(active),
            )
        with span("engine.tick.wait"):
            out = np.asarray(jax.block_until_ready(out))
        with span("engine.tick.commit"):
            t = self.now()
            self.stats.decode_steps += 1
            self.stats.occupancy.append(len(active_ids))
            self.stats.peak_pages_in_use = max(
                self.stats.peak_pages_in_use, self.cache.allocator.n_allocated
            )
            for i in active_ids:
                lane = self._lanes[i]
                token = int(out[i])
                lane.length += 1
                lane.last_token = token
                lane.tokens.append(token)
                lane.token_times.append(t)
                self.stats.tokens_generated += 1
                if self._is_finished(lane, token):
                    self._finish(i, t, reason=self._reason(lane, token))

    # -------------------------------------------------------------------- run
    def _warmup(self, requests: list[GenerationRequest]) -> None:
        """Compile the decode step and every prefill bucket outside the
        measured window (none of this is counted or timestamped)."""
        nb = self.config.max_batch
        _, self.cache.pages = self._decode(
            self.params, self.cache.pages, self.cache.device_block_tables(),
            jnp.zeros(nb, jnp.int32), jnp.zeros((nb, 1), jnp.int32),
            jnp.zeros(nb, bool),
        )
        empty = jnp.full((self.config.max_blocks,), -1, jnp.int32)
        for bucket in sorted({self.config.prefill_bucket(len(r.prompt))
                              for r in requests}):
            _, self.cache.pages = self._prefill(
                self.params, self.cache.pages, empty, jnp.int32(1),
                jnp.zeros((1, bucket), jnp.int32),
            )
        jax.block_until_ready(self.cache.pages)

    def run(self, requests: list[GenerationRequest] | None = None,
            ) -> tuple[list[GenerationResult], EngineStats]:
        """Serve ``requests`` (plus anything already submitted) to
        completion; returns (results, stats) and leaves every page free."""
        for r in requests or []:
            self.submit(r)
        queued = sorted(self._pending, key=lambda r: (r.arrival_s, r.request_id))
        self._pending = deque(queued)
        self._warmup(queued)

        self._t0 = time.perf_counter()
        with self._spans.listening():
            while self._pending or self._waiting or any(self._lanes):
                # freed lanes/pages are reusable this tick
                with self._spans.span("engine.evict"):
                    self._evict_timeouts()
                self._admit_arrivals()
                if any(self._lanes):
                    self._decode_tick()
                elif self._pending:
                    # idle until the next arrival (nothing to batch)
                    wait = self._pending[0].arrival_s - self.now()
                    if wait > 0:
                        time.sleep(min(wait, 0.01))
        self.stats.elapsed_s = self.now()
        self.stats.host = {k: list(v) for k, v in self._spans.totals.items()}
        self.stats.compiles = self._spans.compiles
        self.stats.compile_s = self._spans.compile_s
        self.results.sort(key=lambda r: r.request_id)
        return self.results, self.stats
