"""Continuous-batching serving engine (paged KV; chunked or bucketed prefill).

The decode step — the paper's workload — runs every cycle over all active
slots.  Two recompile-free admission paths:

  * **bucketed** (the PR 2 path, default): queued prompts are padded to
    power-of-2 length buckets and prefilled together in one fixed-size
    batch — one XLA prefill executable per bucket, ever.  A long prompt
    still occupies the engine for its whole prefill, head-of-line-blocking
    running decodes.
  * **chunked** (``chunked_prefill=True``, paged backend only): prompts are
    fed through the model as fixed-size token slabs *interleaved with
    decode steps* — ONE compiled prefill shape total (no buckets), new
    requests admitted every cycle, and a 4k-token prompt costs each running
    decode at most one chunk of latency per cycle instead of a full-prompt
    stall.  With ``prefix_cache=True`` the paged pool additionally shares
    prompt prefixes across requests (radix index + refcounted pages +
    copy-on-write at a mid-page divergence — ``serve.kvcache``), and a
    prefix hit starts the chunk walk at the first un-cached token.

Scheduling policy (the fairness / starvation guard): admission, chunk
order and capacity-pressure deferral are all strictly FIFO — a request
that cannot reserve pages blocks the queue rather than being overtaken,
so under sustained load every request admits in bounded time; each cycle
runs at most ``chunks_per_step`` prefill slabs *and then* one decode step
over every decoding slot, so neither phase can starve the other.

Cache placement goes through a ``CacheBackend`` (``serve.kvcache``); pure
host-side control around jitted step functions, as production engines do.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from collections import deque
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.tracer import Phase, annotate_gc
from repro.serve import speculate as SP
from repro.serve.config import EngineConfig
from repro.serve.kvcache import (CacheBackend, PagedBackend, bucket_length,
                                 copy_page, kv_row_bytes, make_backend,
                                 resolve_kv_dtype, splice_row)


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray            # (T,) int32
    max_new_tokens: int = 16
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # lifecycle metadata (filled by the engine; *_step counters are engine
    # cycles — deterministic for a seeded trace, the basis of the CI SLO
    # bands — while *_t markers are wall-clock perf_counter seconds)
    submit_step: int = -1
    admit_step: int = -1
    first_token_step: int = -1
    finish_step: int = -1
    submit_t: float = 0.0
    admit_t: float = 0.0
    first_token_t: float = 0.0
    finish_t: float = 0.0

    @property
    def prompt_len(self) -> int:
        return len(self.prompt)

    @property
    def ttft_s(self) -> float:
        """Time to first token: submit -> first generated token."""
        return self.first_token_t - self.submit_t

    @property
    def decode_tok_s(self) -> float:
        """Steady-state decode rate: tokens after the first, per second."""
        dt = self.finish_t - self.first_token_t
        return (len(self.out) - 1) / dt if dt > 0 and len(self.out) > 1 \
            else 0.0

    @property
    def tpot_s(self) -> float:
        """Time per output token after the first (1 / decode_tok_s)."""
        r = self.decode_tok_s
        return 1.0 / r if r else 0.0


def splice_cache(batch_cache, one_cache, slot: int, slots: int):
    """Insert a B=1 prefill cache into slot ``slot`` of the batch cache
    (compat shim over ``kvcache.splice_row``; the engine itself splices
    through its ``CacheBackend``)."""
    return jax.tree.map(
        lambda dst, src: splice_row(dst, src, 0, slot, slots),
        batch_cache, one_cache)


class ServingEngine:
    """Slot-based continuous batching over a pluggable cache backend.

    ``backend``: 'dense' (default, the original layout), 'paged', or a
    ``CacheBackend`` instance.  Bucketed mode: ``prefill_batch`` admissions
    share one bucketed prefill call; ``min_bucket`` is the smallest prompt
    bucket.  Chunked mode (``chunked_prefill=True``): prompts prefill as
    ``chunk_size``-token slabs interleaved with decode (attention-only
    archs over the paged backend); ``prefix_cache=True`` additionally
    reuses shared prompt-prefix pages (``chunk_step`` overrides the
    default ``serve.step.make_chunk_step(model)``).
    """

    def __init__(self, model, *, params,
                 config: Optional[EngineConfig] = None,
                 prefill_step=None, serve_step=None,
                 prefill_extras=None, backend=None, chunk_step=None,
                 tracer=None, profiler=None,
                 draft_model=None, draft_params=None, **legacy):
        """``config``: an ``EngineConfig`` — the primary constructor path
        (``repro.serve.build_engine`` is the one factory).  The legacy
        loose keywords (``slots=``, ``cache_len=``, ...) keep working for
        one release through a shim that emits a ``DeprecationWarning`` and
        forwards into ``EngineConfig.from_legacy_kwargs`` (DESIGN.md §10);
        speculative-decoding options live ONLY on the config.

        ``draft_model`` / ``draft_params`` (required when
        ``config.speculate_k > 0``): the draft half of the speculative
        pair, run over its own private paged cache.

        ``prefill_extras(req) -> dict``: extra prefill batch entries
        (modality frontend stubs for enc-dec / VLM archs).  ``tracer``: a
        ``repro.obs.Tracer`` fed with per-request lifecycle spans and
        allocator events (None: zero overhead).  ``metrics_window`` bounds
        the per-request latency samples ``metrics()`` aggregates so a
        long-lived engine never grows without bound.

        ``tp > 1`` runs every jitted step under ``shard_map`` over a 1-D
        tensor-parallel mesh (``repro.dist.tp``): attention heads / ffn
        dims / MoE experts shard across devices and the KV page pools
        shard on the head axis, while block tables, the prefix index and
        the allocator stay host-side and replicated.  ``tp_mode``:
        ``"exact"`` (token-identical to tp=1) or ``"overlap"`` (ring
        collectives from ``repro.dist.collective_matmul``; tolerance-equal).
        ``async_dispatch`` (default): the decode step submitted in cycle N
        is consumed at the start of cycle N+1, so host-side scheduling work
        overlaps the in-flight device step (one-step-deep pipeline).

        ``profiler``: a ``repro.obs.DispatchProfiler`` — the engine
        brackets every step submission in a phase context (``admit`` /
        ``bucketed_prefill`` / ``chunk_prefill`` / ``decode`` /
        ``collective`` under TP) so registry-kernel dispatches and
        measured step walls aggregate per phase (None: zero overhead).

        Every phase of a cycle also opens a ``jax.profiler``
        annotation ``engine.<phase>`` (``_phase``), so a profiler trace
        shows where the host was while the device ran or sat idle:
        ``engine.step`` round each cycle, and in it ``consume`` (its
        blocking read ``stream_out``), ``admit``, ``chunk``,
        ``first_token``, ``decode_submit`` and ``finish``; ``prefill`` on
        the bucketed path; ``draft`` (its passes ``draft_step``,
        ``draft_catchup``) and ``verify`` when speculating; ``engine.gc``
        round each Python garbage collection."""
        if legacy:
            if config is not None:
                raise TypeError(
                    "pass either config=EngineConfig(...) or the legacy "
                    f"keywords, not both (got {sorted(legacy)})")
            warnings.warn(
                "ServingEngine(slots=..., cache_len=..., ...) keyword "
                "construction is deprecated — pass config=EngineConfig(...)"
                " or build via repro.serve.build_engine (DESIGN.md §10)",
                DeprecationWarning, stacklevel=2)
            config = EngineConfig.from_legacy_kwargs(**legacy)
        elif config is None:
            config = EngineConfig()
        self.backend: CacheBackend = make_backend(
            backend if backend is not None else config.backend)
        # a passed-in backend instance wins over config.backend: normalize
        # the record to what actually runs, then cross-validate
        config = dataclasses.replace(config, backend=self.backend.name)
        config.validate()
        self.config = config
        slots, cache_len = config.slots, config.cache_len

        if prefill_step is None or serve_step is None:
            from repro.serve.step import make_prefill_step, make_serve_step
            if prefill_step is None:
                prefill_step = make_prefill_step(model)
            if serve_step is None:
                serve_step = make_serve_step(
                    model, temperature=config.temperature, seed=config.seed)

        self.model = model
        self.tracer = tracer
        self.profiler = profiler
        annotate_gc()
        self.slots = slots
        self.cache_len = cache_len
        self.params = params
        self.prefill_extras = prefill_extras
        self.backend.tracer = tracer       # allocator/prefix/COW events
        # the ONE kv-storage-dtype resolution (DESIGN.md §10): an explicit
        # backend kv_dtype wins, else the model's rt.kv_dtype() alias is
        # collapsed here — every downstream consumer (chunk staging, the
        # streamed-bytes model, the backend pools) reads this value
        self.kv_dtype = (getattr(self.backend, "kv_dtype", None)
                         or resolve_kv_dtype(model))
        if isinstance(self.backend, PagedBackend) \
                and self.backend.kv_dtype is None:
            self.backend.kv_dtype = self.kv_dtype
        self.prefill_batch = config.prefill_batch or min(slots, 4)
        self.min_bucket = min(config.min_bucket, cache_len)
        self.chunked = config.chunked_prefill
        self.chunk_size = min(config.chunk_size, cache_len)
        self.chunks_per_step = max(1, config.chunks_per_step)
        # frontend tokens prepended to the decoder sequence (VLM archs)
        self._front = model.cfg.frontend_tokens \
            if getattr(model.cfg, "frontend", None) == "vision" else 0
        # right-padding a prompt is exact only for causal attention: a
        # recurrent mixer (mamba/rwkv) scans THROUGH pad tokens and hands
        # decode a polluted state — those archs prefill at exact length
        # (same-length prompts still batch; compiles are per length, as in
        # the seed engine, instead of per bucket)
        self._exact_prefill = any(
            m != "attn" for (m, f) in model.cfg.layer_kinds())

        if self.chunked:
            if not isinstance(self.backend, PagedBackend):
                raise ValueError("chunked_prefill requires the paged "
                                 "backend (slabs write through block "
                                 "tables)")
            if (self._exact_prefill or self._front
                    or model.cfg.encoder_decoder
                    or model.cfg.attention == "mla"):
                raise ValueError(
                    "chunked_prefill supports causal-attention decoder "
                    "archs only (recurrent mixers cannot resume a scan "
                    "mid-prompt from pages; MLA/enc-dec keep dense "
                    "caches) — use the bucketed engine for "
                    f"{model.cfg.name!r}")
            self.backend.prefix_cache = (config.prefix_cache
                                         or self.backend.prefix_cache)
            if self.kv_dtype == "int8":
                # int8 pools: stage this request's own rows in bf16 so a
                # later chunk never re-reads its predecessors quantized
                self.backend.chunk_stage = self.chunk_size
        elif config.prefix_cache:
            raise ValueError("prefix_cache requires chunked_prefill (a "
                             "prefix hit resumes prefill mid-prompt, which "
                             "only the chunk walk supports)")

        # --------------------------------------------------- tensor parallel
        tp = config.tp
        self.tp = tp
        self.tp_mode = config.tp_mode
        self._async = bool(config.async_dispatch)
        self._tpx = None
        self._kv_shards = 1
        if tp > 1:
            from repro.dist.tp import TPExecutor
            self._tpx = TPExecutor(model, tp, mode=config.tp_mode)
            self._tpx.profiler = profiler
            self._kv_shards = self._tpx.plan.kv_shards
            self.params = self._tpx.shard_params(model, params)

        self._prefill_traces = 0
        # exact mode: tokens must not depend on tp, so the tp=1 steps
        # compile with the same XLA options as the sharded ones
        from repro.dist.tp import compiler_options
        opts = compiler_options(config.tp_mode)

        def counted_prefill(params, batch):
            self._prefill_traces += 1      # runs at trace time only
            return prefill_step(params, batch)

        if self._tpx is not None:
            # probe = the uncounted twin: jit_step's one eval_shape must not
            # inflate the compile counter
            self.prefill_step = self._tpx.jit_step(counted_prefill,
                                                   probe=prefill_step)
            self.serve_step = self._tpx.jit_step(serve_step, donate=2)
        else:
            self.prefill_step = jax.jit(counted_prefill,
                                        compiler_options=opts)
            self.serve_step = jax.jit(serve_step, donate_argnums=(2,),
                                      compiler_options=opts)
        if self.chunked:
            if chunk_step is None:
                from repro.serve.step import make_chunk_step
                chunk_step = make_chunk_step(model)

            def counted_chunk(params, batch, caches):
                self._prefill_traces += 1  # runs at trace time only
                return chunk_step(params, batch, caches)

            if self._tpx is not None:
                self.chunk_step = self._tpx.jit_step(counted_chunk,
                                                     probe=chunk_step,
                                                     donate=2)
            else:
                self.chunk_step = jax.jit(counted_chunk, donate_argnums=(2,),
                                          compiler_options=opts)
            self._copy_page = jax.jit(copy_page, donate_argnums=(0,))
        self.caches = self.backend.init_caches(model, slots, cache_len)
        if self._tpx is not None:
            self.caches = self._tpx.shard_caches(self.caches)
            self.backend.tp = tp
            self.backend.kv_shards = self._kv_shards
        # streamed-bytes model (DESIGN.md §8): decode reads every cached
        # row of every decoding slot once per step; a head-sharded pool
        # streams 1/kv_shards of each row per device
        self._kv_row_bytes = kv_row_bytes(model.cfg, self.kv_dtype)

        # ------------------------------------------- speculative decoding
        # (DESIGN.md §10) the FLOP-side roofline lever: a draft model
        # proposes k tokens per cycle, one target verify pass scores all
        # k+1 positions through the chunked slab path, and the host
        # accept/reject rule (serve.speculate) emits 1..k+1 tokens.
        self.spec_k = config.speculate_k
        self.draft_model = draft_model
        self.draft_steps = 0               # draft forward passes
        self.verify_passes = 0             # target verify passes
        self.draft_tokens_proposed = 0
        self.draft_tokens_accepted = 0
        self.spec_tokens_emitted = 0
        self.spec_slot_passes = 0          # per-slot verify scorings
        self.rollback_pages = 0            # lookahead pages freed
        if self.spec_k:
            if draft_model is None or draft_params is None:
                raise ValueError(
                    "speculate_k > 0 needs draft_model + draft_params — "
                    "build the engine via repro.serve.build_engine")
            from repro.serve.step import make_draft_step, make_verify_step
            # the verify pass consumes its emissions synchronously (the
            # accept/reject rule needs the logits on the host)
            self._async = False
            self._draft_W = self.spec_k + 1
            self._temperature = config.temperature
            # the draft runs over its own full-occupancy paged pool (same
            # page size, reservations never fail) sized for the deepest
            # chain the bookkeeping can reach past the target's horizon
            self._draft_cache_len = cache_len + 2 * self._draft_W
            self._draft_backend = PagedBackend(
                page_size=self.backend.page_size)
            self._draft_backend.tracer = tracer
            self.draft_params = draft_params
            self.draft_caches = self._draft_backend.init_caches(
                draft_model, slots, self._draft_cache_len)
            self.draft_step = jax.jit(make_draft_step(draft_model),
                                      donate_argnums=(2,),
                                      compiler_options=opts)
            self.verify_step = jax.jit(make_verify_step(model),
                                       donate_argnums=(2,),
                                       compiler_options=opts)
            self._spec_rng = np.random.default_rng(config.seed)
            self._draft_pos = np.zeros((slots,), np.int32)
            # tokens emitted by the target that the draft model has not
            # ingested yet (flushed as the first chain step of each cycle)
            self._draft_pending: Dict[int, List[int]] = {}
            self._draft_ready: set = set()   # slots the draft caught up on
        self.active: Dict[int, Optional[Request]] = {
            i: None for i in range(slots)}
        self.pos = np.zeros((slots,), np.int32)
        self.last_tok = np.zeros((slots,), np.int32)
        # per-admission nonce: a request reusing a slot must not replay its
        # predecessor's sampling randomness at equal positions
        self._nonce = np.zeros((slots,), np.int32)
        self.queue: deque = deque()
        self.stop_token = config.stop_token
        self.steps = 0                     # engine cycles (admit/chunk/decode)
        self.decode_steps = 0              # cycles that ran serve_step
        # chunked-prefill bookkeeping
        self._prefilling: deque = deque()            # slots mid-prefill
        self._decoding: set = set()                  # slots generating
        self._chunk_off: Dict[int, int] = {}         # next token to prefill
        self._stage_base: Dict[int, int] = {}        # first non-shared pos
        # ------------------------------------------------------- metrics
        # _admission_seq is the nonce source and NEVER resets (a reset
        # nonce would replay a previous request's sampling randomness);
        # everything below it is a resettable window (reset_metrics).
        self._admission_seq = 0
        self.tokens_generated = 0
        self.requests_admitted = 0
        self.requests_finished = 0
        self.deferrals = 0                 # cycles a request sat pool-blocked
        self.prefill_calls = 0
        self.prefill_s = 0.0
        self.decode_s = 0.0
        self.chunk_calls = 0
        self.chunk_tokens = 0                        # valid slab rows
        self.prefill_tokens = 0                      # admitted prompt tokens
        self.shared_tokens = 0                       # served from the prefix
        # async dispatch: the parked decode step (futures + slot snapshot +
        # submit timestamps) and its overlap accounting
        self._inflight = None
        self.kv_bytes_streamed = 0                   # modeled, all devices
        self.kv_bytes_streamed_per_device = 0        # modeled, one device
        self.host_overlap_s = 0.0      # host work while a step is in flight
        self.stream_wait_s = 0.0       # blocked in stream-out (np.asarray)
        # bounded latency samples: a soak appends one entry per finished
        # request; the deque keeps the trailing window only
        self._ttfts: deque = deque(maxlen=config.metrics_window)
        self._decode_rates: deque = deque(maxlen=config.metrics_window)

    @property
    def prefill_traces(self) -> int:
        """Prefill executables compiled so far (bucketed: == distinct
        buckets used; chunked: exactly one, ever)."""
        return self._prefill_traces

    # -------------------------------------------------------------- admit
    def submit(self, req: Request):
        # impossible requests fail HERE, loudly — once queued, a request is
        # only ever deferred (transient pool pressure), never dropped
        rows = self._front + req.prompt_len
        if rows >= self.cache_len:
            raise ValueError(
                f"prompt needs {rows} cache rows (incl. frontend) but "
                f"cache_len is {self.cache_len}")
        self.backend.check_admissible(rows + req.max_new_tokens)
        req.submit_step = self.steps
        req.submit_t = time.perf_counter()
        self.queue.append(req)
        if self.tracer is not None:
            self.tracer.instant("submit", "queue", rid=req.rid,
                                prompt_len=req.prompt_len,
                                max_new=req.max_new_tokens,
                                queue_depth=len(self.queue))

    def _free_slots(self) -> List[int]:
        return [s for s, r in self.active.items() if r is None]

    def _phase(self, name: str, **kw) -> Phase:
        """One engine phase (``repro.obs.tracer.Phase``): the
        ``engine.<name>`` annotation, plus this engine's profiler phase
        ``kw["prof"]`` and tracer span ``kw["span"]`` where given."""
        return Phase(name, profiler=self.profiler, tracer=self.tracer, **kw)

    def _admit_group(self, group, slots_for) -> List[Request]:
        """One bucketed batched prefill for ``group`` (list of Requests);
        returns requests whose prefill-emitted first token already finished
        them (stop token, or max_new_tokens == 1)."""
        if self._exact_prefill:
            bucket = group[0].prompt_len       # group is same-length
        else:
            bucket = max(bucket_length(r.prompt_len, self.min_bucket,
                                       self.cache_len) for r in group)
        Bp = self.prefill_batch
        with self._phase("prefill", prof="bucketed_prefill", key=bucket,
                         span="prefill", bucket=bucket,
                         batch=len(group)) as ph:
            tokens = np.zeros((Bp, bucket), np.int32)
            lengths = np.ones((Bp,), np.int32)
            for i, req in enumerate(group):
                tokens[i, :req.prompt_len] = req.prompt
                lengths[i] = self._front + req.prompt_len
            batch = {"tokens": jnp.asarray(tokens),
                     "length": jnp.asarray(lengths)}
            if self.prefill_extras is not None:
                extras: Dict[str, Any] = {}
                per_req = [self.prefill_extras(r) for r in group]
                for k in per_req[0]:
                    rows = [e[k] for e in per_req]
                    rows += [rows[-1]] * (Bp - len(rows))  # pad batch rows
                    extras[k] = jnp.concatenate(rows, axis=0)
                batch.update(extras)
            next_tok, prefill_caches = self.prefill_step(self.params, batch)
            next_tok = np.asarray(next_tok)
        self.prefill_calls += 1

        finished: List[Request] = []
        for i, req in enumerate(group):
            slot = slots_for[i]
            plen = self._front + req.prompt_len
            self.caches = self.backend.admit(
                self.caches, prefill_caches, row=i, slot=slot,
                prompt_len=plen)
            self.active[slot] = req
            req.admit_step = self.steps
            req.admit_t = time.perf_counter()
            self.requests_admitted += 1
            self._admission_seq += 1
            self.prefill_tokens += req.prompt_len
            self._nonce[slot] = self._admission_seq
            self.pos[slot] = plen
            if self.tracer is not None:
                self.tracer.instant("admit", slot, rid=req.rid,
                                    prompt_len=req.prompt_len,
                                    wait_steps=self.steps - req.submit_step)
            tok = int(next_tok[i])
            req.out.append(tok)
            req.first_token_step = self.steps
            req.first_token_t = time.perf_counter()
            if self.tracer is not None:
                self.tracer.instant("first_token", slot, rid=req.rid,
                                    ttft_steps=self.steps - req.submit_step)
            self.tokens_generated += 1
            self.last_tok[slot] = tok
            # the first token obeys the same finish rules as decode tokens
            # (both prefill paths must emit identical streams)
            if len(req.out) >= req.max_new_tokens or tok == self.stop_token:
                finished.append(self._finish(slot, req))
            else:
                self._decoding.add(slot)
        self.prefill_s += time.perf_counter() - ph.t0
        return finished

    def _admit(self) -> List[Request]:
        """Admit as many queued requests as slots + cache capacity allow
        (possibly several bucketed prefill calls); returns requests their
        first token already finished."""
        finished: List[Request] = []
        while self.queue:
            free = self._free_slots()
            if not free:
                break
            with self._phase("admit", prof="admit"):
                group, slots_for = self._gather_group(free)
            if not group:
                break
            finished.extend(self._admit_group(group, slots_for))
        return finished

    def _gather_group(self, free):
        """Pop a bucketed-prefill admission group off the queue."""
        group, slots_for = [], []
        while (self.queue and free
               and len(group) < self.prefill_batch):
            req = self.queue[0]
            if self._exact_prefill and group \
                    and req.prompt_len != group[0].prompt_len:
                break                      # exact-length groups only
            slot = free[0]
            need = self._front + req.prompt_len + req.max_new_tokens
            if not self.backend.reserve(slot, need):
                self._defer(req, need)
                break                  # pool exhausted: defer admission
            self.queue.popleft()
            free.pop(0)
            group.append(req)
            slots_for.append(slot)
        return group, slots_for

    # ------------------------------------------------- chunked admission
    def _admit_chunked(self, count_defer: bool = True):
        """Assign slots + pages to queued requests, strictly FIFO: a
        request the pool cannot hold right now *blocks* admission (no
        overtaking — the starvation guard) until releases free pages."""
        with self._phase("admit", prof="admit"):
            self._admit_chunked_locked(count_defer)

    def _admit_chunked_locked(self, count_defer: bool):
        while self.queue:
            free = self._free_slots()
            if not free:
                return
            req = self.queue[0]
            slot = free[0]
            need = req.prompt_len + req.max_new_tokens
            if self.backend.prefix_cache:
                offset = self.backend.reserve_with_prefix(
                    slot, need, req.prompt)
                if offset is None:
                    self._defer(req, need, count=count_defer)
                    return                 # pool exhausted: defer (FIFO)
                cow = self.backend.take_cow(slot)
                if cow is not None:
                    src, dst = cow
                    self.caches = self._copy_page(
                        self.caches, jnp.int32(src), jnp.int32(dst))
                    self.backend.cow_done(slot)
            else:
                if not self.backend.reserve(slot, need):
                    self._defer(req, need, count=count_defer)
                    return
                offset = 0
            self.queue.popleft()
            self.active[slot] = req
            req.admit_step = self.steps
            req.admit_t = time.perf_counter()
            self.requests_admitted += 1
            self._admission_seq += 1
            self.prefill_tokens += req.prompt_len
            self.shared_tokens += offset
            self._nonce[slot] = self._admission_seq
            self.pos[slot] = 0
            self._chunk_off[slot] = offset
            self._stage_base[slot] = offset
            self._prefilling.append(slot)
            if self.tracer is not None:
                self.tracer.instant("admit", slot, rid=req.rid,
                                    prompt_len=req.prompt_len,
                                    prefix_offset=offset,
                                    wait_steps=self.steps - req.submit_step)

    def _defer(self, req: Request, need: int, count: bool = True):
        """Head-of-queue request cannot reserve pages this cycle.  The
        async early-admission pass passes ``count=False``: it retries after
        the in-flight decode is consumed, and only the retry counts — so
        deferral totals match the synchronous engine."""
        if not count:
            return
        self.deferrals += 1
        if self.tracer is not None:
            self.tracer.instant("defer", "queue", rid=req.rid,
                                need_tokens=need)

    def _chunk_one(self) -> List[Request]:
        """Run one prefill slab for the oldest mid-prefill request; on the
        prompt's final slab, emit its first token (greedy argmax of the
        last valid row — the bucketed engine's readout)."""
        slot = self._prefilling[0]
        req = self.active[slot]
        C = self.chunk_size
        off = self._chunk_off[slot]
        end = min(off + C, req.prompt_len)
        valid = end - off
        with self._phase("chunk", prof="chunk_prefill", span="chunk",
                         track=slot, rid=req.rid, off=off,
                         valid=valid) as ph:
            tokens = np.zeros((1, C), np.int32)
            tokens[0, :valid] = req.prompt[off:end]
            batch = {
                "tokens": jnp.asarray(tokens),
                "offset": jnp.asarray([off], jnp.int32),
                "valid": jnp.asarray([valid], jnp.int32),
                "stage_base": jnp.asarray([self._stage_base[slot]],
                                          jnp.int32),
                "block_tables": jnp.asarray(
                    self.backend.block_tables[slot:slot + 1]),
            }
            next_tok, self.caches = self.chunk_step(
                self.params, batch, self.caches)
        self.prefill_s += ph.t1 - ph.t0
        self.chunk_calls += 1
        self.chunk_tokens += valid
        self._chunk_off[slot] = end
        if end < req.prompt_len:
            return []
        # prompt fully on-pool: index its pages for prefix reuse, start
        # decoding from its first generated token
        with self._phase("first_token"):
            self._prefilling.popleft()
            if self.backend.prefix_cache:
                self.backend.register_prefix(slot, req.prompt)
            tok = int(np.asarray(next_tok)[0])
        self.prefill_calls += 1
        req.out.append(tok)
        req.first_token_step = self.steps
        req.first_token_t = time.perf_counter()
        if self.tracer is not None:
            self.tracer.instant("first_token", slot, rid=req.rid,
                                ttft_steps=self.steps - req.submit_step)
        self.tokens_generated += 1
        self.last_tok[slot] = tok
        self.pos[slot] = req.prompt_len
        if len(req.out) >= req.max_new_tokens or tok == self.stop_token:
            return [self._finish(slot, req)]
        self._decoding.add(slot)
        return []

    def _finish(self, slot: int, req: Request) -> Request:
        req.done = True
        req.finish_step = self.steps
        req.finish_t = time.perf_counter()
        self.active[slot] = None
        self._decoding.discard(slot)
        with self._phase("finish"):
            self.backend.release(slot)
            if self.spec_k and slot in self._draft_ready:
                self._draft_backend.release(slot)
                self._draft_ready.discard(slot)
                self._draft_pending.pop(slot, None)
        self.requests_finished += 1
        # latency samples: only requests that actually emitted a first
        # token have a TTFT, and only multi-token requests have a decode
        # rate — a request finished without either (e.g. truncated before
        # generating) would record a negative ttft_s / a 0.0 rate and drag
        # every mean and percentile
        if req.out and req.first_token_t > 0.0:
            self._ttfts.append(req.ttft_s)
        if len(req.out) > 1 and req.finish_t > req.first_token_t:
            self._decode_rates.append(req.decode_tok_s)
        if self.tracer is not None:
            self.tracer.instant("finish", slot, rid=req.rid,
                                generated=len(req.out),
                                total_steps=self.steps - req.submit_step)
            if req.admit_t > 0.0:
                self.tracer.span("request", slot,
                                 self.tracer.rel(req.admit_t),
                                 self.tracer.rel(req.finish_t), rid=req.rid,
                                 prompt_len=req.prompt_len,
                                 generated=len(req.out))
        return req

    # -------------------------------------------------------------- decode
    def _decode_block_tables(self):
        """Block tables for the decode batch.  Chunked mode masks slots
        that are not decoding (idle or mid-prefill) to the NULL page: the
        decode step computes garbage rows for them regardless, and this
        keeps their scatter writes off live pages — in particular off a
        mid-prefill slot's freshly written slabs."""
        bt = self.backend.block_tables
        if not self.chunked:
            return jnp.asarray(bt)
        mask = np.zeros((self.slots, 1), bt.dtype)
        for s in self._decoding:
            mask[s] = 1
        return jnp.asarray(bt * mask)

    def _submit_decode(self):
        """Enqueue one decode step over the decoding slots and return
        without blocking (JAX async dispatch): the device futures, the
        decoding-slot snapshot and the submit phase (its two stamps) park
        in ``_inflight`` until ``_consume`` streams the tokens out."""
        slots = tuple(sorted(self._decoding))
        with self._phase("decode_submit", prof="decode", span="device_submit",
                         batch=len(slots)) as sub:
            batch = {"tokens": jnp.asarray(self.last_tok[:, None]),
                     "pos": jnp.asarray(self.pos),
                     "sample_nonce": jnp.asarray(self._nonce)}
            extras = self.backend.batch_extras()
            if "block_tables" in extras:
                extras["block_tables"] = self._decode_block_tables()
            batch.update(extras)
            next_tok, self.caches = self.serve_step(
                self.params, batch, self.caches)
        # streamed-bytes model: this step reads every cached row of every
        # decoding slot once; a head-sharded pool streams 1/kv_shards of
        # each row per device
        rows = int(sum(int(self.pos[s]) + 1 for s in slots))
        self.kv_bytes_streamed += rows * self._kv_row_bytes
        self.kv_bytes_streamed_per_device += rows * (
            self._kv_row_bytes // max(self._kv_shards, 1))
        self._inflight = (next_tok, slots, sub)

    def _consume(self) -> List[Request]:
        """Block on the in-flight decode step's tokens (the engine's only
        ``block_until_ready`` point) and apply them to the slots that were
        decoding at submit time."""
        if self._inflight is None:
            return []
        with self._phase("consume"):
            return self._consume_inflight()

    def _consume_inflight(self) -> List[Request]:
        next_tok, slots, sub = self._inflight
        self._inflight = None
        with self._phase("stream_out", span="stream_out",
                         batch=len(slots)) as out:
            toks = np.asarray(next_tok)[:, 0]      # blocks on the device
        wait = out.t1 - out.t0
        # host work done between submit and here overlapped the device
        # step — but only the async pipeline actually interleaves any;
        # the sync path consumes immediately and must report ~0 overlap
        if self._async:
            self.host_overlap_s += max(0.0, out.t0 - sub.t1)
        self.stream_wait_s += wait
        if self.profiler is not None:
            # the submit span landed in the decode phase; the stream-out
            # wait is the rest of the step's measured wall
            self.profiler.add_wall("decode", wait)
        # decode_s counts host time attributable to decode (submit + wait,
        # not the overlapped window) so prefill_s + decode_s ~= wall time
        self.decode_s += (sub.t1 - sub.t0) + wait
        self.decode_steps += 1
        if self.tracer is not None:
            self.tracer.span("decode", "engine", self.tracer.rel(sub.t0),
                             self.tracer.rel(out.t1), batch=len(slots))
        finished: List[Request] = []
        for slot in slots:
            req = self.active[slot]
            tok = int(toks[slot])
            req.out.append(tok)
            self.tokens_generated += 1
            self.last_tok[slot] = tok
            self.pos[slot] += 1
            if len(req.out) >= req.max_new_tokens or tok == self.stop_token \
                    or self.pos[slot] >= self.cache_len - 1:
                finished.append(self._finish(slot, req))
        return finished

    # ------------------------------------------------- speculative decode
    def _draft_forward(self, feed: Dict[int, List[int]],
                       offsets: Dict[int, int]) -> np.ndarray:
        """One batched draft slab: ``feed[slot]`` tokens are written into
        the draft cache at ``offsets[slot]`` and each slot's last-valid-row
        fp32 logits come back (B, V).  Inactive rows run with valid=0
        against NULL-masked block tables (their scatter writes land on the
        scratch page) and are ignored on the host."""
        W = self._draft_W
        bt = self._draft_backend.block_tables
        tokens = np.zeros((self.slots, W), np.int32)
        valid = np.zeros((self.slots,), np.int32)
        offs = np.zeros((self.slots,), np.int32)
        mask = np.zeros((self.slots, 1), bt.dtype)
        for s, toks in feed.items():
            tokens[s, :len(toks)] = toks
            valid[s] = len(toks)
            offs[s] = offsets[s]
            mask[s] = 1
        batch = {"tokens": jnp.asarray(tokens),
                 "offset": jnp.asarray(offs),
                 "valid": jnp.asarray(valid),
                 "stage_base": jnp.zeros((self.slots,), jnp.int32),
                 "block_tables": jnp.asarray(bt * mask)}
        with self._phase("draft_step", prof="draft"):
            logits, self.draft_caches = self.draft_step(
                self.draft_params, batch, self.draft_caches)
            logits = np.asarray(logits)
        self.draft_steps += 1
        return logits

    def _draft_catchup(self, slot: int):
        """Walk ``slot``'s prompt through the draft model in W-token slabs
        (the draft's own chunked prefill).  Afterwards the draft cache
        covers the prompt and the target's first emission waits in
        ``_draft_pending`` — flushed as step 1 of the next chain."""
        req = self.active[slot]
        if not self._draft_backend.reserve(slot, self._draft_cache_len):
            raise RuntimeError("draft pool exhausted — it is sized for "
                               "full occupancy, so this cannot happen")
        W = self._draft_W
        prompt = [int(t) for t in req.prompt]
        with self._phase("draft_catchup") as ph:
            off = 0
            while off < len(prompt):
                end = min(off + W, len(prompt))
                self._draft_forward({slot: prompt[off:end]}, {slot: off})
                off = end
        self.prefill_s += ph.t1 - ph.t0
        self._draft_pos[slot] = len(prompt)
        self._draft_pending[slot] = [int(self.last_tok[slot])]
        self._draft_ready.add(slot)
        if self.tracer is not None:
            self.tracer.instant("draft_catchup", slot, rid=req.rid,
                                tokens=len(prompt))

    def _spec_cycle(self) -> List[Request]:
        """One speculative cycle over the decoding slots, replacing the
        plain decode step: linear draft chain (k proposals per slot), ONE
        target verify pass scoring all k+1 positions (the TROOP lever —
        every byte of target weights/KV streamed does up to (k+1)x work),
        host accept/reject (``serve.speculate``; greedy mode is
        token-identical to ``_consume``), then page rollback of the
        rejected lookahead tail."""
        for s in sorted(self._decoding):
            if s not in self._draft_ready:
                self._draft_catchup(s)
        slots = tuple(sorted(self._decoding))
        if not slots:
            return []
        t0 = time.perf_counter()
        W = self._draft_W

        # 1) per-slot window: the finish rule caps pos at cache_len-1, so
        # lookahead never needs rows past cache_len-2; clamp to what the
        # target pool covers after extension.  extend() is all-or-nothing,
        # and the admission-time baseline reservation already covers
        # pos+1 rows for any active slot — under pool pressure k degrades
        # toward plain decode instead of deadlocking.
        k_eff: Dict[int, int] = {}
        for s in slots:
            k = max(0, min(self.spec_k,
                           self.cache_len - 2 - int(self.pos[s])))
            if k > 0:
                covered = self.backend.extend(s, int(self.pos[s]) + k + 1)
                k = max(0, min(k, covered - int(self.pos[s]) - 1))
            k_eff[s] = k

        # 2) linear draft chain: step 1 flushes each slot's pending target
        # emissions, steps 2..k feed the previous proposal back
        drafts: Dict[int, List[int]] = {s: [] for s in slots}
        dists: Dict[int, List[np.ndarray]] = {s: [] for s in slots}
        fed: Dict[int, int] = {s: 0 for s in slots}
        cur = {s: int(self._draft_pos[s]) for s in slots}
        feed = {s: list(self._draft_pending[s])
                for s in slots if k_eff[s] > 0}
        kmax = max(k_eff.values(), default=0)
        with self._phase("draft", span="draft" if kmax else None,
                         batch=len(slots), k=kmax):
            for j in range(1, kmax + 1):
                if not feed:
                    break
                logits = self._draft_forward(feed, cur)
                nxt: Dict[int, List[int]] = {}
                for s, toks in feed.items():
                    if j == 1:
                        fed[s] = len(toks)
                    cur[s] += len(toks)
                    row = logits[s]
                    if self._temperature > 0:
                        p = SP.softmax(row, self._temperature)
                        d = int(self._spec_rng.choice(p.shape[0], p=p))
                        dists[s].append(p)
                    else:
                        d = int(np.argmax(row))
                    drafts[s].append(d)
                    if k_eff[s] > j:
                        nxt[s] = [d]
                feed = nxt

        # 3) one target pass scores every window: logits row i is the
        # target distribution conditioned on the first i draft tokens
        with self._phase("verify", prof=f"verify@{self.spec_k}",
                         span="verify", batch=len(slots)):
            tokens = np.zeros((self.slots, W), np.int32)
            valid = np.zeros((self.slots,), np.int32)
            offs = np.zeros((self.slots,), np.int32)
            for s in slots:
                win = [int(self.last_tok[s])] + drafts[s]
                tokens[s, :len(win)] = win
                valid[s] = len(win)
                offs[s] = int(self.pos[s])
            batch = {"tokens": jnp.asarray(tokens),
                     "offset": jnp.asarray(offs),
                     "valid": jnp.asarray(valid),
                     "block_tables": self._decode_block_tables()}
            logits, self.caches = self.verify_step(
                self.params, batch, self.caches)
            logits = np.asarray(logits)
        rows = int(sum(int(self.pos[s]) + int(valid[s]) for s in slots))
        self.kv_bytes_streamed += rows * self._kv_row_bytes
        self.kv_bytes_streamed_per_device += rows * (
            self._kv_row_bytes // max(self._kv_shards, 1))

        # 4) host accept/reject + emission (finish rules identical to
        # ``_consume``)
        finished: List[Request] = []
        for s in slots:
            req = self.active[s]
            k = k_eff[s]
            rows_l = logits[s, :k + 1]
            if self._temperature > 0:
                tprobs = SP.softmax(rows_l, self._temperature)
                dprobs = (np.stack(dists[s]) if dists[s]
                          else np.zeros((0, rows_l.shape[-1])))
                emitted, a = SP.speculative_sample(
                    tprobs, dprobs, drafts[s], self._spec_rng)
            else:
                emitted, a = SP.greedy_verify(
                    np.argmax(rows_l, axis=-1), drafts[s])
            self.draft_tokens_proposed += k
            self.draft_tokens_accepted += a
            done = False
            for tok in emitted:
                tok = int(tok)
                req.out.append(tok)
                self.tokens_generated += 1
                self.spec_tokens_emitted += 1
                self.last_tok[s] = tok
                self.pos[s] += 1
                if (len(req.out) >= req.max_new_tokens
                        or tok == self.stop_token
                        or self.pos[s] >= self.cache_len - 1):
                    done = True
                    break
            if done:
                finished.append(self._finish(s, req))
                continue
            # draft bookkeeping: the draft cache holds valid rows for the
            # flushed pending tokens and d_1..d_a (d_k's KV was never
            # written); everything past them is overwritten by the next
            # chain, which starts exactly at the new _draft_pos
            x = int(self.last_tok[s])
            if k == 0:
                # only reachable right at the cache horizon (pos >=
                # cache_len-2), where the emission above finishes the slot
                # — kept for safety
                self._draft_pending[s].append(x)
            elif a < k:
                self._draft_pos[s] += fed[s] + a
                self._draft_pending[s] = [x]
            else:
                self._draft_pos[s] += fed[s] + k - 1
                self._draft_pending[s] = [drafts[s][-1], x]
            assert len(self._draft_pending[s]) <= W

        # 5) rewind surviving slots to their baseline reservation: the
        # lookahead tail past prompt_len + max_new holds only rejected or
        # replayable rows (a slot's valid rows never exceed
        # prompt_len + max_new - 1), and tail pages are always private —
        # shared prefix pages sit at the front of the run
        for s in slots:
            req = self.active[s]
            if req is None:
                continue
            freed = self.backend.rollback(
                s, req.prompt_len + req.max_new_tokens)
            if freed:
                self.rollback_pages += freed
                if self.tracer is not None:
                    self.tracer.instant("rollback", s, rid=req.rid,
                                        pages=freed)
        self.verify_passes += 1
        self.spec_slot_passes += len(slots)
        self.decode_steps += 1
        self.decode_s += time.perf_counter() - t0
        return finished

    def step(self) -> Optional[List[Request]]:
        """One engine cycle: admit, (chunked: run prefill slabs,) then
        decode every generating slot.

        With ``async_dispatch`` (the default) the decode step submitted in
        cycle N is consumed at the START of cycle N+1, so the host's
        admission / prefix-index / allocator work overlaps the in-flight
        device step.  Token streams are identical to the synchronous
        engine; a request's finish surfaces one cycle later.

        Returns the requests that finished this cycle, or ``None`` when the
        engine is idle (nothing active after admission).
        """
        with self._phase("step", step=self.steps):
            return self._cycle()

    def _cycle(self) -> Optional[List[Request]]:
        finished: List[Request] = []
        if self.chunked:
            if self._inflight is not None:
                # overlap host-side admission with the in-flight decode; a
                # deferral here is retried (and counted) after consume
                self._admit_chunked(count_defer=False)
            finished.extend(self._consume())
            self._admit_chunked()
            chunk_finished: List[Request] = []
            for _ in range(self.chunks_per_step):
                if not self._prefilling:
                    break
                chunk_finished.extend(self._chunk_one())
            # a finish above may unblock a deferred reservation: admit
            # again so freed pages go back to work within the same cycle
            if chunk_finished:
                self._admit_chunked()
            finished.extend(chunk_finished)
        else:
            finished.extend(self._consume())
            finished.extend(self._admit())
        if not self._decoding:
            if (self.chunked and self._prefilling) or finished:
                self.steps += 1
                return finished
            return None
        if self.spec_k:
            finished.extend(self._spec_cycle())
        else:
            self._submit_decode()
            if not self._async:
                finished.extend(self._consume())
        self.steps += 1
        return finished

    def run_until_drained(self, max_steps: int = 10_000) -> List[Request]:
        """Run until queue + slots are empty (or ``max_steps`` decode steps
        have run *in this call* — a long-lived engine keeps serving across
        calls); returns every request that finished during the run."""
        finished: List[Request] = []
        start = self.steps
        while (self.queue or any(r is not None
                                 for r in self.active.values())):
            if self.steps - start >= max_steps:
                break
            out = self.step()
            if out is None:
                break
            finished.extend(out)
        return finished

    # ------------------------------------------------------------- metrics
    def metrics(self) -> Dict[str, Any]:
        """Engine throughput/latency counters + backend occupancy.

        Per-request latency aggregates (``ttft_*``, ``decode_tok_s_mean``)
        cover requests finished so far — the inputs ``benchmarks/ci_gate``
        and ``serve_bench`` gate on, not just aggregate steps/s."""
        m = {
            "engine_cycles": self.steps,
            "decode_steps": self.decode_steps,
            "tokens_generated": self.tokens_generated,
            "requests_admitted": self.requests_admitted,
            "requests_finished": self.requests_finished,
            "prefill_calls": self.prefill_calls,
            "prefill_traces": self.prefill_traces,
            "prefill_s": self.prefill_s,
            "decode_s": self.decode_s,
            "decode_steps_per_s": (self.decode_steps / self.decode_s
                                   if self.decode_s else 0.0),
            "tokens_per_s": (self.tokens_generated
                             / (self.decode_s + self.prefill_s)
                             if self.decode_s + self.prefill_s else 0.0),
            "deferrals": self.deferrals,
            "tp": self.tp,
            "kv_shards": self._kv_shards,
            "async_dispatch": self._async,
            "kv_bytes_streamed": self.kv_bytes_streamed,
            "kv_bytes_streamed_per_device": self.kv_bytes_streamed_per_device,
            "host_overlap_s": self.host_overlap_s,
            "stream_wait_s": self.stream_wait_s,
            "dispatch_overlap_fraction": (
                self.host_overlap_s
                / (self.host_overlap_s + self.stream_wait_s)
                if self.host_overlap_s + self.stream_wait_s > 0 else 0.0),
            "ttft_s_mean": (float(np.mean(self._ttfts))
                            if self._ttfts else 0.0),
            "ttft_s_p50": (float(np.percentile(self._ttfts, 50))
                           if self._ttfts else 0.0),
            "ttft_s_p95": (float(np.percentile(self._ttfts, 95))
                           if self._ttfts else 0.0),
            "decode_tok_s_mean": (float(np.mean(self._decode_rates))
                                  if self._decode_rates else 0.0),
            "decode_tok_s_p95": (float(np.percentile(self._decode_rates, 95))
                                 if self._decode_rates else 0.0),
        }
        if self.chunked:
            m.update({
                "chunked_prefill": True,
                "chunk_size": self.chunk_size,
                "chunk_calls": self.chunk_calls,
                "chunk_utilization": (
                    self.chunk_tokens / (self.chunk_calls * self.chunk_size)
                    if self.chunk_calls else 0.0),
                "prefix_hit_rate": (self.shared_tokens / self.prefill_tokens
                                    if self.prefill_tokens else 0.0),
            })
        if self.spec_k:
            m.update({
                "speculate_k": self.spec_k,
                "draft_steps": self.draft_steps,
                "verify_passes": self.verify_passes,
                "draft_tokens_proposed": self.draft_tokens_proposed,
                "draft_tokens_accepted": self.draft_tokens_accepted,
                "acceptance_rate": (
                    self.draft_tokens_accepted / self.draft_tokens_proposed
                    if self.draft_tokens_proposed else 0.0),
                # per-SLOT passes, so batching cannot inflate it: 1.0 at
                # zero acceptance, k+1 at full acceptance — the (k+1)x
                # useful-work-per-weight-byte factor of the roofline story
                "tokens_per_target_pass": (
                    self.spec_tokens_emitted / self.spec_slot_passes
                    if self.spec_slot_passes else 0.0),
                "rollback_pages": self.rollback_pages,
            })
        m.update(self.backend.stats())
        return m

    def reset_metrics(self):
        """Zero the metrics window (counters, timers, latency samples) so a
        long-lived engine can report per-interval numbers.  Does NOT touch
        scheduling state: ``steps`` keeps counting (in-flight ``*_step``
        deltas stay valid) and ``_admission_seq`` — the sampling-nonce
        source — never resets, so a slot reused after a reset cannot replay
        a predecessor's randomness."""
        self.tokens_generated = 0
        self.requests_admitted = 0
        self.requests_finished = 0
        self.deferrals = 0
        self.prefill_calls = 0
        self.prefill_s = 0.0
        self.decode_s = 0.0
        self.decode_steps = 0
        self.chunk_calls = 0
        self.chunk_tokens = 0
        self.prefill_tokens = 0
        self.shared_tokens = 0
        self.kv_bytes_streamed = 0
        self.kv_bytes_streamed_per_device = 0
        self.host_overlap_s = 0.0
        self.stream_wait_s = 0.0
        self.draft_steps = 0
        self.verify_passes = 0
        self.draft_tokens_proposed = 0
        self.draft_tokens_accepted = 0
        self.spec_tokens_emitted = 0
        self.spec_slot_passes = 0
        self.rollback_pages = 0
        self._ttfts.clear()
        self._decode_rates.clear()
