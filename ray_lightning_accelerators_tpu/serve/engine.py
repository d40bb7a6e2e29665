"""Continuous-batching serve engine over the static-shaped decode loop.

**Paged KV cache (default)**: instead of one dense
``[L, max_slots, H, max_total_len, D]`` cache — which pins HBM
proportional to ``max_total_len − actual_len`` for every slot — the
engine owns a fixed pool of ``[L, n_blocks, H, block_len, D]`` KV blocks
plus a per-slot int32 block table.  Decode attention reads through the
indirection (a gather over the table INSIDE the jitted step; tables are
traced operands), so the engine's whole lifecycle is TWO compiled
program families, none ever retraced per request:

- **chunk prefill** (one per suffix-length bucket): run the right-padded
  un-shared part of a prompt through `GPT.decode_chunk_paged`, writing
  its k/v into the request's table-mapped blocks and returning the first
  greedy token;
- **step**: one ``decode_step_rows_paged`` over ALL slots at per-row
  positions, argmax per row.

Joining, retiring and GROWING a sequence (its position crossing a block
boundary into the next pre-reserved block) are host-side table writes —
the PR 2 no-recompile invariant, preserved through the indirection and
pinned by ``analysis.compile_guard`` in the tests.

**Shared-prefix reuse**: prompts are hashed block-wise at admission
(a chain hash, so a block key commits to the WHOLE prefix before it);
full blocks matching the allocator's LRU prefix index are mapped into
the new request's table with a refcount instead of re-prefilled —
system-prompt-heavy traffic skips most of its prefill compute and
shares the HBM.  This is copy-on-write where the copy branch is
provably unreachable: sharers only ever WRITE at positions past their
shared full-prefix blocks (suffix prefill starts at the first un-shared
block; decode writes at ``pos >= prompt_len``), so refcounts alone
guarantee safety.  Evicting an unreferenced cached block is an LRU pop.

**Speculative lane**: constructed with a draft model, an idle engine
routes ``submit(..., speculative=True)`` requests through greedy
speculative decode — the draft proposes ``spec_k`` tokens per round
(`models.speculative.build_draft_proposer`), the target verifies them
in ONE paged chunk pass that drafts into the request's scratch blocks,
and only accepted tokens' positions survive (rejected positions are
rewritten before the causal mask can expose them — the linear-cache
no-rollback property, inherited by the paged layout).  A busy engine
decodes the same request in a normal slot; either lane obeys the
exactness contract, so clients cannot tell them apart.

**Exactness contract**: greedy only; every response is token-identical
to a standalone ``GPT.generate(prompt, max_new_tokens)`` of that
prompt.  This holds because the paged attention performs the same
arithmetic per attended position as the dense decode paths (gathers are
exact value copies; masked positions contribute exactly-zero softmax
terms), pad positions are rewritten before the mask exposes them, and
shared prefix blocks hold bit-identical k/v for an identical token
prefix (k/v are deterministic functions of the prefix).  The CPU test
suite asserts it token-for-token — across staggered join/retire, block
growth, prefix hits and the speculative lane.

``paged=False`` keeps the PR 2 dense allocator (three compiled
programs: bucketed pad-prefill, slot join, batched step) — the probe
uses it as the placed-bytes baseline the paged pool is judged against.
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import OrderedDict, deque
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..telemetry import recorder as telemetry
from ..utils.logging import log
from .batcher import (AdmissionController, ServeCancelled, ServeRequest,
                      ServeResponse, blocks_for_request,
                      chain_prefix_keys)
from .metrics import ServeMetrics

# live-plane labels for engines sharing one process (telemetry/live.py)
_ENGINE_SEQ = itertools.count()


class BlockAllocator:
    """Host-side bookkeeping for the paged pool's physical blocks: a
    free list, per-block refcounts, and an LRU prefix index mapping
    chain-hash keys of FULL prompt blocks to the physical block holding
    their k/v.

    Lifetimes: a freshly allocated block starts at refcount 1 (its
    owner); a prefix hit retains (+1) the shared block for the new
    sharer.  ``release`` drops a reference; an unreferenced block
    returns to the free list UNLESS it is registered in the prefix
    index, where it stays resident as reusable cache until LRU eviction
    reclaims it for a new allocation.  Block 0 is reserved as the
    garbage block (inactive decode rows scatter there) and is never
    handed out.

    Thread-safety: a single lock — the engine loop owns alloc/release,
    but the metrics gauge reads ``stats()`` from other threads.
    """

    def __init__(self, n_blocks: int, block_len: int):
        if n_blocks < 2:
            raise ValueError("the pool needs >= 2 blocks (block 0 is "
                             "the reserved garbage block)")
        if block_len < 1:
            raise ValueError("block_len must be >= 1")
        self.n_blocks = n_blocks
        self.block_len = block_len
        self._lock = threading.Lock()
        self._free: deque = deque(range(1, n_blocks))
        self._ref = np.zeros((n_blocks,), np.int32)
        self._index: "OrderedDict[str, int]" = OrderedDict()  # LRU
        self._key_of: Dict[int, str] = {}

    # ------------------------------------------------------------------ #
    def alloc(self, n: int) -> Optional[List[int]]:
        """``n`` fresh blocks at refcount 1, or None when even evicting
        every unreferenced cached prefix block cannot free enough."""
        with self._lock:
            if n <= 0:
                return []
            while len(self._free) < n:
                if not self._evict_one_locked():
                    return None
            out = [self._free.popleft() for _ in range(n)]
            for b in out:
                self._ref[b] = 1
            return out

    def _evict_one_locked(self) -> bool:
        victim = None
        for key, blk in self._index.items():  # oldest (LRU) first
            if self._ref[blk] == 0:
                victim = (key, blk)
                break
        if victim is None:
            return False
        key, blk = victim
        del self._index[key]
        del self._key_of[blk]
        self._free.append(blk)
        return True

    def lookup_run(self, keys: List[str], max_blocks: int) -> List[int]:
        """Longest run of prefix-index hits from block 0, each RETAINED
        for the caller (and bumped to MRU).  ``max_blocks`` caps the run
        (the engine keeps >= 1 suffix token so the last prompt hidden
        state is actually computed)."""
        out: List[int] = []
        with self._lock:
            for key in keys[:max_blocks]:
                blk = self._index.get(key)
                if blk is None:
                    break
                self._index.move_to_end(key)
                self._ref[blk] += 1
                out.append(blk)
        return out

    def release(self, block: int) -> None:
        """Drop one reference; unreferenced unregistered blocks go back
        to the free list, registered ones stay cached (evictable)."""
        with self._lock:
            self._ref[block] -= 1
            if self._ref[block] <= 0:
                self._ref[block] = 0
                if block not in self._key_of:
                    self._free.append(block)

    def register(self, key: str, block: int) -> bool:
        """Publish a full prompt block under its chain-hash key for
        future prefix hits.  First writer wins: if another block already
        carries the key (two identical prompts admitted concurrently),
        the caller's block stays private and is freed at retire."""
        with self._lock:
            if key in self._index or block in self._key_of:
                return False
            self._index[key] = block
            self._key_of[block] = key
            return True

    def stats(self) -> Dict[str, int]:
        with self._lock:
            used = int((self._ref[1:] > 0).sum())
            cached = sum(1 for b in self._key_of
                         if self._ref[b] == 0)
            return {"total": self.n_blocks - 1, "used": used,
                    "cached": cached, "free": len(self._free)}


class _Slot:
    """Host-side state of one active decode slot."""

    __slots__ = ("req", "resp", "pos", "last", "generated", "remaining",
                 "t_last", "blocks")

    def __init__(self, req: ServeRequest, resp: ServeResponse, pos: int,
                 first_token: int, t_now: float,
                 blocks: Optional[List[int]] = None):
        self.req = req
        self.resp = resp
        self.pos = pos                    # position of the token to feed
        self.last = first_token           # token to feed next step
        self.generated = [first_token]
        self.remaining = req.max_new_tokens - 1
        self.t_last = t_now               # per-token latency anchor
        self.blocks = blocks or []        # physical KV blocks (paged)


class _PrefillCursor:
    """A long prompt streaming in through chunked prefill: blocks-so-far
    plus the next prompt position to feed.  The cursor owns its blocks
    (released exactly once on completion-failure/cancel/death, like a
    slot's), but its slot's row in the engine's table array stays ZEROED
    until completion — decode feeds inactive rows token 0 at position 0,
    and that write must keep routing to the reserved garbage block, not
    into a half-prefilled prompt's block 0."""

    __slots__ = ("req", "resp", "blocks", "shared", "keys", "pos",
                 "chunks", "t_start")

    def __init__(self, req: ServeRequest, resp: ServeResponse,
                 shared: List[int], keys: List[str], pos: int,
                 t_start: float):
        self.req = req
        self.resp = resp
        self.blocks = list(shared)   # grows as chunks land
        self.shared = list(shared)   # prefix-cache hits (refcounted)
        self.keys = keys
        self.pos = pos               # next prompt position to feed
        self.chunks = 0
        self.t_start = t_start       # prefill-duration anchor


class ServeEngine:
    """Continuous-batching greedy inference over one model replica.

    ``max_slots``: fixed decode batch.  ``queue_depth``: admission cap
    beyond the slots (backpressure).  ``max_total_len``: per-slot token
    budget; prompt + max_new_tokens of every request must fit (defaults
    to the model's max_seq_len).

    Paged knobs (``paged=True``, the default): ``block_len`` tokens per
    KV block; ``n_blocks`` physical blocks in the pool (+1 reserved
    garbage block; default gives every slot its full ``max_total_len``
    worth — shrink it to trade worst-case capacity for HBM, admission
    rejects/backpressures typed against the real pool);
    ``prefix_cache`` enables shared-prefix reuse;
    ``pool_overcommit`` scales the admission-time worst-case block
    budget (> 1.0 banks on prefix sharing).  ``draft_model`` /
    ``draft_params`` / ``spec_k`` arm the speculative lane.

    ``chunked_prefill`` (default on, paged only): prompts spanning more
    than RLA_TPU_SERVE_CHUNK_BLOCKS KV blocks stream through
    ``decode_chunk_paged`` in pool-bounded chunks INTERLEAVED with live
    decode steps — big chunks while decode is idle, small
    (RLA_TPU_SERVE_CHUNK_MIN_BLOCKS) chunks between decode waves — so
    one long prompt monopolizes neither the decode cadence nor its
    disaggregated prefill lane.  Admission then judges prompts against
    the model's ``max_seq_len`` rather than the ``max_total_len``
    bucket (the per-slot block table spans the model), a paused prefill
    holds only its blocks-so-far, and the chunk buckets are the
    existing prefill buckets so steady state compiles nothing new.
    Token-identical to whole-prompt prefill (greedy argmax over the
    same positions).  The speculative lane keeps blocking prefill (and
    a draft model pins the table span to ``max_total_len`` — its dense
    cache must cover every padded bucket).

    ``paged=False``: the PR 2 dense allocator; ``prompt_block`` then
    bounds prefill compile count (paged mode buckets by ``block_len``).
    """

    def __init__(self, model: Any, params: Any, *, max_slots: int = 4,
                 queue_depth: int = 64,
                 max_total_len: Optional[int] = None,
                 max_new_tokens_cap: Optional[int] = None,
                 prompt_block: int = 8,
                 metrics: Optional[ServeMetrics] = None,
                 perf_timeline: Any = None,
                 idle_poll_s: float = 0.05,
                 paged: bool = True,
                 block_len: int = 16,
                 n_blocks: Optional[int] = None,
                 prefix_cache: bool = True,
                 pool_overcommit: float = 1.0,
                 draft_model: Any = None,
                 draft_params: Any = None,
                 spec_k: int = 4,
                 slo: Any = "env",
                 handoff_wave_bytes: Optional[int] = None,
                 chunked_prefill: bool = True):
        import jax

        from ..utils import compile_cache
        compile_cache.enable()  # before this engine's first compile
        if hasattr(model, "_uniform_stack_only"):
            # a conv layer's serving state is not a KV block (ROADMAP R5)
            model._uniform_stack_only("ServeEngine")
        if model.cfg.sliding_window is not None:
            raise ValueError(
                "the serve engine needs linear cache slots; "
                "sliding_window models are unsupported (their rolling "
                "ring cache cannot slot-join)")
        if max_slots < 1:
            raise ValueError("max_slots must be >= 1")
        W = (max_total_len if max_total_len is not None
             else model.cfg.max_seq_len)
        if W > model.cfg.max_seq_len:
            raise ValueError(
                f"max_total_len {W} exceeds the model's max_seq_len "
                f"{model.cfg.max_seq_len}")
        self.model = model
        self.params = jax.tree.map(jax.numpy.asarray, params)
        self.max_slots = max_slots
        self.max_total_len = W
        self.paged = bool(paged)
        self.metrics = metrics or ServeMetrics()
        # optional telemetry.perf.StepTimeline: the engine loop feeds
        # its prefill/decode phase times into the same per-step ledger
        # the trainer uses (phases "prefill"/"decode"; aggregate-only —
        # the loop has no optimizer-step bracket)
        self.perf_timeline = perf_timeline
        self._idle_poll_s = idle_poll_s
        self._jax = jax
        # donate the cache/pool operand where donation is real (TPU/GPU):
        # the hot loop reassigns the cache every call, so without
        # donation each step/join copies the whole [L,...] pair and
        # doubles peak cache memory.  CPU ignores donation with a
        # warning per call site -- skip it there to keep test logs quiet.
        donate = jax.default_backend() != "cpu"
        self._donate = donate

        # -- SLO engine (serve/slo.py) --------------------------------- #
        # slo: an SloPolicy, None (disabled), or "env" (default — built
        # from the SLO knobs, see analysis/knobs.py; no knob set = no tracker, zero
        # per-request overhead).  With a policy attached: admission
        # stamps each request's absolute deadline, expired requests are
        # shed typed BEFORE prefill, TTFT/token-cadence observations
        # feed the rolling burn-rate window, and the slo_burn_rate /
        # slo_violations_total signals ride every metrics snapshot.
        from .slo import SloPolicy, SloTracker
        if slo == "env":
            slo = SloPolicy.from_env()
        if slo is not None and not isinstance(slo, SloPolicy):
            raise ValueError(
                "slo must be an SloPolicy, None, or 'env'; got "
                f"{type(slo).__name__}")
        self.slo_policy = slo if slo is not None and slo.enabled else None
        self._slo = (SloTracker(self.slo_policy, self.metrics)
                     if self.slo_policy is not None else None)
        if self._slo is not None:
            self.metrics.bind_slo(self._slo.gauges)

        # -- speculative lane ------------------------------------------ #
        self.draft_model = draft_model
        self.draft_params = None
        self.spec_k = int(spec_k)
        if draft_model is not None:
            if not self.paged:
                raise ValueError("the speculative lane needs the paged "
                                 "engine (its chunk scorer drafts into "
                                 "scratch blocks); pass paged=True")
            if spec_k < 1:
                raise ValueError("spec_k must be >= 1")
            if draft_model.cfg.sliding_window is not None:
                raise ValueError("speculative decoding needs a linear "
                                 "draft cache (sliding_window "
                                 "unsupported)")
            if draft_model.cfg.vocab_size != model.cfg.vocab_size:
                raise ValueError(
                    f"draft vocab {draft_model.cfg.vocab_size} != target "
                    f"vocab {model.cfg.vocab_size}")
            self.draft_params = jax.tree.map(jax.numpy.asarray,
                                             draft_params)
            from ..models.speculative import build_draft_proposer
            self._d_propose = build_draft_proposer(
                draft_model, self.draft_params, self.spec_k)

        if self.paged:
            self.block_len = int(block_len)
            if self.block_len < 1:
                raise ValueError("block_len must be >= 1")
            headroom = self.spec_k if draft_model is not None else 0
            self.max_blocks_per_slot = -(-(W + headroom) // self.block_len)
            # chunked long-prompt prefill: the per-slot block-table SPAN
            # widens to the model's max_seq_len so admission stops
            # refusing prompts longer than the max_total_len bucket —
            # the pool budget (not the table width) bounds what can
            # actually place.  Pool sizing, the one-full-request floor
            # and the dense-equivalent gauge all stay keyed to
            # max_total_len: capacity parity is about the DECODE working
            # set, and a streaming prefill holds only its blocks-so-far.
            self.chunked_prefill = bool(chunked_prefill)
            from ..analysis import knobs as _knobs
            self._chunk_blocks = max(1, _knobs.get_int(
                "RLA_TPU_SERVE_CHUNK_BLOCKS", 8))
            self._chunk_min_blocks = max(1, min(
                _knobs.get_int("RLA_TPU_SERVE_CHUNK_MIN_BLOCKS", 1),
                self._chunk_blocks))
            self.table_blocks = self.max_blocks_per_slot
            if self.chunked_prefill and draft_model is None:
                self.table_blocks = max(
                    self.table_blocks,
                    -(-model.cfg.max_seq_len // self.block_len))
            if n_blocks is None:
                # capacity parity with the dense allocator by default:
                # the HBM win comes from sizing the pool BELOW this
                n_blocks = max_slots * self.max_blocks_per_slot + 1
            if n_blocks < self.max_blocks_per_slot + 1:
                raise ValueError(
                    f"n_blocks {n_blocks} cannot hold even one full "
                    f"request ({self.max_blocks_per_slot} blocks + the "
                    "reserved garbage block)")
            self.n_blocks = int(n_blocks)
            if draft_model is not None:
                # the draft's FIXED dense cache must cover every padded
                # prompt bucket + drafting headroom (one program per
                # bucket; block rounding may admit prompts past W)
                self._draft_cache_len = (self.max_blocks_per_slot
                                         * self.block_len + self.spec_k)
                if draft_model.cfg.max_seq_len < self._draft_cache_len:
                    raise ValueError(
                        f"draft max_seq_len "
                        f"{draft_model.cfg.max_seq_len} < the engine's "
                        f"block-table span + spec_k "
                        f"({self._draft_cache_len})")
            self.prefix_cache = bool(prefix_cache)
            self.allocator = BlockAllocator(self.n_blocks, self.block_len)
            self.prompt_block = self.block_len  # buckets = block multiples
            self.batcher = AdmissionController(
                queue_depth=queue_depth,
                max_new_tokens_cap=max_new_tokens_cap,
                block_len=self.block_len,
                pool_blocks=self.n_blocks - 1,
                max_blocks_per_slot=self.table_blocks,
                spec_headroom=headroom,
                pool_overcommit=pool_overcommit,
                hard_total_cap=model.cfg.max_seq_len,
                slo_policy=self.slo_policy)
            self._tables = np.zeros(
                (max_slots, self.table_blocks), np.int32)
            self.metrics.bind_pool(self._pool_gauges)
            self.metrics.bind_chunks(lambda: {
                "active_long_prefills": sum(
                    1 for c in self._cursors if c is not None)})

            def step_tokens(p, pool, tables, t, pos):
                # argmax INSIDE the compiled step (compile-guard pins the
                # program count); D2H per step is [B] tokens + [B] health
                # bits (the numeric guard: per-row all-finite logits,
                # riding the feed-gate sync the loop pays anyway)
                logits, pool = model.decode_step_rows_paged(
                    p, pool, tables, t, pos)
                ok = jax.numpy.all(
                    jax.numpy.isfinite(logits),
                    axis=tuple(range(1, logits.ndim)))
                return jax.numpy.argmax(logits, -1).astype(
                    jax.numpy.int32), ok, pool

            self._step = jax.jit(step_tokens,
                                 donate_argnums=(1,) if donate else ())
        else:
            self.chunked_prefill = False  # dense rows cannot chunk-join
            self.prompt_block = max(1, prompt_block)
            self.batcher = AdmissionController(
                queue_depth=queue_depth, max_total_len=W,
                max_new_tokens_cap=max_new_tokens_cap,
                slo_policy=self.slo_policy)
            self._join = jax.jit(type(model).cache_join,
                                 donate_argnums=(0,) if donate else ())

            def step_tokens(p, c, t, pos):
                logits, cache = model.decode_step_rows(p, c, t, pos)
                ok = jax.numpy.all(
                    jax.numpy.isfinite(logits),
                    axis=tuple(range(1, logits.ndim)))
                return jax.numpy.argmax(logits, -1).astype(
                    jax.numpy.int32), ok, cache

            self._step = jax.jit(step_tokens,
                                 donate_argnums=(1,) if donate else ())
        self.metrics.bind_queue(lambda: self.batcher.depth)
        # -- KV handoff (disaggregated prefill/decode lanes) ------------ #
        # An export request's prefilled blocks stay pinned here (with
        # their object-store wave refs) until the decode side confirms
        # the copy landed and the driver calls release_handoff — the
        # exactly-once seam: a decode-replica crash mid-import can
        # always fall back to the still-resident source blocks.
        if handoff_wave_bytes is None:
            from ..analysis import knobs
            handoff_wave_bytes = knobs.get_int(
                "RLA_TPU_SERVE_HANDOFF_WAVE_BYTES", 4 << 20)
        self.handoff_wave_bytes = max(1, int(handoff_wave_bytes))
        self._handoff_lock = threading.Lock()
        self._handoffs: Dict[int, Tuple[ServeRequest, List[int],
                                        List[Any]]] = {}
        self._handoff_ids = itertools.count()
        self._prefills: Dict[Any, Any] = {}
        self._cache = None          # dense cache OR paged pool
        self._pool_bytes = 0        # measured placed pool bytes (paged)
        self._slots: List[Optional[_Slot]] = [None] * max_slots
        self._cursors: List[Optional[_PrefillCursor]] = [None] * max_slots
        self._spec_active = 0
        self._stop = threading.Event()
        self._cancel_active = False
        self._thread: Optional[threading.Thread] = None
        self._live_label: Optional[str] = None
        # mesh mutation LAST, after every validation that can raise: a
        # failed construction must not hand the caller back a model
        # silently stripped of its training mesh.  Decode runs
        # replicated, exactly like generate() — a training-time mesh
        # must not carve up step-sized activations (jit tracing is lazy,
        # so nulling here still precedes every trace).
        self._mesh_saved, model.mesh = model.mesh, None
        if draft_model is not None:
            self._draft_mesh_saved = draft_model.mesh
            draft_model.mesh = None

    # ------------------------------------------------------------------ #
    # Lifecycle                                                          #
    # ------------------------------------------------------------------ #
    def start(self) -> "ServeEngine":
        if self._thread is not None:
            raise RuntimeError("engine already started")
        if self.paged:
            self._cache = self.model.paged_cache_alloc(self.n_blocks,
                                                       self.block_len)
        else:
            self._cache = self.model.decode_cache_alloc(
                self.max_slots, self.max_total_len)
        # placed-bytes truth for the waste-ratio gauges (and the probe's
        # dense baseline): the real arrays' nbytes, not a formula
        self._pool_bytes = int(self._cache["k"].nbytes
                               + self._cache["v"].nbytes)
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="rla-tpu-serve-engine")
        self._thread.start()
        # live telemetry plane (telemetry/live.py): when
        # RLA_TPU_METRICS_PORT is configured, this engine's live
        # ServeMetrics (+ SLO burn rate) become scrapeable on the
        # process's /metrics and /statusz while it serves
        from ..telemetry import live as live_lib
        srv = live_lib.maybe_start_from_env()
        if srv is not None:
            self._live_label = f"engine{next(_ENGINE_SEQ)}"
            srv.sources.add_serve(self._live_label, self.metrics,
                                  slo=self._slo)
        return self

    def stop(self, cancel_active: bool = False,
             timeout: float = 60.0) -> None:
        """Stop admitting; by default FINISH the in-flight slots (their
        budgets bound the wait), cancel everything still queued with
        ``ServeCancelled``, then join the loop.  ``cancel_active=True``
        cancels in-flight requests too (fast teardown)."""
        self._cancel_active = cancel_active
        self._stop.set()
        self.batcher.kick()
        if self._thread is not None:
            self._thread.join(timeout=timeout)
            self._thread = None
        n = self.batcher.shutdown()
        if n:
            self.metrics.inc("cancelled", n)
        # any export holds never released by the driver (tier teardown
        # mid-handoff): free their blocks and object-store payloads now
        with self._handoff_lock:
            held = list(self._handoffs.keys())
        for hid in held:
            self.release_handoff(hid)
        if self._live_label is not None:
            from ..telemetry import live as live_lib
            srv = live_lib.get_server()
            if srv is not None:
                srv.sources.remove_serve(self._live_label)
            self._live_label = None
        self.model.mesh = self._mesh_saved
        if self.draft_model is not None:
            self.draft_model.mesh = self._draft_mesh_saved

    def __enter__(self) -> "ServeEngine":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # ------------------------------------------------------------------ #
    # Client surface                                                     #
    # ------------------------------------------------------------------ #
    def submit(self, prompt: Any, max_new_tokens: int,
               speculative: bool = False) -> ServeResponse:
        """Admit a request (typed QueueFull/PoolExhausted/RequestRejected
        backpressure); the response resolves to prompt + greedily
        generated tokens, token-identical to ``generate()``.
        ``speculative=True`` hints the engine to route this single-stream
        request through the speculative lane when it is idle (needs a
        draft model; a busy engine uses a normal slot)."""
        from .batcher import PoolExhausted, QueueFull, RequestRejected
        if speculative and self.draft_model is None:
            self.metrics.inc("rejected")  # typed rejections all count
            raise RequestRejected(
                "speculative routing needs a draft model: construct the "
                "engine with draft_model=/draft_params=")
        try:
            resp = self.batcher.submit(prompt, max_new_tokens,
                                       speculative=speculative)
        except PoolExhausted:
            self.metrics.inc("rejected")
            self.metrics.inc("pool_exhausted")
            raise
        except (QueueFull, RequestRejected):
            # admission rejections only: a ServeCancelled from a stopping
            # engine must not read as overload in the counters
            self.metrics.inc("rejected")
            raise
        self.metrics.inc("submitted")
        # per-request trace (minted at admission): the whole
        # admit -> prefill -> respond lifecycle shares it
        telemetry.emit("serve_admit", trace=resp.request.trace_id,
                       request=resp.request.request_id,
                       prompt_len=int(resp.request.prompt.size))
        return resp

    def submit_handoff(self, prompt: Any, max_new_tokens: int, *,
                       t_submit: Optional[float] = None,
                       deadline: Optional[float] = None,
                       trace_id: Optional[str] = None) -> ServeResponse:
        """Admit a PREFILL-ONLY request (the disaggregated prefill
        lane, serve/replicas.py): the engine prefills the prompt into
        its pool and the response resolves to a KV handoff DESCRIPTOR —
        a picklable dict a decode-lane engine turns back into a live
        slot via ``submit_import`` — instead of tokens.  The prefilled
        blocks stay pinned on this engine until ``release_handoff``.
        ``t_submit``/``deadline``/``trace_id`` carry the client's
        ORIGINAL stamps so the hop never resets the SLO clock."""
        from .batcher import PoolExhausted, QueueFull, RequestRejected
        if not self.paged:
            self.metrics.inc("rejected")
            raise RequestRejected(
                "KV handoff needs the paged engine (the descriptor is a "
                "block-table span); pass paged=True")
        try:
            resp = self.batcher.submit(prompt, max_new_tokens,
                                       export_handoff=True,
                                       t_submit=t_submit,
                                       deadline=deadline,
                                       trace_id=trace_id)
        except PoolExhausted:
            self.metrics.inc("rejected")
            self.metrics.inc("pool_exhausted")
            raise
        except (QueueFull, RequestRejected):
            self.metrics.inc("rejected")
            raise
        self.metrics.inc("submitted")
        telemetry.emit("serve_admit", trace=resp.request.trace_id,
                       request=resp.request.request_id,
                       prompt_len=int(resp.request.prompt.size),
                       export_handoff=True)
        return resp

    def submit_import(self, descriptor: Dict[str, Any]) -> ServeResponse:
        """Admit a request whose prefill ALREADY HAPPENED on a prefill-
        lane engine: ``descriptor`` is a ``submit_handoff`` result.  The
        engine allocates fresh physical blocks, replays the descriptor's
        object-store waves into them (the block-id remap), and the
        request starts life mid-decode — the response resolves to
        prompt + generated tokens exactly like ``submit``.  Bypasses the
        queue-depth cap (the request was admitted once at the tier) but
        not the pool check: the blocks are real memory here."""
        from .batcher import PoolExhausted, QueueFull, RequestRejected
        if not self.paged:
            self.metrics.inc("rejected")
            raise RequestRejected(
                "KV handoff import needs the paged engine; pass "
                "paged=True")
        if int(descriptor.get("block_len", -1)) != self.block_len:
            self.metrics.inc("rejected")
            raise RequestRejected(
                f"handoff block_len {descriptor.get('block_len')} != "
                f"this engine's block_len {self.block_len}: a block-id "
                "remap cannot re-tile blocks")
        try:
            resp = self.batcher.submit(
                descriptor["prompt"], int(descriptor["max_new_tokens"]),
                import_handoff=descriptor,
                t_submit=descriptor.get("t_submit"),
                deadline=descriptor.get("deadline"),
                trace_id=descriptor.get("trace_id"))
        except PoolExhausted:
            self.metrics.inc("rejected")
            self.metrics.inc("pool_exhausted")
            raise
        except (QueueFull, RequestRejected):
            self.metrics.inc("rejected")
            raise
        self.metrics.inc("submitted")
        telemetry.emit("serve_admit", trace=resp.request.trace_id,
                       request=resp.request.request_id,
                       prompt_len=int(resp.request.prompt.size),
                       import_handoff=True)
        return resp

    def release_handoff(self, handoff_id: int) -> bool:
        """Drop an export's hold: release its pinned blocks (registered
        full prompt blocks stay LRU-cached in the prefix index — the
        source keeps serving prefix hits until eviction reclaims them),
        return its admission reservation, and delete the object-store
        wave payloads.  Idempotent; safe from any thread (the allocator,
        admission controller and object store are each internally
        locked, and release never touches the device pool)."""
        with self._handoff_lock:
            held = self._handoffs.pop(handoff_id, None)
        if held is None:
            return False
        req, blocks, refs = held
        for b in blocks:
            self.allocator.release(b)
        self.batcher.release_blocks(req)
        from ..runtime import object_store
        store = object_store.global_store()
        for ref in refs:
            try:
                store.delete(ref)
            except Exception:
                pass  # best-effort: a dead owner already unlinked
        telemetry.emit("serve_kv_release", request=req.request_id,
                       handoff=handoff_id, blocks=len(blocks))
        return True

    def stats(self) -> Dict[str, Any]:
        out = self.metrics.snapshot()
        if self._slo is not None:
            # the ttft-vs-cadence burn split rides every stats snapshot
            # so the tier's lane autoscaler reads it for free
            # (serve/controller.py _lane_for_growth_locked)
            out["slo_families"] = self._slo.family_rates()
        return out

    # ------------------------------------------------------------------ #
    # Pool gauges (paged)                                                #
    # ------------------------------------------------------------------ #
    def _pool_gauges(self) -> Dict[str, Any]:
        """Live block-pool occupancy + HBM truth for the metrics
        snapshot.  ``dense_equivalent_bytes`` is what the PR 2 dense
        allocator would pin for the SAME live sequences (one full
        max-length row each); ``cache_waste_ratio`` is the fraction of
        that the paged layout avoids."""
        st = self.allocator.stats()
        per_block = (self._pool_bytes / self.n_blocks
                     if self._pool_bytes else 0.0)
        row_bytes = per_block * self.max_blocks_per_slot
        active = sum(1 for s in self._slots if s is not None) \
            + sum(1 for c in self._cursors if c is not None) \
            + self._spec_active
        used_bytes = st["used"] * per_block
        dense_eq = active * row_bytes
        return {
            "block_pool_total": st["total"],
            "block_pool_used": st["used"],
            "block_pool_cached": st["cached"],
            "block_pool_free": st["free"],
            "block_pool_occupancy": (st["used"] / st["total"]
                                     if st["total"] else 0.0),
            "block_len": self.block_len,
            "hbm_cache_bytes": self._pool_bytes,
            "hbm_used_bytes": int(used_bytes),
            "dense_equivalent_bytes": int(dense_eq),
            "cache_waste_ratio": (1.0 - used_bytes / dense_eq
                                  if dense_eq > 0 else 0.0),
        }

    # ------------------------------------------------------------------ #
    # Driver loop                                                        #
    # ------------------------------------------------------------------ #
    def _run(self) -> None:
        try:
            while True:
                if not self._stop.is_set():
                    self._admit()
                active = [i for i, s in enumerate(self._slots)
                          if s is not None]
                prefilling = self.paged and any(
                    c is not None for c in self._cursors)
                if self._stop.is_set() and self._cancel_active \
                        and (active or prefilling):
                    self._cancel_slots()
                    continue
                if prefilling:
                    # cadence-aware chunk budget: big chunks while
                    # decode is idle, small chunks between decode waves
                    self._advance_prefills(decode_active=bool(active))
                    # a cursor that completed THIS iteration just armed
                    # its slot's block table: recompute the wave so the
                    # row decodes now — a stale wave would feed token 0
                    # at position 0 THROUGH the armed table and stomp
                    # the prompt's first block of KV
                    active = [i for i, s in enumerate(self._slots)
                              if s is not None]
                if active:
                    self._decode_step(active)
                elif prefilling:
                    continue  # cursors advancing; no decode, no sleep
                elif self._stop.is_set():
                    return
                else:
                    self.batcher.wait_for_work(self._idle_poll_s)
        except BaseException as e:  # engine death must fail loudly, typed
            log.error("serve engine loop died: %s", e)
            for i, s in enumerate(self._slots):
                if s is not None:
                    if s.resp._fail(e):
                        self.metrics.inc("failed")
                    self._release_request(s.req, s.blocks)
                self._slots[i] = None
            for i, cur in enumerate(self._cursors):
                # a mid-stream prefill's blocks-so-far release exactly
                # once, like a slot's (the tier requeues the request)
                if cur is not None:
                    if cur.resp._fail(e):
                        self.metrics.inc("failed")
                    self._release_request(cur.req, cur.blocks)
                self._cursors[i] = None
            n = self.batcher.shutdown()
            if n:  # keep completed+failed+cancelled == submitted honest
                self.metrics.inc("cancelled", n)
            raise

    def _bucket(self, s0: int) -> int:
        b = self.prompt_block
        return min(-(-s0 // b) * b, self.max_total_len)

    # -- compiled-program memos ---------------------------------------- #
    def _prefill_fn(self, padded_len: int):
        """Dense bucketed pad-prefill (paged=False)."""
        key = ("dense", padded_len)
        if key not in self._prefills:
            jax, model = self._jax, self.model
            jnp = jax.numpy

            def fn(params, tokens, last_index):
                h_last, cache = model._prefill(params, tokens, padded_len,
                                               last_index=last_index)
                logits = model._unembed_matmul(h_last, params,
                                               model.compute_dtype)
                return jnp.argmax(logits, -1).astype(jnp.int32), cache

            # memoized per prompt bucket: each padded length compiles
            # exactly once for the engine's lifetime, bounded by
            # max_total_len / prompt_block buckets
            self._prefills[key] = jax.jit(fn)  # graftlint: ok(retrace) — memoized per bucket
        return self._prefills[key]

    def _chunk_prefill_fn(self, padded_len: int):
        """Paged chunk prefill per suffix-length bucket: run the padded
        un-shared suffix at its true positions through the block table,
        return the first greedy token.  The pool operand is donated; the
        block table and start position are traced, so prefix hits of any
        depth reuse one program per bucket."""
        key = ("chunk", padded_len)
        if key not in self._prefills:
            jax, model = self._jax, self.model
            jnp = jax.numpy

            def fn(params, pool, table, tokens, pos0, last_rel):
                logits, pool = model.decode_chunk_paged(
                    params, pool, table, tokens, pos0,
                    last_index=last_rel)
                return jnp.argmax(logits, -1).astype(jnp.int32), pool

            self._prefills[key] = jax.jit(  # graftlint: ok(retrace) — memoized per bucket
                fn, donate_argnums=(1,) if self._donate else ())
        return self._prefills[key]

    def _spec_score_fn(self):
        """Speculative chunk scorer (one program: spec_k is static):
        feed [last, d_1..d_{k-1}] at pos..pos+k-1, return the target's
        greedy token per position."""
        key = ("spec", self.spec_k)
        if key not in self._prefills:
            jax, model = self._jax, self.model
            jnp = jax.numpy

            def fn(params, pool, table, chunk, pos0):
                logits, pool = model.decode_chunk_paged(
                    params, pool, table, chunk, pos0)
                return jnp.argmax(logits[0], -1).astype(jnp.int32), pool

            self._prefills[key] = jax.jit(  # graftlint: ok(retrace) — memoized once (spec_k static)
                fn, donate_argnums=(1,) if self._donate else ())
        return self._prefills[key]

    def _draft_prefill_fn(self, padded_len: int):
        """Draft-model bucketed pad-prefill into a FIXED-length dense
        cache (max_total_len + spec_k), so every speculative request
        shares one program per prompt bucket."""
        key = ("draft", padded_len)
        if key not in self._prefills:
            jax, draft = self._jax, self.draft_model
            cache_len = self._draft_cache_len

            def fn(dparams, tokens, last_index):
                _, cache = draft._prefill(dparams, tokens, cache_len,
                                          last_index=last_index)
                return cache

            self._prefills[key] = jax.jit(fn)  # graftlint: ok(retrace) — memoized per bucket
        return self._prefills[key]

    def _kv_gather_fn(self, cap: int):
        """KV-handoff export gather, one program per wave width: read a
        fixed-width wave of block ids out of the pool.  The pool is NOT
        donated (the source keeps serving from it); ids short of ``cap``
        are padded with the garbage block 0 and sliced off host-side, so
        every wave of a handoff — and every later handoff with the same
        wave bound — reuses this one program (zero steady-state
        recompiles, compile-guard pinned in the tests)."""
        key = ("kv_gather", cap)
        if key not in self._prefills:
            jax, model = self._jax, self.model

            def fn(pool, ids):
                return model.paged_blocks_gather(pool, ids)

            self._prefills[key] = jax.jit(fn)  # graftlint: ok(retrace) — memoized per wave width
        return self._prefills[key]

    def _kv_scatter_fn(self, cap: int):
        """KV-handoff import scatter (the block-id remap made real):
        write a fixed-width wave of shipped block payloads into freshly
        allocated local ids.  Pad entries target the garbage block 0.
        Pool donated where donation is real — the hot-loop reassignment
        argument from the decode step applies unchanged."""
        key = ("kv_scatter", cap)
        if key not in self._prefills:
            jax, model = self._jax, self.model

            def fn(pool, ids, k, v):
                return model.paged_blocks_scatter(pool, ids, k, v)

            self._prefills[key] = jax.jit(  # graftlint: ok(retrace) — memoized per wave width
                fn, donate_argnums=(0,) if self._donate else ())
        return self._prefills[key]

    # -- block bookkeeping ---------------------------------------------- #
    def _prefix_keys(self, prompt: np.ndarray) -> List[str]:
        """Chain hashes of the prompt's FULL blocks: key j commits to
        tokens [0, (j+1)*block_len) — a hit therefore guarantees the
        whole prefix matches, which is what makes the cached k/v exact
        for the new request."""
        return chain_prefix_keys(prompt, self.block_len)

    def _release_request(self, req: ServeRequest,
                         blocks: List[int]) -> None:
        """Return a request's blocks (refcounted) and its admission-time
        reservation; exactly once per placed request."""
        if self.paged:
            for b in blocks:
                self.allocator.release(b)
        self.batcher.release_blocks(req)

    def _observe_pool(self) -> None:
        if self.paged:
            st = self.allocator.stats()
            active = sum(1 for s in self._slots if s is not None) \
                + sum(1 for c in self._cursors if c is not None) \
                + self._spec_active
            self.metrics.observe_pool(st["used"], active)

    def _place_blocks(self, req: ServeRequest
                      ) -> Optional[Tuple[List[int], List[int],
                                          List[str]]]:
        """Prefix-lookup + allocate a request's remaining blocks.
        Returns (blocks, shared, keys) or None when the pool cannot
        place it right now (caller pushes the request back)."""
        s0 = int(req.prompt.size)
        needed = req.blocks_reserved or blocks_for_request(
            s0, req.max_new_tokens, self.block_len,
            self.spec_k if req.speculative else 0)
        shared: List[int] = []
        keys: List[str] = []
        if self.prefix_cache:
            keys = self._prefix_keys(req.prompt)
            if keys:
                self.metrics.inc("prefix_lookups")
            # keep >= 1 suffix token: the last prompt position's hidden
            # state must actually be computed to produce token 0
            shared = self.allocator.lookup_run(keys,
                                               (s0 - 1) // self.block_len)
            if shared:
                self.metrics.inc("prefix_hits")
                self.metrics.inc("prefix_hit_blocks", len(shared))
        fresh = self.allocator.alloc(needed - len(shared))
        if fresh is None:
            for b in shared:
                self.allocator.release(b)
            return None
        return shared + fresh, shared, keys

    def _register_prompt_blocks(self, req: ServeRequest,
                                blocks: List[int], shared: List[int],
                                keys: List[str]) -> None:
        """Publish this prompt's newly computed FULL blocks for future
        prefix hits (partial/pad blocks never register)."""
        if not self.prefix_cache:
            return
        for j in range(len(shared), int(req.prompt.size)
                       // self.block_len):
            self.allocator.register(keys[j], blocks[j])

    # -- admission ------------------------------------------------------ #
    def _pop_admittable(self) -> Optional[Tuple[ServeRequest,
                                                ServeResponse]]:
        """Next queued request still worth serving.  With an SLO policy
        attached, a request whose deadline passed while it queued is
        shed typed (``DeadlineExceeded``) RIGHT HERE — before any
        prefill compute is spent on a response the client already
        abandoned — its admission block reservation returns to the
        budget, and the pop retries the next request."""
        while True:
            item = self.batcher.pop()
            if item is None:
                return None
            req, resp = item
            if self._slo is not None and req.deadline is not None \
                    and time.monotonic() > req.deadline:
                exc = self._slo.shed(req,
                                     time.monotonic() - req.t_submit)
                if resp._fail(exc):
                    self.metrics.inc("failed")
                self.batcher.release_blocks(req)
                continue
            # NOTE: the deadline-MET observation is recorded at prefill
            # (the one-per-request point), not here — a pool-full head
            # request is re-popped via push_front every loop iteration,
            # and per-pop observations would flood the window with
            # non-violations exactly when overload matters
            return item

    def _admit(self) -> int:
        """Fill free slots from the queue: prefill each request into its
        cache (dense row-join or paged blocks), record TTFT (the first
        token exists the moment prefill returns)."""
        jnp = self._jax.numpy
        admitted = 0
        for i in range(self.max_slots):
            if self._slots[i] is not None or self._cursors[i] is not None:
                continue
            item = self._pop_admittable()
            if item is None:
                break
            req, resp = item
            if self.paged and self.chunked_prefill \
                    and req.import_handoff is None and not req.speculative \
                    and int(req.prompt.size) \
                    > self._chunk_blocks * self.block_len:
                # long prompt: stream it through a prefill cursor the
                # loop advances between decode waves (no upfront block
                # placement — a paused prefill holds only its
                # blocks-so-far, allocated chunk by chunk)
                self._start_cursor(i, req, resp)
                admitted += 1
                continue
            if self.paged and req.import_handoff is not None:
                # decode-lane entry: no prefill, just a block remap
                if not self._admit_import(i, req, resp):
                    break  # pool cannot place it now; request pushed back
                admitted += 1
                continue
            if self.paged and req.speculative \
                    and self.draft_model is not None \
                    and all(s is None for s in self._slots):
                # idle engine: the single-stream latency lane
                if not self._run_speculative(req, resp):
                    break  # pool cannot place it now; request pushed back
                admitted += 1
                continue
            if self.paged:
                placed = self._place_blocks(req)
                if placed is None:
                    # pool exhausted right now: FIFO head waits (no
                    # starvation; retires free blocks every step)
                    self.batcher.push_front(item)
                    break
                blocks, shared, keys = placed
            else:
                blocks, shared, keys = None, (), ()
            try:
                self._admit_one(i, req, resp, blocks, shared, keys)
            except BaseException as e:
                # the popped request is in neither the queue nor a slot:
                # its future must fail HERE or the client hangs until
                # timeout while the loop dies loudly
                if resp._fail(e):
                    self.metrics.inc("failed")
                if self.paged:
                    self._release_request(req, blocks)
                raise
            admitted += 1
        return admitted

    def _paged_prefill(self, req: ServeRequest, resp: ServeResponse,
                       blocks: List[int], shared, keys,
                       slot: int, speculative: bool = False
                       ) -> Tuple[int, np.ndarray, float]:
        """The one paged prefill path (normal slots AND the speculative
        lane ride it, so they cannot drift): build the request's table,
        chunk-prefill the un-shared suffix into its blocks, register the
        new full prompt blocks, and record TTFT.  Returns (first token,
        table row, completion timestamp)."""
        jnp = self._jax.numpy
        t_a = time.monotonic()
        # queue wait = admission -> this slot-join moment; ttft below
        # is queue_wait + prefill by construction
        self.metrics.observe_queue_wait(t_a - req.t_submit)
        self.metrics.observe_long_prefill(int(req.prompt.size))
        start = len(shared) * self.block_len
        sfx = req.prompt[start:]
        P = -(-int(sfx.size) // self.block_len) * self.block_len
        padded = np.zeros((1, P), np.int32)
        padded[0, :sfx.size] = sfx
        table = np.zeros((self.table_blocks,), np.int32)
        table[:len(blocks)] = blocks
        tok0, self._cache = self._chunk_prefill_fn(P)(
            self.params, self._cache, jnp.asarray(table),
            jnp.asarray(padded), jnp.int32(start),
            jnp.int32(int(sfx.size) - 1))
        self.metrics.inc("prefill_chunks")
        self._register_prompt_blocks(req, blocks, shared, keys)
        # graftlint: ok(host-sync) — TTFT gate: the first token must
        first = int(np.asarray(tok0)[0])  # be real before it is timed
        now = time.monotonic()
        resp.ttft_s = now - req.t_submit
        self.metrics.observe_ttft(resp.ttft_s)
        if self._slo is not None:
            self._slo.observe_ttft(resp.ttft_s, req)
            self._slo.observe_deadline_met(req)
        self.metrics.observe_prefill(now - t_a)
        if self.perf_timeline is not None:
            self.perf_timeline.observe("prefill", now - t_a)
        telemetry.emit("serve_prefill", trace=req.trace_id,
                       request=req.request_id, bucket=P, slot=slot,
                       shared_blocks=len(shared),
                       speculative=speculative,
                       ttft_ms=round(resp.ttft_s * 1e3, 3))
        return first, table, now

    def _admit_one(self, i: int, req: ServeRequest, resp: ServeResponse,
                   blocks: Optional[List[int]], shared, keys) -> None:
        """Prefill one placed request into slot ``i`` (or finish it at
        prefill for single-token budgets)."""
        jnp = self._jax.numpy
        s0 = int(req.prompt.size)
        if self.paged:
            first, table, now = self._paged_prefill(req, resp, blocks,
                                                    shared, keys, slot=i)
            if req.export_handoff:
                # prefill lane: the request's lifecycle on THIS engine
                # ends here — ship the blocks, keep them pinned until
                # the decode side confirms (release_handoff)
                self._export_handoff(req, resp, blocks, keys, first)
                self._observe_pool()
                return
        else:
            t_a = time.monotonic()
            self.metrics.observe_queue_wait(t_a - req.t_submit)
            P = self._bucket(s0)
            padded = np.zeros((1, P), np.int32)
            padded[0, :s0] = req.prompt
            tok0, row_cache = self._prefill_fn(P)(
                self.params, jnp.asarray(padded), jnp.int32(s0 - 1))
            if req.max_new_tokens > 1:
                # single-token requests finish at prefill; joining
                # their row would copy the whole cache for nothing
                self._cache = self._join(self._cache, row_cache,
                                         jnp.int32(i))
            # graftlint: ok(host-sync) — TTFT gate: the first token must
            first = int(np.asarray(tok0)[0])  # be real before timing
            now = time.monotonic()
            resp.ttft_s = now - req.t_submit
            self.metrics.observe_ttft(resp.ttft_s)
            if self._slo is not None:
                self._slo.observe_ttft(resp.ttft_s, req)
                self._slo.observe_deadline_met(req)
            self.metrics.observe_prefill(now - t_a)
            if self.perf_timeline is not None:
                self.perf_timeline.observe("prefill", now - t_a)
            telemetry.emit("serve_prefill", trace=req.trace_id,
                           request=req.request_id, bucket=P, slot=i,
                           shared_blocks=0,
                           ttft_ms=round(resp.ttft_s * 1e3, 3))
        if req.max_new_tokens == 1:
            self._finish(req, resp, [first])
            if self.paged:
                self._release_request(req, blocks)
        else:
            slot = _Slot(req, resp, pos=s0, first_token=first,
                         t_now=now,
                         blocks=blocks if self.paged else None)
            self._slots[i] = slot
            if self.paged:
                self._tables[i, :] = table
        self._observe_pool()

    # -- chunked long-prompt prefill ------------------------------------- #
    def _start_cursor(self, i: int, req: ServeRequest,
                      resp: ServeResponse) -> None:
        """Begin streaming a long prompt into slot ``i``: the prefix
        lookup happens NOW (a hit's blocks are exact KV, so the cursor
        starts past them), but blocks are otherwise allocated chunk by
        chunk — a paused prefill holds only its blocks-so-far.  The
        slot's table row stays zeroed until completion (see
        :class:`_PrefillCursor`)."""
        s0 = int(req.prompt.size)
        t_a = time.monotonic()
        # queue wait = admission -> the moment prefill starts; ttft at
        # completion is queue_wait + (streamed) prefill by construction
        self.metrics.observe_queue_wait(t_a - req.t_submit)
        self.metrics.observe_long_prefill(s0)
        shared: List[int] = []
        keys: List[str] = []
        if self.prefix_cache:
            keys = self._prefix_keys(req.prompt)
            if keys:
                self.metrics.inc("prefix_lookups")
            # keep >= 1 suffix token (the last position's hidden state
            # must be computed to produce token 0)
            shared = self.allocator.lookup_run(keys,
                                               (s0 - 1) // self.block_len)
            if shared:
                self.metrics.inc("prefix_hits")
                self.metrics.inc("prefix_hit_blocks", len(shared))
        self._cursors[i] = _PrefillCursor(
            req, resp, shared, keys, pos=len(shared) * self.block_len,
            t_start=t_a)
        telemetry.emit("serve_prefill_start", trace=req.trace_id,
                       request=req.request_id, slot=i, prompt=s0,
                       shared_blocks=len(shared), streamed=True)

    def _advance_prefills(self, decode_active: bool) -> None:
        """Advance every streaming prefill by ONE chunk this loop
        iteration.  The chunk budget is cadence-aware: the big quantum
        (RLA_TPU_SERVE_CHUNK_BLOCKS) while no decode slot is live, the
        small one (RLA_TPU_SERVE_CHUNK_MIN_BLOCKS) between decode waves
        — decode cadence stays bounded by one small chunk's compute.
        Both quanta are fixed buckets of the existing chunk-prefill
        program family, so steady state compiles nothing new."""
        for i, cur in enumerate(self._cursors):
            if cur is not None:
                self._advance_cursor(i, cur, decode_active)

    def _advance_cursor(self, i: int, cur: _PrefillCursor,
                        decode_active: bool) -> None:
        jnp = self._jax.numpy
        C = (self._chunk_min_blocks if decode_active
             else self._chunk_blocks) * self.block_len
        s0 = int(cur.req.prompt.size)
        rem = s0 - cur.pos
        if rem <= C:
            self._complete_cursor(i, cur)
            return
        # intermediate chunk at the exact quantum (no pad): allocate the
        # blocks its real positions write, run it at its true positions
        # through the table, discard the greedy token (position
        # pos+C-1's continuation is recomputed exactly by later chunks'
        # attention over these same blocks)
        need = -(-(cur.pos + C) // self.block_len) - len(cur.blocks)
        if need > 0:
            fresh = self.allocator.alloc(need)
            if fresh is None:
                return  # pool full now; the cursor waits, holding
                        # blocks-so-far (decode retires free blocks)
            cur.blocks.extend(fresh)
        table = np.zeros((self.table_blocks,), np.int32)
        table[:len(cur.blocks)] = cur.blocks
        chunk = np.ascontiguousarray(
            cur.req.prompt[cur.pos:cur.pos + C].reshape(1, C))
        t0 = time.monotonic()
        _, self._cache = self._chunk_prefill_fn(C)(
            self.params, self._cache, jnp.asarray(table),
            jnp.asarray(chunk), jnp.int32(cur.pos), jnp.int32(C - 1))
        cur.pos += C
        cur.chunks += 1
        self.metrics.inc("prefill_chunks")
        if self.perf_timeline is not None:
            self.perf_timeline.observe("prefill", time.monotonic() - t0)

    def _complete_cursor(self, i: int, cur: _PrefillCursor) -> None:
        """Final chunk: allocate the request's remaining (decode)
        blocks, run the padded tail, surface the first token, and
        promote the cursor to a live slot (or hand off / finish).  Pad
        positions are safe exactly as in the whole-prompt path: writes
        past the allocated span route to the garbage block through the
        zeroed table tail, and in-span pads sit at positions >= s0 that
        decode rewrites before the causal mask exposes them."""
        jnp = self._jax.numpy
        req, resp = cur.req, cur.resp
        s0 = int(req.prompt.size)
        needed = req.blocks_reserved or blocks_for_request(
            s0, req.max_new_tokens, self.block_len)
        need = needed - len(cur.blocks)
        if need > 0:
            fresh = self.allocator.alloc(need)
            if fresh is None:
                return  # pool full now; retry next loop iteration
            cur.blocks.extend(fresh)
        rem = s0 - cur.pos
        P = -(-rem // self.block_len) * self.block_len
        padded = np.zeros((1, P), np.int32)
        padded[0, :rem] = req.prompt[cur.pos:]
        table = np.zeros((self.table_blocks,), np.int32)
        table[:len(cur.blocks)] = cur.blocks
        t0 = time.monotonic()
        tok0, self._cache = self._chunk_prefill_fn(P)(
            self.params, self._cache, jnp.asarray(table),
            jnp.asarray(padded), jnp.int32(cur.pos), jnp.int32(rem - 1))
        cur.chunks += 1
        self.metrics.inc("prefill_chunks")
        self._register_prompt_blocks(req, cur.blocks, cur.shared,
                                     cur.keys)
        # graftlint: ok(host-sync) — TTFT gate: the first token must
        first = int(np.asarray(tok0)[0])  # be real before it is timed
        now = time.monotonic()
        resp.ttft_s = now - req.t_submit
        self.metrics.observe_ttft(resp.ttft_s)
        if self._slo is not None:
            self._slo.observe_ttft(resp.ttft_s, req)
            self._slo.observe_deadline_met(req)
        self.metrics.observe_prefill(now - cur.t_start)
        if self.perf_timeline is not None:
            self.perf_timeline.observe("prefill", now - t0)
        telemetry.emit("serve_prefill", trace=req.trace_id,
                       request=req.request_id, bucket=P, slot=i,
                       shared_blocks=len(cur.shared), streamed=True,
                       chunks=cur.chunks,
                       ttft_ms=round(resp.ttft_s * 1e3, 3))
        self._cursors[i] = None
        if req.export_handoff:
            # the disaggregated prefill lane rides the same cursor: the
            # request's lifecycle on THIS engine ends here
            self._export_handoff(req, resp, cur.blocks, cur.keys, first)
            self._observe_pool()
            return
        if req.max_new_tokens == 1:
            self._finish(req, resp, [first])
            self._release_request(req, cur.blocks)
        else:
            self._slots[i] = _Slot(req, resp, pos=s0, first_token=first,
                                   t_now=now, blocks=cur.blocks)
            self._tables[i, :] = 0
            self._tables[i, :len(cur.blocks)] = cur.blocks
        self._observe_pool()

    # -- KV handoff (disaggregated lanes) -------------------------------- #
    def _export_handoff(self, req: ServeRequest, resp: ServeResponse,
                        blocks: List[int], keys: List[str],
                        first: int) -> None:
        """Ship a just-prefilled request's KV blocks to the object store
        in bounded waves and resolve its response with the handoff
        descriptor.  The blocks stay pinned (refcounted) on this engine
        until ``release_handoff`` — a decode-side crash mid-import can
        always re-prefill against the still-cached source."""
        jnp = self._jax.numpy
        from ..parallel.redistribute import wave_schedule
        from ..runtime import object_store
        s0 = int(req.prompt.size)
        # per-block payload bytes (k+v), measured from the real pool
        per_block = max(1, self._pool_bytes // self.n_blocks)
        waves = wave_schedule([per_block] * len(blocks),
                              self.handoff_wave_bytes)
        cap = max(len(w) for w in waves)
        gather = self._kv_gather_fn(cap)
        store = object_store.global_store()
        refs: List[Any] = []
        wave_out: List[Tuple[int, Any]] = []
        total_bytes = 0
        try:
            for w in waves:
                ids = np.zeros((cap,), np.int32)  # pad = garbage block 0
                ids[:len(w)] = [blocks[j] for j in w]
                k, v = gather(self._cache, jnp.asarray(ids))
                # graftlint: ok(host-sync) — the copy IS the handoff
                kk = np.asarray(k)[:, :len(w)]
                vv = np.asarray(v)[:, :len(w)]  # graftlint: ok(host-sync) — the copy IS the handoff
                ref = store.put({"k": kk, "v": vv})
                refs.append(ref)
                wave_out.append((len(w), ref))
                total_bytes += kk.nbytes + vv.nbytes
        except BaseException:
            for ref in refs:  # don't leak shm segments on a failed ship
                try:
                    store.delete(ref)
                except Exception:
                    pass
            raise
        hid = next(self._handoff_ids)
        desc = {
            "handoff_id": hid,
            "request_id": req.request_id,
            "prompt": req.prompt,
            "max_new_tokens": req.max_new_tokens,
            "first": first,
            "pos": s0,
            "keys": list(keys),
            "block_len": self.block_len,
            "wave_cap": cap,
            "waves": wave_out,
            "bytes": total_bytes,
            "t_submit": req.t_submit,
            "deadline": req.deadline,
            "trace_id": req.trace_id,
        }
        with self._handoff_lock:
            self._handoffs[hid] = (req, list(blocks), refs)
        self.metrics.inc("kv_handoffs")
        self.metrics.inc("kv_handoff_bytes", total_bytes)
        telemetry.emit("serve_kv_export", trace=req.trace_id,
                       request=req.request_id, handoff=hid,
                       blocks=len(blocks), waves=len(wave_out),
                       bytes=total_bytes)
        if resp._complete(desc):
            self.metrics.inc("completed")

    def _admit_import(self, i: int, req: ServeRequest,
                      resp: ServeResponse) -> bool:
        """Turn a handoff descriptor into a live decode slot: allocate
        this engine's own blocks (the remap — no prefix lookup, the
        shipped bytes ARE the prefix), replay the object-store waves
        into them, register the full prompt blocks under their chain
        keys (first-writer-wins), and join mid-decode.  Returns False
        when the pool cannot place it right now (request pushed back).
        A stale-ref failure (source died and unlinked its segments)
        fails THIS response typed without killing the loop — the driver
        requeues the original for a full re-prefill."""
        jnp = self._jax.numpy
        from ..runtime import object_store
        desc = req.import_handoff
        needed = req.blocks_reserved or blocks_for_request(
            int(req.prompt.size), req.max_new_tokens, self.block_len)
        blocks = self.allocator.alloc(needed)
        if blocks is None:
            self.batcher.push_front((req, resp))
            return False
        try:
            cap = int(desc["wave_cap"])
            scatter = self._kv_scatter_fn(cap)
            store = object_store.global_store()
            idx = 0
            for count, ref in desc["waves"]:
                payload = store.get(ref)
                ids = np.zeros((cap,), np.int32)  # pad = garbage block 0
                ids[:count] = blocks[idx:idx + count]
                idx += count
                kk, vv = payload["k"], payload["v"]
                if count < cap:
                    pad = [(0, 0)] * kk.ndim
                    pad[1] = (0, cap - count)
                    kk = np.pad(kk, pad)  # pad payloads land in block 0
                    vv = np.pad(vv, pad)
                self._cache = scatter(self._cache, jnp.asarray(ids),
                                      jnp.asarray(kk), jnp.asarray(vv))
        except object_store.ObjectStoreError as e:
            self._release_request(req, blocks)
            if resp._fail(e):
                self.metrics.inc("failed")
            return True  # consumed; the loop (and the tier) live on
        except BaseException as e:
            self._release_request(req, blocks)
            if resp._fail(e):
                self.metrics.inc("failed")
            raise
        # register only AFTER every wave landed: a partially imported
        # block must never be reachable from the prefix index
        if self.prefix_cache:
            for j, key in enumerate(desc.get("keys", ())):
                self.allocator.register(key, blocks[j])
        first = int(desc["first"])
        now = time.monotonic()
        # no TTFT/queue-wait observation here: the first token was timed
        # where it was produced (the prefill lane); this engine only
        # contributes decode cadence
        telemetry.emit("serve_kv_import", trace=req.trace_id,
                       request=req.request_id,
                       handoff=desc.get("handoff_id"),
                       blocks=len(blocks), waves=len(desc["waves"]))
        if req.max_new_tokens == 1:
            self._finish(req, resp, [first])
            self._release_request(req, blocks)
        else:
            self._slots[i] = _Slot(req, resp, pos=int(desc["pos"]),
                                   first_token=first, t_now=now,
                                   blocks=blocks)
            self._tables[i, :] = 0
            self._tables[i, :len(blocks)] = blocks
        self._observe_pool()
        return True

    # -- decode --------------------------------------------------------- #
    def _decode_step(self, active: List[int]) -> None:
        """One batched step over ALL slots (static shape); only active
        rows advance host-side.  Inactive rows feed token 0 at position
        0 — dense: their slot is rewritten by the next join before the
        causal mask can expose the garbage; paged: their all-zero table
        routes the write to the reserved garbage block."""
        jnp = self._jax.numpy
        toks = np.zeros((self.max_slots,), np.int32)
        poss = np.zeros((self.max_slots,), np.int32)
        for i in active:
            s = self._slots[i]
            toks[i] = s.last
            poss[i] = s.pos
        t0 = time.monotonic()
        if self.paged:
            toks_next, row_ok, self._cache = self._step(
                self.params, self._cache, jnp.asarray(self._tables),
                jnp.asarray(toks), jnp.asarray(poss))
        else:
            toks_next, row_ok, self._cache = self._step(
                self.params, self._cache, jnp.asarray(toks),
                jnp.asarray(poss))
        # deliberate: step k+1's input IS step k's output, so the loop
        # must materialize it — the one sync a greedy feed cannot avoid
        nxt = np.asarray(toks_next)  # graftlint: ok(host-sync) — feed gate
        # the numeric guard's health bits ride that same materialization
        okh = np.asarray(row_ok)  # graftlint: ok(host-sync) — feed gate
        now = time.monotonic()
        self.metrics.observe_step(now - t0, len(active))
        if self.perf_timeline is not None:
            self.perf_timeline.observe("decode", now - t0)
        # batched event (one per step, not per slot): slot-level identity
        # lives in the admit/prefill/respond events' traces
        telemetry.emit("serve_decode_step", active=len(active),
                       step_ms=round((now - t0) * 1e3, 3))
        retired = False
        for i in active:
            s = self._slots[i]
            if not bool(okh[i]):
                # non-finite logits for THIS row: fail the one request
                # typed (NumericAnomaly crosses the replica wire with its
                # postmortem intact) instead of streaming garbage tokens;
                # the slot's blocks go back to the pool and the other
                # rows of the batch are untouched
                from ..runtime.guardian import NumericAnomaly
                err = NumericAnomaly.for_trip(
                    step=s.pos, blame="unknown",
                    flags={"decode_logits_nonfinite": True},
                    detail="serve decode produced non-finite logits")
                self.metrics.inc("numeric_anomalies")
                if s.resp._fail(err):
                    self.metrics.inc("failed")
                telemetry.emit("anomaly_trip", tier="serve", slot=i,
                               pos=s.pos, request_id=id(s.req))
                if self.paged:
                    self._release_request(s.req, s.blocks)
                    self._tables[i, :] = 0
                self._slots[i] = None
                retired = True
                continue
            tok = int(nxt[i])
            s.generated.append(tok)
            s.pos += 1
            s.last = tok
            s.remaining -= 1
            gap = now - s.t_last
            self.metrics.observe_token_latency(gap)
            if self._slo is not None:
                self._slo.observe_token(gap, s.req)
            s.t_last = now
            if s.remaining <= 0:
                self._finish(s.req, s.resp, s.generated)
                if self.paged:
                    self._release_request(s.req, s.blocks)
                    self._tables[i, :] = 0
                self._slots[i] = None  # retire = host-side table write
                retired = True
        if retired:
            self._observe_pool()

    # -- speculative lane ------------------------------------------------ #
    def _run_speculative(self, req: ServeRequest,
                         resp: ServeResponse) -> bool:
        """Serve one single-stream request end-to-end through greedy
        speculative decode against the PAGED pool: paged chunk prefill
        (prefix hits included), then rounds of draft-propose / one-pass
        target verification whose chunk writes land in the request's
        pre-reserved scratch blocks.  Rejected positions are rewritten
        by later rounds before the mask can expose them (the linear-
        cache no-rollback argument).  Returns False when the pool cannot
        place the request right now (request pushed back, nothing
        consumed)."""
        jnp = self._jax.numpy
        placed = self._place_blocks(req)
        if placed is None:
            self.batcher.push_front((req, resp))
            return False
        blocks, shared, keys = placed
        self._spec_active = 1
        try:
            try:
                self._spec_decode(req, resp, blocks, shared, keys)
            except BaseException as e:
                # the request is in neither the queue nor a slot: fail
                # its future here or the client hangs until timeout
                if resp._fail(e):
                    self.metrics.inc("failed")
                raise
        finally:
            self._spec_active = 0
            self._release_request(req, blocks)
            self._observe_pool()
        return True

    def _spec_decode(self, req: ServeRequest, resp: ServeResponse,
                     blocks: List[int], shared, keys) -> None:
        jnp = self._jax.numpy
        s0 = int(req.prompt.size)
        first, table, now = self._paged_prefill(req, resp, blocks,
                                                shared, keys, slot=-1,
                                                speculative=True)
        table_j = jnp.asarray(table)
        self.metrics.inc("speculative_requests")
        self._observe_pool()
        out = [first]
        if req.max_new_tokens > 1:
            # draft prefill: full padded prompt, fixed cache length.
            # Bucket WITHOUT the dense max_total_len clamp: block
            # rounding may admit prompts past W (the table span covers
            # them; the admission hard cap bounds them by max_seq_len)
            PB = -(-s0 // self.block_len) * self.block_len
            dpad = np.zeros((1, PB), np.int32)
            dpad[0, :s0] = req.prompt
            d_cache = self._draft_prefill_fn(PB)(
                self.draft_params, jnp.asarray(dpad),
                jnp.int32(s0 - 1))
            score = self._spec_score_fn()
            k = self.spec_k
            mx = req.max_new_tokens
            t_last_tok = now
            while len(out) < mx:
                if self._stop.is_set() and self._cancel_active:
                    # fast teardown must be able to interrupt the lane
                    # mid-request, exactly like _cancel_slots does for
                    # slot decodes
                    if resp._fail(ServeCancelled(
                            f"request {req.request_id} cancelled "
                            "mid-speculative-decode: engine stopped "
                            "with cancel_active")):
                        self.metrics.inc("cancelled")
                    return
                pos = s0 + len(out) - 1  # newest real token's slot
                last = jnp.asarray([out[-1]], jnp.int32)
                d_cache, draft_toks = self._d_propose(
                    d_cache, last, jnp.asarray(pos))
                # the next round's feed depends on these tokens
                # graftlint: ok(host-sync) — accept gate
                drafts = [int(t) for t in np.asarray(draft_toks)]
                chunk = jnp.asarray([[out[-1]] + drafts[:-1]],
                                    jnp.int32)
                t0 = time.monotonic()
                greedy_arr, self._cache = score(
                    self.params, self._cache, table_j, chunk,
                    jnp.int32(pos))
                # graftlint: ok(host-sync) — accept gate
                greedy = np.asarray(greedy_arr)
                accept = 0
                while accept < k and greedy[accept] == drafts[accept] \
                        and len(out) + accept + 1 < mx:
                    accept += 1
                self.metrics.inc("speculative_tokens_accepted",
                                 accept)
                new = drafts[:accept] + [int(greedy[accept])] \
                    if accept < k else drafts[:accept]
                new = new[:mx - len(out)]
                now = time.monotonic()
                self.metrics.observe_spec_round(now - t0, len(new))
                # per-token latency: the round produced len(new)
                # tokens in one target pass — amortize honestly
                dt_tok = (now - t_last_tok) / max(1, len(new))
                for _ in new:
                    self.metrics.observe_token_latency(dt_tok)
                    if self._slo is not None:
                        self._slo.observe_token(dt_tok, req)
                t_last_tok = now
                out.extend(new)
        self._finish(req, resp, out)

    def _finish(self, req: ServeRequest, resp: ServeResponse,
                generated: List[int]) -> None:
        tokens = np.concatenate(  # graftlint: ok(host-sync) — host list,
            [req.prompt, np.asarray(generated, np.int32)])  # no device value
        if resp._complete(tokens):
            self.metrics.inc("completed")
            telemetry.emit("serve_respond", trace=req.trace_id,
                           request=req.request_id,
                           tokens=len(generated))

    def _cancel_slots(self) -> None:
        for i, s in enumerate(self._slots):
            if s is None:
                continue
            if s.resp._fail(ServeCancelled(
                    f"request {s.req.request_id} cancelled mid-decode: "
                    "engine stopped with cancel_active")):
                self.metrics.inc("cancelled")
            self._release_request(s.req, s.blocks)
            if self.paged:
                self._tables[i, :] = 0
            self._slots[i] = None
        for i, cur in enumerate(self._cursors):
            if cur is None:
                continue
            if cur.resp._fail(ServeCancelled(
                    f"request {cur.req.request_id} cancelled "
                    "mid-prefill: engine stopped with cancel_active")):
                self.metrics.inc("cancelled")
            self._release_request(cur.req, cur.blocks)
            self._cursors[i] = None
