"""Serving cells: a paged ``ServeEngine`` under a closed loop of clients.

The cell file's ``settings``: ``max_slots``, ``max_total_len``,
``queue_depth``, ``check_responses`` and, for the traced run,
``trace_seconds``.  The traffic mix gives the clients, the request
shapes and how many first completions are discarded as ramp-up.
"""
from __future__ import annotations

import threading
import time

import numpy as np

from benchmark.lib import cells, reference, stats, traffic
from benchmark.lib import trace as trace_lib

# A generated token may trail the reference's own greedy choice by at
# most this many standard deviations of its logit row: two correct
# programs for one greedy decode (bfloat16 paged engine, float32
# reference) disagree only where the top two logits nearly tie (worst
# seen on the chip: 0.04); a wrong block, position or stale donated
# buffer is off by whole deviations.
TIE_TOL = 0.1
RESULT_TIMEOUT_S = 300.0
# more requests a second than any cell completes: sizes the seeded stream
# (which wraps round rather than run dry)
REQUESTS_PER_S_CEILING = 40


class _Record:
    __slots__ = ("request", "t_submit", "t_first", "t_done", "tokens",
                 "error")

    def __init__(self, request):
        self.request = request
        self.t_submit = self.t_first = self.t_done = None
        self.tokens = self.error = None


class _ClosedLoop:
    """``clients`` threads; each takes the next request of the seeded
    stream, submits it, waits for the reply, stamps it, and goes on
    until told to stop.  Times are ``time.monotonic()``, the engine's
    own clock."""

    def __init__(self, engine, requests, clients: int):
        self.engine, self.requests = engine, requests
        self.records, self.lock = [], threading.Lock()
        self.next = 0
        self.stop = threading.Event()
        self.threads = [threading.Thread(target=self._client, daemon=True,
                                         name=f"bench-client-{i}")
                        for i in range(clients)]

    def _client(self):
        while not self.stop.is_set():
            with self.lock:
                # the stream wraps round rather than let the load thin out
                rec = _Record(self.requests[self.next % len(self.requests)])
                self.next += 1
            try:
                with trace_lib.annotate("client_submit"):
                    rec.t_submit = time.monotonic()
                    resp = self.engine.submit(rec.request.prompt,
                                              rec.request.max_new_tokens)
                rec.tokens = np.asarray(resp.result(RESULT_TIMEOUT_S))
                rec.t_done = time.monotonic()
                # the engine stamps admission and first token on the same
                # clock: client-side admission delay + its own ttft
                rec.t_first = resp.request.t_submit + resp.ttft_s
            except Exception as e:  # a failed request is a result, counted
                rec.t_done, rec.error = time.monotonic(), e
            with self.lock:
                self.records.append(rec)

    def completed(self) -> int:
        with self.lock:
            return len(self.records)


def _check_responses(params, records, vocab_size: int, width: int) -> dict:
    """Every generated token of a sample of responses against the plain
    reference's full forward of the same sequence (padded to ``width``:
    causal, so the padding changes nothing before it)."""
    import jax.numpy as jnp

    tokens = np.zeros((len(records), width), np.int32)
    for i, rec in enumerate(records):
        tokens[i, :rec.tokens.size] = rec.tokens
    tokens = jnp.asarray(tokens)
    margins = np.asarray(reference.tie_margins(
        reference.logits(params, tokens), tokens))
    worst, exact, total, intact = 0.0, 0, 0, True
    for i, rec in enumerate(records):
        n_prompt = rec.request.prompt.size
        out = rec.tokens
        intact &= (out.size == n_prompt + rec.request.max_new_tokens
                   and np.array_equal(out[:n_prompt], rec.request.prompt)
                   and int(out.min()) >= 0 and int(out.max()) < vocab_size)
        m = margins[i, n_prompt - 1:out.size - 1]  # rows that chose a token
        worst = max(worst, float(m.max()))
        exact += int((m == 0).sum())
        total += m.size
    return {"responses": len(records), "generated_tokens": total,
            "argmax_fraction": exact / max(total, 1),
            "max_tie_margin": worst, "prompts_intact": bool(intact),
            "ok": bool(intact and np.isfinite(worst) and worst <= TIE_TOL)}


def run(cell, *, devices, seed, seconds, trace: bool, t_process, compiles,
        emit) -> dict:
    import jax
    import jax.numpy as jnp

    from ray_lightning_accelerators_tpu.serve import ServeEngine


    settings, mix = cell["workload"]["settings"], cell["traffic"]
    model_cfg = cell["config"]["model"]
    clients = int(mix["clients"])
    seed32 = seed % (2 ** 31 - 1)

    model = cells.build_model(cell["config"], settings)
    model.compute_dtype = jnp.bfloat16
    # weights on the device, in the type they are served in, in one
    # jitted call from the seed
    params = jax.jit(lambda key: jax.tree.map(
        lambda p: p.astype(jnp.bfloat16), model.init_params(key)))(
            jax.random.PRNGKey(seed32))

    # enough requests for the longest plausible window: blocks of the
    # mix's fixed shapes, each in a seeded order
    block = int(mix["block_requests"])
    requests = traffic.serve_requests(
        mix, seed, model_cfg["vocab_size"],
        n_blocks=int(seconds * REQUESTS_PER_S_CEILING / block) + 2)

    engine = ServeEngine(model, params,
                         max_slots=int(settings["max_slots"]),
                         max_total_len=int(settings["max_total_len"]),
                         queue_depth=int(settings["queue_depth"]))
    engine.start()
    loop = _ClosedLoop(engine, requests, clients)
    captured = None
    try:
        # warm-up: every prefill bucket (whole blocks up to the chunk
        # quantum) and the decode step, one request at a time; then the
        # chunked path of a long prompt beside a live decode
        rng = np.random.default_rng(seed32)
        bl = engine.block_len
        chunk = getattr(engine, "_chunk_blocks", 8) * bl   # largest bucket
        for n in range(bl, chunk + 1, bl):
            engine.submit(rng.integers(0, model_cfg["vocab_size"], n)
                          .astype(np.int32), 2).result(RESULT_TIMEOUT_S)
        long = min(3 * chunk + bl // 2,
                   int(settings["max_total_len"]) - 9)
        warm = [engine.submit(rng.integers(0, model_cfg["vocab_size"], n)
                              .astype(np.int32), 8) for n in (bl, long)]
        for resp in warm:
            resp.result(RESULT_TIMEOUT_S)

        for t in loop.threads:
            t.start()
        ramp = int(mix["discard_first_completions"])
        deadline = time.monotonic() + RESULT_TIMEOUT_S
        while loop.completed() < ramp:
            if time.monotonic() > deadline:
                raise TimeoutError("ramp-up did not complete")
            time.sleep(0.002)
        engine.metrics.reset()
        compiles_at_t0 = compiles.count()
        t0, t0_perf = time.monotonic(), time.perf_counter()
        if trace:
            time.sleep(min(1.0, seconds / 4))
            with trace_lib.capture() as captured:
                with trace_lib.annotate("window"):
                    time.sleep(float(settings["trace_seconds"]))
        time.sleep(max(0.0, t0 + seconds - time.monotonic()))
        t1 = time.monotonic()
        engine_stats = engine.stats()
        window_compiles = compiles.count() - compiles_at_t0
    finally:
        loop.stop.set()
        engine.stop(cancel_active=True)
        for t in loop.threads:
            if t.is_alive():
                t.join(RESULT_TIMEOUT_S)
    after_stop = engine.stats()

    with loop.lock:
        in_window = [r for r in loop.records if t0 <= r.t_done <= t1]
    done = [r for r in in_window if r.error is None]
    failed = [r for r in in_window if r.error is not None]
    for r in failed[:3]:
        emit(info="failed_request", error=repr(r.error)[:300])
    out_tokens = sum(r.request.max_new_tokens for r in done)
    ttft = [r.t_first - r.t_submit for r in done]
    tpot = [x for x in (stats.time_per_output_token(
        r.t_first, r.t_done, r.request.max_new_tokens) for r in done)
        if x is not None]

    sample = [done[i] for i in np.random.default_rng(seed32).choice(
        len(done), size=min(int(settings["check_responses"]), len(done)),
        replace=False)] if done else []
    check = (_check_responses(params, sample, model_cfg["vocab_size"],
                              int(settings["max_total_len"]))
             if sample else {"ok": False, "responses": 0})
    emit(info="reference_check", **check)
    checks = {
        "reference": check["ok"],
        "no_compile_in_window": window_compiles == 0,
        "pool_empty_after_stop": after_stop.get("block_pool_used") == 0,
        "no_failed_request": not failed,
        "enough_requests": len(done) >= 20,
    }
    emit(info="serve", completed=len(done), failed=len(failed),
         window_s=t1 - t0, output_tokens=out_tokens,
         ttft_p50_ms=stats.percentile(ttft, 50) * 1e3 if ttft else None,
         tpot_p50_ms=stats.percentile(tpot, 50) * 1e3 if tpot else None,
         window_compiles=window_compiles, requests_drawn=loop.next,
         requests_generated=len(requests),
         engine={k: engine_stats.get(k) for k in (
             "steps", "tokens_generated", "prefill_chunks", "busy_s",
             "prefix_hits", "prefix_hit_blocks", "peak_concurrent",
             "max_batch", "block_pool_total", "hbm_cache_bytes",
             "decode_step_s", "prefill_s", "queue_wait_s", "ttft_s")})
    end_to_end = {"serve_tok_s": out_tokens / (t1 - t0),
                  "setup_s": t0_perf - t_process}
    if ttft:
        end_to_end["ttft_p95_ms"] = stats.percentile(ttft, 95) * 1e3
    if tpot:
        end_to_end["tpot_p95_ms"] = stats.percentile(tpot, 95) * 1e3
    return {
        "correct": all(checks.values()), "checks": checks,
        "attempted": len(in_window), "failed": len(failed),
        "end_to_end": end_to_end,
        "units": {"serve_tok_s": "tokens/s", "ttft_p95_ms": "ms",
                  "tpot_p95_ms": "ms", "setup_s": "s"},
        "counters": {"engine_stats": engine_stats,
                     "max_slots": int(settings["max_slots"]),
                     "clients": clients},
        "trace": trace_lib.reduce(captured[0]) if captured else None,
    }
