"""Speculative decoding: a small draft model proposes, the target verifies.

No reference analog (the reference has no inference stack at all).  Greedy
speculative decoding is EXACT: the output token sequence is identical to
target-only greedy decode, but the target runs once per ~accepted-run of
draft tokens instead of once per token — and its chunk forward
(`GPT._decode_chunk`) scores k positions in one pass, turning k
bandwidth-bound single-token reads of the weights into one.  Wall-clock
win ≈ (mean accepted run length) / (1 + cost_draft/cost_target · k).

Mechanics worth noting:

- **No cache rollback.**  Both caches are linear (slot == position) and
  every attention mask stops at the current position, so entries written
  for rejected draft tokens are never attended and are overwritten when
  real tokens land on those positions.
- **Self-repairing feed.**  Each round feeds "the last token" (which may
  be a correction the model never processed) at its position, so both
  models' caches stay consistent without special cases.
- Greedy only (exactness is the contract); batch size 1 (acceptance
  length varies per row); rolling-window caches unsupported (the chunk
  path needs linear slots).
- **Serving**: because greedy speculative decode obeys the same
  exactness contract as `serve.ServeEngine` (token-identical to target
  greedy `generate()`), the serve engine ROUTES single-stream (batch-1)
  requests through this path: construct the engine with
  ``draft_model=``/``draft_params=`` and submit with
  ``speculative=True`` (or call `serve_speculative` below).  An idle
  engine drafts with `build_draft_proposer` and verifies through its
  PAGED chunk scorer — draft tokens land in the request's scratch
  blocks and only accepted tokens' positions survive (rejected
  positions are rewritten before the causal mask can expose them, the
  same no-rollback property as the linear caches here).  A busy engine
  decodes the request in a normal continuous-batching slot instead;
  clients cannot tell which path produced a response.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .transformer import GPT


def build_draft_proposer(draft: GPT, draft_params, k: int):
    """Jitted draft proposer ``(cache, tok [1], pos) -> (cache, [k])``:
    all ``k`` draft steps in ONE dispatch (a host loop of k jit calls
    would pay k dispatch latencies per round).  The draft cache absorbs
    ``tok`` at ``pos`` first, then greedily extends — shared by
    `speculative_generate` and the serve engine's speculative lane so
    the two drafting paths cannot drift."""

    def _draft_k(cache, tok, pos):
        def step(carry, i):
            c, t = carry
            logits, c = draft._decode_token(draft_params, c, t, pos + i)
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)
            return (c, nxt), nxt

        (cache, _), toks = jax.lax.scan(
            step, (cache, tok), jnp.arange(k))
        return cache, toks[:, 0]  # [k] drafted tokens

    return jax.jit(_draft_k)


def serve_speculative(engine: Any, prompt, max_new_tokens: int,
                      timeout: Optional[float] = None) -> np.ndarray:
    """Route one single-stream request through a running ServeEngine's
    speculative lane (the engine must carry a draft model).  Blocks for
    the full token sequence — token-identical to target-only greedy
    `generate()` whichever lane actually served it."""
    return engine.submit(prompt, max_new_tokens,
                         speculative=True).result(timeout)


def speculative_generate(target: GPT, target_params,
                         draft: GPT, draft_params,
                         prompt, max_new_tokens: int,
                         k: int = 4) -> Tuple[jax.Array, dict]:
    """Greedy decode of ``max_new_tokens`` tokens, exact vs target-only
    greedy.  Returns (tokens [1, prompt+new], stats dict with
    ``rounds``/``accept_rate``).

    ``draft`` and ``target`` must share the vocabulary; ``k`` is the
    number of tokens drafted per round.
    """
    prompt = jnp.asarray(prompt, jnp.int32)
    # explicit single-stream shape contract (not an implicit assumption):
    # acceptance length varies per row, so rows cannot share a chunk pass
    if prompt.ndim != 2 or prompt.shape[0] != 1:
        raise ValueError(
            "speculative decoding is single-stream: expected a prompt "
            f"shaped [1, prompt_len], got {tuple(prompt.shape)} -- batch "
            "requests belong in serve.ServeEngine's continuous-batching "
            "slots; only batch-1 streams may route through speculative "
            "decode")
    if max_new_tokens < 1:
        raise ValueError("max_new_tokens must be >= 1")
    if target.cfg.sliding_window is not None or \
            draft.cfg.sliding_window is not None:
        raise NotImplementedError(
            "speculative decoding needs linear caches (sliding_window "
            "unsupported)")
    target_params = jax.tree.map(jnp.asarray, target_params)
    draft_params = jax.tree.map(jnp.asarray, draft_params)
    s0 = prompt.shape[1]
    total = s0 + max_new_tokens
    for m, name in ((target, "target"), (draft, "draft")):
        if total > m.cfg.max_seq_len:
            raise ValueError(f"{name} max_seq_len {m.cfg.max_seq_len} < "
                             f"{total}")

    t_mesh, target.mesh = target.mesh, None
    d_mesh, draft.mesh = draft.mesh, None
    try:
        # caches get k slots of headroom: the final round may draft/score
        # up to k positions past the last needed token, and an
        # out-of-range dynamic_update_slice would silently CLAMP onto (and
        # corrupt) the last real slots
        cache_len = total + k
        h_t, t_cache = target._prefill(target_params, prompt, cache_len)
        _, d_cache = draft._prefill(draft_params, prompt, cache_len)

        d_propose = build_draft_proposer(draft, draft_params, k)
        t_chunk = jax.jit(lambda c, toks, p: target._decode_chunk(
            target_params, c, toks, p))

        dt = target.compute_dtype
        first = jnp.argmax(
            (h_t @ target._unembed_w(target_params, dt)).astype(jnp.float32),
            -1).astype(jnp.int32)  # token at position s0
        out = [int(first[0])]
        rounds = 0
        accepted_total = 0
        while len(out) < max_new_tokens:
            rounds += 1
            pos = s0 + len(out) - 1   # position of the newest token
            last = jnp.asarray([out[-1]], jnp.int32)
            # draft proposes k tokens (its cache absorbs `last` first)
            d_cache, draft_toks = d_propose(d_cache, last,
                                            jnp.asarray(pos))
            drafts = [int(t) for t in np.asarray(draft_toks)]
            # target scores [last, d_1..d_{k-1}] in ONE chunk pass:
            # logits[i] predicts position pos+i+1 (validates drafts[i])
            chunk = jnp.asarray([[out[-1]] + drafts[:-1]], jnp.int32)
            t_logits, t_cache = t_chunk(t_cache, chunk, pos)
            greedy = np.asarray(jnp.argmax(t_logits[0], -1))
            accept = 0
            while accept < k and greedy[accept] == drafts[accept] and \
                    len(out) + accept + 1 < max_new_tokens:
                accept += 1
            accepted_total += accept
            new = drafts[:accept] + [int(greedy[accept])] \
                if accept < k else drafts[:accept]
            out.extend(new[:max_new_tokens - len(out)])
        tokens = jnp.concatenate(
            [prompt, jnp.asarray([out], jnp.int32)], axis=1)
        stats = {"rounds": rounds,
                 "accept_rate": accepted_total / max(rounds * k, 1)}
        return tokens, stats
    finally:
        target.mesh = t_mesh
        draft.mesh = d_mesh
