"""One general traffic generator.  A traffic mix is a data file of
parameters under ``benchmark/traffic/``; this module turns it and
``--seed`` into inputs.  The program sees only the generated inputs.

Every seed gets the SAME multiset of sizes: the sizes are drawn from the
mix's own ``shape_seed`` and only their order (and the token ids) come
from ``--seed``, so two seeds differ in order, never in work.
"""
from __future__ import annotations

import dataclasses

import numpy as np


def _rng(*words) -> np.random.Generator:
    # SeedSequence takes any non-negative int, so seeds over 2**31 are fine
    return np.random.default_rng([int(w) & 0xFFFFFFFFFFFFFFFF for w in words])


# --------------------------------------------------------------------- #
# training                                                               #
# --------------------------------------------------------------------- #
def train_tokens(mix: dict, seed: int, n_sequences: int, seq_len: int,
                 vocab_size: int) -> np.ndarray:
    """``[n_sequences, seq_len]`` int32 token ids with Zipf unigram
    statistics (exponent ``mix["zipf_exponent"]``): there is something
    to learn in a few steps, so the loss visibly falls."""
    if mix["kind"] != "lm_sequences":
        raise ValueError(f"not a training mix: {mix['kind']!r}")
    p = np.arange(1, vocab_size + 1, dtype=np.float64) ** -float(
        mix["zipf_exponent"])
    return _rng(seed, 1).choice(
        vocab_size, p=p / p.sum(), size=(n_sequences, seq_len)
    ).astype(np.int32)


# --------------------------------------------------------------------- #
# serving                                                                #
# --------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class Request:
    index: int
    prompt: np.ndarray      # [prompt_len] int32
    max_new_tokens: int
    shared_prefix: int      # which shared system prompt (-1: none)


def _lognormal_lengths(rng, spec: dict, n: int) -> np.ndarray:
    raw = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def request_shapes(mix: dict) -> list:
    """The mix's fixed block of ``(prompt_len, output_len, prefix_id)``:
    the same for every ``--seed``."""
    if mix["kind"] != "chat_requests":
        raise ValueError(f"not a serving mix: {mix['kind']!r}")
    rng = _rng(mix["shape_seed"], 2)
    n = int(mix["block_requests"])
    prompts = _lognormal_lengths(rng, mix["prompt_tokens"], n)
    outputs = _lognormal_lengths(rng, mix["output_tokens"], n)
    shared = mix["shared_prefix"]
    k = int(shared["count"])
    zipf = np.arange(1, k + 1, dtype=np.float64) ** -float(
        shared["zipf_exponent"])
    prefix = rng.choice(k, p=zipf / zipf.sum(), size=n)
    has_prefix = rng.permutation(n) < round(n * float(shared["share"]))
    prefix = np.where(has_prefix, prefix, -1)
    return [(int(p), int(o), int(s))
            for p, o, s in zip(prompts, outputs, prefix)]


def serve_requests(mix: dict, seed: int, vocab_size: int,
                   n_blocks: int) -> list:
    """``n_blocks`` seeded permutations of the mix's fixed block, as
    ``Request``s with uniform token ids.  A prompt with a shared prefix
    is that system prompt followed by its own suffix; its total length
    is the drawn one (never shorter than the prefix plus
    ``shared_prefix.min_suffix``)."""
    shapes = request_shapes(mix)
    shared = mix["shared_prefix"]
    rng = _rng(seed, 3)
    systems = rng.integers(0, vocab_size,
                           (int(shared["count"]), int(shared["tokens"])))
    out = []
    for _ in range(n_blocks):
        for j in rng.permutation(len(shapes)):
            n_prompt, n_out, prefix = shapes[j]
            if prefix >= 0:
                n_suffix = max(n_prompt - int(shared["tokens"]),
                               int(shared["min_suffix"]))
                prompt = np.concatenate(
                    [systems[prefix], rng.integers(0, vocab_size, n_suffix)])
            else:
                prompt = rng.integers(0, vocab_size, n_prompt)
            out.append(Request(len(out), prompt.astype(np.int32), n_out,
                               prefix))
    return out
