"""Flagship model: GPT-style decoder LM, sharded over every mesh axis.

No reference analog (the reference delegates models to the user; its largest
example is an MNIST MLP, examples/ray_ddp_example.py:18-59).  This model
exists to exercise and benchmark the framework's TPU path end-to-end:

- parameters carry **logical axis names** translated to mesh shardings by
  `parallel.sharding` (embed->fsdp for ZeRO-3, mlp/heads/vocab->tensor for
  megatron-style TP, batch->(data,fsdp), seq->sequence);
- layers are **stacked and scanned** (`lax.scan` over the layer dim): one
  trace/compile regardless of depth, optional `jax.checkpoint` remat, and
  the natural substrate for pipeline parallelism;
- attention dispatches to the Pallas flash kernel single-shard or ring
  attention when the mesh has a `sequence` axis (context parallelism);
- compute in bf16 (MXU-native), accumulation and softmax statistics in f32.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ..analysis import knobs
from ..core.module import TpuModule
from ..parallel import collectives as collectives_lib
from ..parallel import mesh as mesh_lib
from ..parallel import sharding as sharding_lib
from ..parallel.ring_attention import ring_attention_sharded
from ..ops.attention import FLASH_RESIDUALS, flash_attention
from ..ops.conv import gated_short_conv
from ..ops.moe import (MOE_PLAN, dropless_logical_axes, dropless_moe,
                       init_dropless_params, init_latent_moe_params,
                       init_moe_params, latent_moe, latent_moe_logical_axes,
                       moe_logical_axes, moe_mlp)
from ..ops.norms import rms_norm
from ..ops.ssm import (init_mamba2_params, mamba2_logical_axes,
                       mamba2_mixer)
from ..utils.logging import log
from ..utils.scope import scoped


@dataclasses.dataclass
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_heads: int = 8
    d_ff: int = 2048
    n_layers: int = 8
    max_seq_len: int = 2048
    dropout: float = 0.0          # residual-branch dropout (train only)
    causal: bool = True
    remat: bool = False           # jax.checkpoint each layer
    # what the rematerialized backward may keep: "nothing" recomputes the
    # whole layer (min HBM) but the named residuals, "dots" saves matmul
    # outputs too (recompute only elementwise — the usual sweet spot:
    # matmuls are the expensive part to redo on the MXU, activations are
    # the expensive part to hold in HBM).  The named residuals, kept by
    # every policy (``_KEPT_UNDER_REMAT``): the flash kernel's output and
    # log-sum-exp where the key length spans several blocks, and the
    # routing plan of a dropless expert layer
    remat_policy: str = "nothing"
    pipeline_microbatches: int = 4  # GPipe schedule when mesh has pipeline>1
    rope_theta: float = 10000.0
    tie_embeddings: bool = True
    # MoE: num_experts > 1 replaces every dense MLP with a routed
    # mixture-of-experts block sharded over the `expert` mesh axis
    num_experts: int = 1
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    # streaming LM-head loss (ops/losses.py): never materializes the full
    # [B,S,V] logits; engaged when the mesh doesn't shard seq/tensor/pipe
    fused_loss: bool = True
    loss_chunk_rows: int = 1024
    # loss shaping: eps-smoothed targets (regularization) and the PaLM
    # z-loss term z * logsumexp(logits)^2 (keeps the softmax normalizer
    # near 1 — the standard bf16-training stability knob)
    label_smoothing: float = 0.0
    z_loss: float = 0.0
    # context-parallel strategy over the `sequence` mesh axis:
    # "ring" (KV neighbor exchange) or "ulysses" (head/seq all-to-all;
    # needs n_heads % sequence_axis == 0)
    context_parallel: str = "ring"
    # grouped-query attention: fewer K/V heads than Q heads shrinks the
    # decode KV cache (and its HBM traffic) by n_heads/n_kv_heads;
    # None = multi-head attention (kv heads == query heads)
    n_kv_heads: Optional[int] = None
    # sliding-window (Mistral-style) causal attention: each token sees at
    # most the last `sliding_window` tokens; None = full causal.  Not
    # combinable with a sharded sequence axis (ring/Ulysses are full-
    # attention strategies)
    sliding_window: Optional[int] = None
    # flash-attention grid blocks; None = ops/attention.py default (512,
    # env-overridable).  A block that holds the whole sequence is the fast
    # one on the v5e: no k-walk carry, and the kernels walk only its causal
    # triangle.  At seq 1024, heads of 64, us a head forward / backward:
    # 2.5 / 4.8 with 1024 against 5.4 / 5.0 with 512 (PERF.md section 6,
    # PR 26); both benchmark cells set 1024.
    flash_block_q: Optional[int] = None
    flash_block_k: Optional[int] = None
    # ---- the layer stack ---------------------------------------------- #
    # The stack is a sequence of RUNS (``layer_runs()``): consecutive
    # layers of one (operator, feed-forward) kind are one stacked
    # subtree of the parameters and one ``lax.scan``, so compile time
    # grows with runs, not layers.  Without ``layer_types`` and with
    # ``moe_router="softmax"`` it is ONE run of attention blocks in
    # ``params["layers"]``; otherwise run i lives in
    # ``params["layers_<i>"]`` (``run_keys()``: the names are a format,
    # checkpoints and the benchmark's references read them).  The
    # fields below state the rest of the block and each is read where
    # it acts; ``layer_runs()`` accepts the combinations that have a
    # reference behind them (GELU, interleaved rotary and no QK-norm
    # with one run; SwiGLU, QK-norm and rotate-half rotary with
    # ``layer_types`` / the sigmoid router; SwiGLU, rotate-half rotary
    # and no QK-norm with ``post_norms``, the looped stack below) and
    # refuses the rest by name.  Decode, serving, quantize_weights and
    # the pipeline walk the one run in ``params["layers"]`` once and
    # refuse any other stack by name.
    #
    # per-layer sequence operator, "conv" (gated short convolution,
    # ops/conv.py) or "full_attention"; None = attention everywhere
    layer_types: Optional[Tuple[str, ...]] = None
    conv_kernel: int = 3          # taps of the short convolution
    # "softmax": the capacity path of ops/moe.py in every layer (today's
    # ``num_experts > 1``).  "sigmoid": the dropless path -- sigmoid
    # scores, a selection bias, top-k weights normalised
    # (``moe_norm_topk``) and scaled (``moe_routed_scale``), SwiGLU
    # experts of width ``moe_d_ff`` -- in every layer past the first
    # ``num_dense_layers``, which keep a dense MLP of width ``d_ff``
    moe_router: str = "softmax"
    num_dense_layers: int = 0
    moe_d_ff: Optional[int] = None        # expert width; None = d_ff
    # ids of the experts this chip holds (the router keeps all
    # ``num_experts`` outputs and its ``moe_top_k`` whatever is held;
    # what absent experts would add is left out); None = all of them
    moe_experts_held: Optional[Tuple[int, ...]] = None
    moe_norm_topk: bool = True
    moe_routed_scale: float = 1.0
    gated_mlp: bool = False       # SwiGLU (silu(x W1) * (x W3)) W2
    qk_norm: bool = False         # per-head RMSNorm on q and k before rope
    # "interleaved": pairs (2i, 2i+1); "half": pairs (i, i + head_dim/2),
    # the rotate-half convention of the published checkpoints; "none": no
    # rotation (a hybrid stack whose state-space layers carry position)
    rope_style: str = "interleaved"
    norm_eps: float = 1e-6
    # ---- a hybrid stack of single-part layers (nemotron_h) ------------ #
    # The published pattern, one letter a layer, each layer ONE part with
    # its own norm and residual: "M" a Mamba-2 mixer (ops/ssm.py), "E" a
    # latent expert layer (ops/moe.latent_moe: ``num_experts`` ReLU^2
    # experts of width ``moe_d_ff`` in a latent of ``moe_latent_dim``,
    # one shared ReLU^2 expert of ``moe_shared_d_ff`` at full width, the
    # sigmoid router's fields above), "*" attention (no QK-norm, no
    # rotation).  A mixer followed by "E" is this file's block exactly
    # (operator, then feed-forward, each with norm and residual), so the
    # letters pair into blocks whose operator or feed-forward may be
    # ABSENT, and consecutive blocks of one kind are one run:
    # "EMEMEMEMEM*" is (none, latent), 4 x (mamba, latent), (mamba,
    # none), (attn, none).  ``n_layers`` counts the letters.
    hybrid_pattern: Optional[str] = None
    # the head size as a value; None = d_model // n_heads
    attn_head_dim: Optional[int] = None
    # ids of the query heads this chip holds, whole groups of their KV
    # heads' readers (``n_heads`` / ``n_kv_heads`` stay the model's
    # counts; a KV head is held when a held query head reads it); None =
    # all of them
    attn_heads_held: Optional[Tuple[int, ...]] = None
    # the Mamba-2 mixer: ``ssm_heads`` heads of ``ssm_head_dim`` in
    # ``ssm_groups`` groups (a group's heads share its B and C and one
    # gated norm), state ``ssm_state``, ``conv_kernel`` taps, scanned in
    # chunks of ``ssm_chunk``; ids of the groups this chip holds (None =
    # all of them)
    ssm_heads: int = 0
    ssm_head_dim: int = 64
    ssm_groups: int = 1
    ssm_state: int = 128
    ssm_chunk: int = 128
    ssm_groups_held: Optional[Tuple[int, ...]] = None
    moe_latent_dim: Optional[int] = None
    moe_shared_d_ff: Optional[int] = None
    # ---- a looped stack (ouro) ---------------------------------------- #
    # ``post_norms``: the sandwich norm, an RMSNorm AFTER each part too
    # (``h + N2(Attn(N1(h)))``, then ``+ N4(SwiGLU(N3(.)))``; four
    # scales a layer), in one run of attention blocks with SwiGLU,
    # rotate-half rotary and no QK-norm.  ``loop_passes``: the whole
    # stack runs that many times on THE SAME weights, every pass ending
    # in the final norm, whose output feeds the next pass and the head
    # (1 = every layer once).  ``exit_gate``: one ``Linear(d_model, 1)``
    # with bias on each pass's normed state; the training loss is the
    # passes' cross-entropies weighted by the gates' exit distribution,
    # less ``exit_beta`` times that distribution's entropy
    # (``GPT._loop_loss``).  ``forward`` gives the last pass's logits.
    post_norms: bool = False
    loop_passes: int = 1
    exit_gate: bool = False
    exit_beta: float = 0.0

    def __post_init__(self):
        # lists from JSON (a benchmark config, a checkpoint's hparams)
        for name in ("layer_types", "moe_experts_held", "attn_heads_held",
                     "ssm_groups_held"):
            value = getattr(self, name)
            if value is not None:
                setattr(self, name, tuple(value))
        self.layer_runs()       # a config no block runs fails here

    # the combinations with a reference behind them (``benchmark/lib``
    # ``reference.py``: GELU, interleaved rotary, no QK-norm;
    # ``models/reference_lfm2.py``: ``_MIXED_BLOCK``; ``models/
    # reference_nemotron_h.py``: ``_HYBRID_BLOCK``; ``models/
    # reference_ouro.py``: ``_LOOP_BLOCK``).  These fields keep their
    # defaults unless ``layer_types``, the sigmoid router or
    # ``post_norms`` is set, and with either of the first two the
    # block's three switches take ``_MIXED_BLOCK``'s values
    _MIXED_ONLY = (("num_dense_layers", 0), ("moe_d_ff", None),
                   ("moe_experts_held", None), ("gated_mlp", False),
                   ("qk_norm", False), ("rope_style", "interleaved"),
                   ("norm_eps", 1e-6))
    _MIXED_BLOCK = (("gated_mlp", True), ("qk_norm", True),
                    ("rope_style", "half"))
    # these belong to ``hybrid_pattern`` and keep their defaults without
    # it; with it the block's switches take ``_HYBRID_BLOCK``'s values
    _HYBRID_ONLY = (("attn_head_dim", None), ("attn_heads_held", None),
                    ("ssm_heads", 0), ("ssm_groups_held", None),
                    ("moe_latent_dim", None), ("moe_shared_d_ff", None))
    _HYBRID_BLOCK = (("gated_mlp", False), ("qk_norm", False),
                     ("rope_style", "none"), ("layer_types", None),
                     ("num_dense_layers", 0), ("moe_router", "sigmoid"))
    # these belong to the looped stack (``post_norms``) and keep their
    # defaults without it; with it the block is ``_LOOP_BLOCK``'s: one
    # run of full causal multi-head attention + dense SwiGLU blocks.
    # ``attn_head_dim``, ``tie_embeddings`` and ``norm_eps`` are values
    _LOOP_ONLY = (("loop_passes", 1), ("exit_gate", False),
                  ("exit_beta", 0.0))
    _LOOP_BLOCK = (("gated_mlp", True), ("qk_norm", False),
                   ("rope_style", "half"), ("layer_types", None),
                   ("hybrid_pattern", None), ("moe_router", "softmax"),
                   ("num_experts", 1), ("n_kv_heads", None),
                   ("sliding_window", None), ("causal", True),
                   ("attn_heads_held", None))

    @property
    def _mixed(self) -> bool:
        return (self.layer_types is not None or self.moe_router == "sigmoid"
                or self.hybrid_pattern is not None)

    def layer_runs(self) -> Tuple[Tuple[str, str, int], ...]:
        """``((operator, feed_forward, n_blocks), ...)`` in stack order.
        operator: "conv" | "attn" | "mamba" (``ops/ssm.mamba2_mixer``) |
        "none"; feed_forward: "dense" | "capacity" (``ops/moe.moe_mlp``)
        | "sparse" (``ops/moe.dropless_moe``) | "latent"
        (``ops/moe.latent_moe``) | "none".  Also the config's input
        check: a stack may set, beside the sizes, exactly one of four
        blocks -- nothing (GELU, interleaved rotary, no QK-norm; one
        run, dense or capacity MoE), ``layer_types`` / ``moe_router=
        "sigmoid"`` (SwiGLU, QK-norm, rotate-half), ``hybrid_pattern``
        (``_hybrid_runs``) or ``post_norms`` (``_loop_runs``: SwiGLU,
        rotate-half, no QK-norm, sandwich norms, ``loop_passes`` runs of
        the one run) -- and anything else is refused by name."""
        if self.moe_router not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown moe_router {self.moe_router!r}")
        if self.post_norms:
            return self._loop_runs()
        for name, default in self._LOOP_ONLY:
            if getattr(self, name) != default:
                raise ValueError(
                    f"TransformerConfig.{name} belongs to the looped stack:"
                    " set post_norms with it")
        if self.hybrid_pattern is not None:
            return self._hybrid_runs()
        for name, default in self._HYBRID_ONLY:
            if getattr(self, name) != default:
                raise ValueError(
                    f"TransformerConfig.{name} belongs to a hybrid stack: "
                    "set hybrid_pattern with it")
        if not self._mixed:
            for name, default in self._MIXED_ONLY:
                if getattr(self, name) != default:
                    raise ValueError(
                        f"TransformerConfig.{name} belongs to the mixed "
                        "layer stack: set layer_types or "
                        "moe_router='sigmoid' with it")
            return (("attn", "capacity" if self.num_experts > 1
                     else "dense", self.n_layers),)
        for name, needed in self._MIXED_BLOCK:
            if getattr(self, name) != needed:
                raise NotImplementedError(
                    f"a mixed layer stack (layer_types / moe_router="
                    f"'sigmoid') runs {name}={needed!r} only")
        types = self.layer_types or ("full_attention",) * self.n_layers
        if len(types) != self.n_layers or set(types) - {
                "conv", "full_attention"}:
            raise ValueError(
                f"layer_types must name n_layers={self.n_layers} layers, "
                f"each 'conv' or 'full_attention'; got {types}")
        sparse = self.moe_router == "sigmoid" and self.num_experts > 1
        if self.num_experts > 1 and not sparse:
            raise NotImplementedError(
                "a mixed layer stack (layer_types) carries the dropless "
                "expert layer only: set moe_router='sigmoid'")
        runs = []
        for i, kind in enumerate(types):
            key = ("conv" if kind == "conv" else "attn",
                   "sparse" if sparse and i >= self.num_dense_layers
                   else "dense")
            if runs and tuple(runs[-1][:2]) == key:
                runs[-1][2] += 1
            else:
                runs.append([*key, 1])
        return tuple(tuple(r) for r in runs)

    def _loop_runs(self) -> Tuple[Tuple[str, str, int], ...]:
        """``layer_runs()`` of the looped stack (``post_norms``): one
        run of attention + dense SwiGLU blocks, which ``loop_passes``
        says how often to run.  What has no reference behind it is
        refused by name."""
        for name, needed in self._LOOP_BLOCK:
            if getattr(self, name) != needed:
                raise NotImplementedError(
                    f"a looped stack (post_norms) runs {name}={needed!r} "
                    "only (set gated_mlp=True, rope_style='half'; "
                    "attn_head_dim, tie_embeddings, norm_eps, rope_theta "
                    "and loop_passes are values)")
        if self.loop_passes < 1:
            raise ValueError(
                f"loop_passes must be at least 1; got {self.loop_passes}")
        if self.exit_gate != (self.loop_passes > 1):
            raise NotImplementedError(
                "the exit gate weighs the passes of a looped stack: set "
                "exit_gate=True with loop_passes > 1 and with nothing "
                f"else; got exit_gate={self.exit_gate}, loop_passes="
                f"{self.loop_passes}")
        if self.exit_beta and not self.exit_gate:
            raise ValueError("exit_beta belongs to exit_gate")
        if self.dropout:
            raise NotImplementedError(
                "dropout in a looped stack (post_norms) is not wired (a "
                "pass would need its own masks); set dropout=0")
        if self.label_smoothing or self.z_loss:
            raise NotImplementedError(
                "a looped stack (post_norms) has a reference for the "
                "plain cross-entropy only: set label_smoothing=0, "
                "z_loss=0")
        return (("attn", "dense", self.n_layers),)

    def _hybrid_runs(self) -> Tuple[Tuple[str, str, int], ...]:
        """``layer_runs()`` of ``hybrid_pattern``: the letters paired
        into blocks, the blocks grouped into runs.  What has no
        reference behind it is refused by name."""
        pattern = self.hybrid_pattern
        for name, needed in self._HYBRID_BLOCK:
            if getattr(self, name) != needed:
                raise NotImplementedError(
                    f"a hybrid stack (hybrid_pattern) runs {name}="
                    f"{needed!r} only")
        if len(pattern) != self.n_layers or set(pattern) - set("ME*"):
            raise NotImplementedError(
                f"hybrid_pattern must name n_layers={self.n_layers} layers,"
                " each 'M' (Mamba-2), 'E' (latent expert layer) or '*' "
                f"(attention); got {pattern!r} (a dense '-' layer has no "
                "reference here)")
        if "E" in pattern and not (
                self.num_experts > 1 and self.moe_d_ff
                and self.moe_latent_dim and self.moe_shared_d_ff):
            raise NotImplementedError(
                "an 'E' layer is the latent expert layer with one shared "
                "expert: set num_experts, moe_d_ff, moe_latent_dim and "
                "moe_shared_d_ff")
        if "M" in pattern and (self.ssm_heads <= 0
                               or self.ssm_heads % self.ssm_groups):
            raise ValueError(
                f"ssm_heads ({self.ssm_heads}) must be a positive multiple "
                f"of ssm_groups ({self.ssm_groups})")
        per_kv = self.n_heads // self.kv_heads
        held, kv_held = self.heads_held, self.kv_heads_held
        if list(held) != sorted(set(held)) or [
                h // per_kv for h in held] != [
                kv for kv in kv_held
                for _ in range(len(held) // len(kv_held))]:
            raise NotImplementedError(
                "attn_heads_held must be ascending and give every held KV "
                f"head as many readers as the others; got {held}")
        runs, i = [], 0
        while i < len(pattern):
            op = {"M": "mamba", "*": "attn", "E": "none"}[pattern[i]]
            i += op != "none"
            ff = "latent" if pattern[i:i + 1] == "E" else "none"
            i += ff != "none"
            if runs and runs[-1][:2] == [op, ff]:
                runs[-1][2] += 1
            else:
                runs.append([op, ff, 1])
        return tuple(tuple(r) for r in runs)

    def run_keys(self) -> Tuple[str, ...]:
        """The parameter subtree of each run, in stack order."""
        if not self._mixed:
            return ("layers",)
        return tuple(f"layers_{i}" for i in range(len(self.layer_runs())))

    @property
    def experts_held(self) -> Tuple[int, ...]:
        held = self.moe_experts_held
        return tuple(range(self.num_experts)) if held is None else held

    @property
    def head_dim(self) -> int:
        if self.attn_head_dim is not None:
            return self.attn_head_dim
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads

    @property
    def heads_held(self) -> Tuple[int, ...]:
        held = self.attn_heads_held
        return tuple(range(self.n_heads)) if held is None else held

    @property
    def kv_heads_held(self) -> Tuple[int, ...]:
        """The KV heads the held query heads read."""
        per_kv = self.n_heads // self.kv_heads
        return tuple(sorted({h // per_kv for h in self.heads_held}))

    @property
    def ssm_held(self) -> Tuple[int, int]:
        """(heads, groups) of the Mamba-2 mixer held here."""
        groups = (self.ssm_groups if self.ssm_groups_held is None
                  else len(self.ssm_groups_held))
        return groups * (self.ssm_heads // self.ssm_groups), groups

    @property
    def kv_heads(self) -> int:
        kv = self.n_kv_heads if self.n_kv_heads is not None else self.n_heads
        assert self.n_heads % kv == 0, \
            f"n_heads ({self.n_heads}) must be divisible by n_kv_heads ({kv})"
        return kv


def _rope(x: jax.Array, positions: jax.Array, theta: float,
          style: str = "interleaved") -> jax.Array:
    """Rotary embeddings.  x: [b, h, s, d]; positions: [s], shared by the
    batch, or per row [b, s] ([b, 1]: every row of a decode step at its
    own position).  One arithmetic per element whatever the rank, so a
    query at position p comes out the same on every path (token identity
    of the decode paths rests on it).  ``style``: "interleaved" pairs
    dimension 2i with 2i + 1, "half" i with i + d/2 (rotate-half, the
    published checkpoints' convention).

    The head stays whole on the lanes: ``x * cos + partner(x) * sin`` in
    float32 over all d lanes, where ``partner`` swaps the two members of
    each pair and carries the sign (minus on the first member).  On the
    TPU a stride-2 slice of the 64-wide lane dimension is a gather, its
    gradient a scatter-add into a zero-filled buffer and the stack /
    reshape that puts the halves back a relayout through ``[.., 32, 2]``
    arrays: six passes over q and over k a layer where one does
    (``PERF.md`` section 6, PR 30: on the chip the 355M step fell 4.3 %
    and the 1.56B one 8.0 %).  ``partner`` is a d x d signed permutation
    contracted on the MXU, which the compiler fuses with the rotation
    into one matmul fusion; each output is one input times +-1, so it is
    exact, for float32 inputs too at the highest precision.  Lane
    rotations and a select by parity, the other exact spelling, lost on
    the chip (slices and concatenates outside any fusion, more memory
    and more all-gathers under FSDP)."""
    return _rotate(x, positions, theta, style, False)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _rotate(x, positions, theta, style, inverse):
    d = x.shape[-1]
    lane = np.arange(d)
    if style == "half":
        pair, partner = lane % (d // 2), (lane + d // 2) % d
    else:
        pair, partner = lane // 2, lane ^ 1
    # column j holds one entry, at row partner(j): minus where j is the
    # first member of its pair (the other way round for the inverse)
    sign = np.where((partner > lane) != inverse, -1.0, 1.0)
    swap = sign[None, :] * (lane[:, None] == partner[None, :])
    freqs = theta ** (-jnp.asarray(2 * pair, jnp.float32) / d)
    angles = (positions[..., None].astype(jnp.float32)
              * freqs[(None,) * positions.ndim])            # [.., d]
    if angles.ndim == 3:
        angles = angles[:, None]                    # the head axis
    swapped = jnp.einsum("...k,kj->...j", x, jnp.asarray(swap, x.dtype),
                         precision=jax.lax.Precision.HIGHEST,
                         preferred_element_type=jnp.float32)
    return (x * jnp.cos(angles) + swapped * jnp.sin(angles)).astype(x.dtype)


def _rotate_fwd(x, positions, theta, style, inverse):
    return _rotate(x, positions, theta, style, inverse), positions


def _rotate_bwd(theta, style, inverse, positions, g):
    # a rotation's transpose is the rotation back: the cotangent takes
    # the same one pass, in its own dtype, with no residual of x
    return _rotate(g, positions, theta, style, not inverse), None


_rotate.defvjp(_rotate_fwd, _rotate_bwd)


def _dense(key, shape, fan_in) -> jax.Array:
    """A float32 weight drawn at scale fan_in ** -0.5."""
    return jax.random.normal(key, shape, jnp.float32) * (fan_in ** -0.5)


def _channel_quant(w: jax.Array):
    """Per-out-channel symmetric int8 of a [K, N] weight: (q8 int8,
    scale [N] f32, dq [K, N] f32).  Same scale convention as
    ``GPT.quantize_weights`` / ``ops.quant.int8_matmul``."""
    wf = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=0)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(wf / scale[None, :]), -127, 127)
    return q.astype(jnp.int8), scale, q * scale[None, :]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _int8_ste_matmul(mode, x2d, w):
    """Training-forward int8 matmul with straight-through gradients:
    ``x2d [M, K] @ int8(w [K, N])``.  The forward streams int8 through
    the ops/quant.py Pallas kernel when ``mode`` says so ("compiled" on
    TPU, "interpret" in CPU tests; None = XLA dequant-dot — still the
    int8-rounded VALUES, so the loss-tolerance story is identical); the
    backward is the standard straight-through estimator: cotangents flow
    through the dequantized weights and straight to the f32 master (the
    round is a zero-gradient a.e. staircase — without STE the weights
    would never train)."""
    out, _ = _int8_ste_fwd(mode, x2d, w)
    return out


def _int8_ste_fwd(mode, x2d, w):
    q8, scale, dq = _channel_quant(w)
    if mode in ("compiled", "interpret"):
        from ..ops import quant
        out = quant.int8_matmul(x2d, q8, scale,
                                interpret=mode == "interpret")
    else:
        out = x2d @ dq.astype(x2d.dtype)
    # residual dequant kept in w's dtype so both cotangents match their
    # primal avals exactly
    return out.astype(x2d.dtype), (x2d, dq.astype(w.dtype))


def _int8_ste_bwd(mode, res, g):
    x2d, dq = res
    gx = (g.astype(jnp.float32) @ dq.T.astype(jnp.float32)
          ).astype(x2d.dtype)
    gw = (x2d.astype(jnp.float32).T @ g.astype(jnp.float32))
    return gx, gw.astype(dq.dtype)


_int8_ste_matmul.defvjp(_int8_ste_fwd, _int8_ste_bwd)


# What every remat policy keeps of a block, by ``checkpoint_name``: dear
# to run again and almost free to hold.  The flash k-walk's output and
# log-sum-exp (tagged where the key length spans several blocks, not at
# one block a sequence) and the plan the dropless expert layer's windows
# read (masked weights, both permutations, the groups' intervals).  A
# block that ran neither carries no name and keeps what the stock
# policy keeps.
_KEPT_UNDER_REMAT = (*FLASH_RESIDUALS, MOE_PLAN)


def _remat_policy(name: str):
    """Map a config string to a jax.checkpoint policy: the named
    residuals, and what the stock policy of that name keeps."""
    stock = {
        "dots": jax.checkpoint_policies.dots_saveable,
        "dots_with_no_batch_dims":
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        "everything": jax.checkpoint_policies.everything_saveable,
    }
    named = jax.checkpoint_policies.save_only_these_names(
        *_KEPT_UNDER_REMAT)
    if name == "nothing":
        return named
    if name not in stock:
        raise ValueError(f"unknown remat_policy {name!r}; choose from "
                         f"{sorted(('nothing', *stock))}")
    return jax.checkpoint_policies.save_from_both_policies(
        named, stock[name])


class GPT(TpuModule):
    """Decoder-only LM.  Batch format: dict(input_ids=[B,S] int32) or a bare
    [B,S] array; loss = next-token cross entropy."""

    def __init__(self, config: Optional[TransformerConfig] = None,
                 lr: float = 3e-4, **cfg_overrides):
        """``lr`` may be a float or an optax schedule (step -> lr), e.g.
        ``utils.schedules.warmup_cosine(...)``; schedules are also exposed
        as ``self.lr_schedule`` so the trainer logs per-step ``lr``."""
        super().__init__()
        if config is None:
            config = TransformerConfig(**cfg_overrides)
        elif isinstance(config, dict):
            # hparams round-trip: load_from_checkpoint calls cls(**hparams)
            # with the asdict()-serialized config
            config = TransformerConfig(**config)
        self.cfg = config
        lr = self.coerce_checkpoint_lr(lr, 3e-4, "GPT")
        self.lr = lr
        if callable(lr):
            self.lr_schedule = lr
        # a schedule callable is not checkpoint-serializable; record its repr
        self.save_hyperparameters(config=dataclasses.asdict(config),
                                  lr=repr(lr) if callable(lr) else lr)

    # ------------------------------------------------------------------ #
    # Parameters                                                         #
    # ------------------------------------------------------------------ #
    def init_params(self, rng) -> Dict[str, Any]:
        cfg = self.cfg
        d = cfg.d_model
        k_embed, k_layers, k_out = jax.random.split(rng, 3)
        layer_keys = jax.random.split(k_layers, cfg.n_layers)
        params = {
            "embed": _dense(k_embed, (cfg.vocab_size, d), d) * d ** 0.5 * 0.02,
            "ln_f": jnp.ones((d,), jnp.float32),
        }
        first = 0
        for key, (op, ff, n) in zip(cfg.run_keys(), cfg.layer_runs()):
            # stacked: leading dim the run's layers
            params[key] = jax.vmap(functools.partial(
                self._init_layer, op=op, ff=ff))(layer_keys[first:first + n])
            first += n
        if not cfg.tie_embeddings:
            params["unembed"] = _dense(k_out, (d, cfg.vocab_size), d)
        if cfg.exit_gate:
            # Linear(d_model, 1) with bias, shared by the passes; its key
            # is folded from the head's, so that the three-way split
            # above (a format) stays as it is
            params["exit_gate"] = {
                "w": _dense(jax.random.fold_in(k_out, 1), (d, 1), d),
                "b": jnp.zeros((1,), jnp.float32)}
        return params

    def _init_layer(self, key, *, op: str, ff: str) -> Dict[str, Any]:
        """One layer: operator ``op`` ("conv" | "attn"), feed-forward
        ``ff`` ("dense" | "capacity" | "sparse").  Which of its keys a
        leaf is drawn from is part of the parameter format, and so is
        the split count: ``split(key, 6)`` and ``split(key, 8)`` give
        different keys, and the gated MLP's third projection took the
        split to 8.  A tree drawn with one count cannot be drawn again
        with the other (checkpoints, the benchmark's ``weights_seed``)."""
        cfg = self.cfg
        d, h, kv, hd, f = (cfg.d_model, len(cfg.heads_held),
                           len(cfg.kv_heads_held), cfg.head_dim, cfg.d_ff)
        ks = jax.random.split(key, 8 if cfg.gated_mlp else 6)
        # an absent part has no norm either; the sandwich norm has one
        # after each part too
        out = {name + post: jnp.ones((d,), jnp.float32)
               for name, kind in (("ln1", op), ("ln2", ff))
               if kind != "none"
               for post in (("", "_post") if cfg.post_norms else ("",))}
        if op == "mamba":
            out["ssm"] = init_mamba2_params(
                ks[0], d, cfg.ssm_held[0], cfg.ssm_head_dim,
                cfg.ssm_held[1], cfg.ssm_state, cfg.conv_kernel)
        elif op == "conv":
            out["conv"] = {
                "w_in": _dense(ks[0], (d, 3 * d), d),
                "conv_w": _dense(ks[1], (d, cfg.conv_kernel),
                                 cfg.conv_kernel),
                "w_out": _dense(ks[2], (d, d), d),
            }
        elif op == "attn":
            out["attn"] = {
                "wq": _dense(ks[0], (d, h, hd), d),
                "wk": _dense(ks[1], (d, kv, hd), d),
                "wv": _dense(ks[2], (d, kv, hd), d),
                "wo": _dense(ks[3], (h, hd, d), d),
            }
            if cfg.qk_norm:
                out["attn"]["q_norm"] = jnp.ones((hd,), jnp.float32)
                out["attn"]["k_norm"] = jnp.ones((hd,), jnp.float32)
        if ff == "sparse":
            out["mlp"] = init_dropless_params(
                ks[4], d, cfg.moe_d_ff or f, cfg.num_experts,
                len(cfg.experts_held))
        elif ff == "latent":
            out["mlp"] = init_latent_moe_params(
                ks[4], d, cfg.moe_latent_dim, cfg.moe_d_ff,
                cfg.moe_shared_d_ff, cfg.num_experts, len(cfg.experts_held))
        elif ff == "capacity":
            out["mlp"] = init_moe_params(ks[4], d, f, cfg.num_experts)
        elif ff == "dense" and cfg.gated_mlp:
            out["mlp"] = {"w1": _dense(ks[4], (d, f), d),
                          "w3": _dense(ks[5], (d, f), d),
                          "w2": _dense(ks[6], (f, d), f)}
        elif ff == "dense":
            out["mlp"] = {"wi": _dense(ks[4], (d, f), d),
                          "wo": _dense(ks[5], (f, d), f)}
        return out

    def _layer_logical_axes(self, op: str, ff: str) -> Dict[str, Any]:
        """The logical axes of ``_init_layer``'s leaves, stacked."""
        cfg = self.cfg
        axes: Dict[str, Any] = {
            name + post: ("layers", None)
            for name, kind in (("ln1", op), ("ln2", ff)) if kind != "none"
            for post in (("", "_post") if cfg.post_norms else ("",))}
        if op == "mamba":
            axes["ssm"] = {name: ("layers",) + ax
                           for name, ax in mamba2_logical_axes().items()}
        elif op == "conv":
            axes["conv"] = {"w_in": ("layers", "embed", "mlp"),
                            "conv_w": ("layers", None, None),
                            "w_out": ("layers", "mlp", "embed")}
        elif op == "attn":
            axes["attn"] = {"wq": ("layers", "embed", "heads", "kv"),
                            "wk": ("layers", "embed", "heads", "kv"),
                            "wv": ("layers", "embed", "heads", "kv"),
                            "wo": ("layers", "heads", "kv", "embed")}
            if cfg.qk_norm:
                axes["attn"]["q_norm"] = ("layers", None)
                axes["attn"]["k_norm"] = ("layers", None)
        if ff in ("sparse", "capacity"):
            expert_axes = (dropless_logical_axes() if ff == "sparse"
                           else moe_logical_axes())
            axes["mlp"] = {name: ("layers",) + ax
                           for name, ax in expert_axes.items()}
        elif ff == "latent":
            axes["mlp"] = jax.tree.map(
                lambda ax: ("layers",) + ax, latent_moe_logical_axes(),
                is_leaf=lambda ax: isinstance(ax, tuple))
        elif ff == "dense" and cfg.gated_mlp:
            axes["mlp"] = {"w1": ("layers", "embed", "mlp"),
                           "w3": ("layers", "embed", "mlp"),
                           "w2": ("layers", "mlp", "embed")}
        elif ff == "dense":
            axes["mlp"] = {"wi": ("layers", "embed", "mlp"),
                           "wo": ("layers", "mlp", "embed")}
        return axes

    def param_logical_axes(self) -> Dict[str, Any]:
        """Logical axis names per leaf; consumed by the accelerator to build
        mesh shardings (parallel/sharding.py rules)."""
        cfg = self.cfg
        axes = {"embed": ("vocab", "embed"), "ln_f": (None,)}
        for key, (op, ff, _) in zip(cfg.run_keys(), cfg.layer_runs()):
            axes[key] = self._layer_logical_axes(op, ff)
        if not cfg.tie_embeddings:
            axes["unembed"] = ("embed", "vocab")
        if cfg.exit_gate:
            axes["exit_gate"] = {"w": ("embed", None), "b": (None,)}
        return axes

    def scanned_param_subtrees(self) -> Tuple[str, ...]:
        """The layer stack is scanned — the overlap-aware FSDP gather
        (``Trainer(gather_mode="scan")``) keeps it fsdp-sharded as scan
        operands and all-gathers each layer inside the scan body: one
        stacked subtree per run."""
        return self.cfg.run_keys()

    def _uniform_stack_only(self, what: str) -> str:
        """Every walker of ``params["layers"]`` as ONE run of attention
        blocks with a KV cache, run ONCE, calls this first and gets the
        run's feed-forward kind: any other stack has no such subtree (a
        conv layer's serving state is not a KV cache, and the decode
        blocks know neither QK-norm nor rotate-half rotary), and a
        looped stack is one run whose cache would hold ``loop_passes`` x
        ``n_layers`` entries behind a block with four norms."""
        if self.cfg.post_norms:
            raise NotImplementedError(
                f"{what} walks params['layers'] once, as one uniform "
                "stack of pre-norm attention blocks (GELU, interleaved "
                "rotary); a looped stack (TransformerConfig.post_norms / "
                "loop_passes / exit_gate: sandwich norms, the stack run "
                "several times on shared weights) trains only")
        if self.cfg.run_keys() != ("layers",):
            raise NotImplementedError(
                f"{what} walks params['layers'] as one uniform stack of "
                "pre-norm attention blocks (GELU, interleaved rotary); a "
                "mixed layer stack (TransformerConfig.layer_types / "
                "moe_router='sigmoid' / hybrid_pattern: conv, Mamba-2 and "
                "expert layers, layers of one part) trains only")
        return self.cfg.layer_runs()[0][1]

    # ------------------------------------------------------------------ #
    # Forward                                                            #
    # ------------------------------------------------------------------ #
    def _constrain(self, x, *spec):
        if self.mesh is not None:
            return sharding_lib.shard_constraint(
                # constraint shim: the spec entries come from the
                # inventoried logical rules (parallel/sharding.py)
                # graftlint: ok(sharding-inventory) — only tuple->P here
                x, self.mesh, jax.sharding.PartitionSpec(*spec))
        return x

    @scoped("gpt/embed")
    def _embed_lookup(self, params, tokens):
        """Token ids -> embedding rows, [*, d].

        The table is vocab-sharded over the tensor axis
        (param_logical_axes: embed -> ("vocab", "embed")), and XLA cannot
        partition a gather whose operand is sharded along the gathered
        dim: it replicates the whole table first ("Involuntary full
        rematerialization" — a per-step all-gather of the embedding on a
        TP pod).  When the tensor axis is real, contract over vocab with
        a one-hot matmul instead: each shard contributes its own rows and
        the tensor-axis psum assembles the result on the MXU.  Plain
        gather otherwise (no tensor sharding = no pathology, and gather
        is cheaper than the [*, V] one-hot)."""
        dt = self.compute_dtype
        w = params["embed"]
        t_size = (mesh_lib.mesh_axis_size(self.mesh, mesh_lib.TENSOR_AXIS)
                  if self.mesh is not None else 1)
        if t_size <= 1:
            if self._is_q8(w):
                # gather the int8 ROWS first, dequantize only those --
                # dequantizing the whole [V, d] table per decode step
                # would re-stream 3x its bytes for a handful of rows
                rows = w["q8"][tokens].astype(jnp.float32)
                return (rows * w["scale"].reshape(-1)).astype(dt)
            return self._wt(w, dt)[tokens]
        onehot = jax.nn.one_hot(tokens, self.cfg.vocab_size, dtype=dt)
        return jnp.einsum("...v,vd->...d", onehot, self._wt(w, dt))

    @scoped("gpt/norm")
    def _rms_norm(self, x, scale):
        # fused pallas kernel on TPU, jnp reference elsewhere
        # (ops/norms.py); per-shard on a multi-device mesh, where x is
        # [batch, seq, d] (or [batch, d]) rows and the scale replicated
        rows = ("batch", "seq", None) if x.ndim == 3 else ("batch", None)
        norm = rms_norm
        if self.cfg.norm_eps != 1e-6:
            eps = self.cfg.norm_eps

            def norm(rows_, scale_):
                return rms_norm(rows_, scale_, eps)
        return sharding_lib.shard_local(
            norm, self.mesh, (rows, (None,)), rows)(x, scale)

    def _attention(self, q, k, v):
        if self.mesh is not None and mesh_lib.mesh_axis_size(
                self.mesh, mesh_lib.SEQUENCE_AXIS) > 1:
            if self.cfg.sliding_window is not None:
                raise NotImplementedError(
                    "sliding_window with a sharded sequence axis is not "
                    "supported; use ring/ulysses full attention or an "
                    "unsharded sequence")
            if self.cfg.context_parallel == "ulysses":
                from ..parallel.ulysses import ulysses_attention_sharded
                return ulysses_attention_sharded(q, k, v, self.mesh,
                                                 causal=self.cfg.causal)
            return ring_attention_sharded(q, k, v, self.mesh,
                                          causal=self.cfg.causal)
        # seq is unsharded on this path: attention is local to a
        # (batch, head) pair
        qkv = ("batch", "heads", None, None)
        attend = functools.partial(
            flash_attention, causal=self.cfg.causal,
            block_q=self.cfg.flash_block_q, block_k=self.cfg.flash_block_k,
            window=self.cfg.sliding_window)
        return sharding_lib.shard_local(
            attend, self.mesh, (qkv, qkv, qkv), qkv)(q, k, v)

    def _dropout(self, x, rng):
        if rng is None:
            return x
        p = self.cfg.dropout
        keep = jax.random.bernoulli(rng, 1.0 - p, x.shape)
        return jnp.where(keep, x / (1.0 - p), 0.0).astype(x.dtype)

    def _mlp_train_matmul(self, x, w, dt):
        """Training MLP projection ``[b,s,din] @ w[din,dout]``.  With
        ``int8_matmul`` (Trainer flag) the forward runs through
        per-out-channel int8 (the ops/quant.py kernel where its shape
        bounds allow — decode-sized rows; the int8-rounded XLA dot
        otherwise) with straight-through gradients to the f32 master;
        plain einsum otherwise.  Tensor-parallel meshes keep the dense
        path — the pallas kernel carries no GSPMD rule (the
        ``_q8_kernel_mode`` gate)."""
        if (not self.int8_matmul or self._is_q8(w)
                or not jnp.issubdtype(w.dtype, jnp.floating)):
            return jnp.einsum("bsd,df->bsf", x, self._wt(w, dt))
        from ..ops import quant
        b, s, din = x.shape
        mode = self._q8_kernel_mode()
        if mode is not None and not quant.supported(b * s, din,
                                                    w.shape[1]):
            mode = None  # int8-rounded XLA dot; values identical
        out = _int8_ste_matmul(mode, x.reshape(b * s, din).astype(dt), w)
        return out.reshape(b, s, w.shape[1])

    def _self_attention(self, x, a, positions):
        """The attention operator on normed rows ``x`` [b, s, d]:
        ``(out [b, s, d], (k, v))`` with k / v rotated, one per KV head
        (what ``_prefill`` caches)."""
        cfg = self.cfg
        dt = self.compute_dtype
        with jax.named_scope("gpt/attn"):
            def rotated(t, scale):
                if cfg.qk_norm:     # per-head RMSNorm before the rotation
                    t = rms_norm(t, a[scale], cfg.norm_eps)
                if cfg.rope_style == "none":
                    return t
                return _rope(t, positions, cfg.rope_theta, cfg.rope_style)

            q = jnp.einsum("bsd,dhk->bhsk", x, self._wt(a["wq"], dt))
            k = jnp.einsum("bsd,dhk->bhsk", x, self._wt(a["wk"], dt))
            v = jnp.einsum("bsd,dhk->bhsk", x, self._wt(a["wv"], dt))
            q = rotated(q, "q_norm")
            k = rotated(k, "k_norm")
            q = self._constrain(q, mesh_lib.BATCH_AXES,
                                mesh_lib.TENSOR_AXIS,
                                mesh_lib.SEQUENCE_AXIS, None)
            # GQA may leave fewer kv heads than the tensor axis can
            # divide; replicate kv over tensor in that case instead of
            # crashing the sharding constraint
            t_size = (mesh_lib.mesh_axis_size(self.mesh,
                                              mesh_lib.TENSOR_AXIS)
                      if self.mesh is not None else 1)
            kv_axis = (mesh_lib.TENSOR_AXIS
                       if t_size <= 1 or cfg.kv_heads % t_size == 0
                       else None)
            k = self._constrain(k, mesh_lib.BATCH_AXES, kv_axis,
                                mesh_lib.SEQUENCE_AXIS, None)
            v = self._constrain(v, mesh_lib.BATCH_AXES, kv_axis,
                                mesh_lib.SEQUENCE_AXIS, None)
            groups = len(cfg.heads_held) // len(cfg.kv_heads_held)
            if groups > 1:  # GQA: broadcast each KV head over its group
                kr = jnp.repeat(k, groups, axis=1)
                vr = jnp.repeat(v, groups, axis=1)
            else:
                kr, vr = k, v
            out = jnp.einsum("bhsk,hkd->bsd", self._attention(q, kr, vr),
                             self._wt(a["wo"], dt))
        return out, (k, v)

    def _feed_forward(self, x, m, ff: str, proj):
        """The feed-forward of kind ``ff`` on normed rows ``x``:
        ``(out, stats)``.  A dense one (GELU, or SwiGLU under
        ``gated_mlp``) multiplies through ``proj(x, w, dtype)``, the
        caller's projection (``_mlp_train_matmul`` in training,
        ``_mlp_proj_decode`` in the decode blocks); the expert layers
        bring their counters: the capacity path its auxiliary loss, the
        dropless path (which names its own scopes) its row counters."""
        cfg = self.cfg
        dt = self.compute_dtype
        if ff in ("sparse", "latent"):
            return (dropless_moe if ff == "sparse" else latent_moe)(
                x, m, top_k=cfg.moe_top_k, held=cfg.experts_held,
                num_experts=cfg.num_experts, norm_topk=cfg.moe_norm_topk,
                scale=cfg.moe_routed_scale, compute_dtype=dt,
                mesh=self.mesh)
        with jax.named_scope("gpt/mlp"):
            if ff == "capacity":
                y, aux = moe_mlp(x, self._dequant_q8_leaves(m, dt),
                                 top_k=cfg.moe_top_k,
                                 capacity_factor=cfg.moe_capacity_factor,
                                 compute_dtype=dt, mesh=self.mesh)
                return y, {"moe_aux_loss": aux}
            if cfg.gated_mlp:
                up = jax.nn.silu(proj(x, m["w1"], dt)) * proj(x, m["w3"], dt)
            else:
                up = jax.nn.gelu(proj(x, m["wi"], dt))
            up = self._constrain(up, mesh_lib.BATCH_AXES,
                                 mesh_lib.SEQUENCE_AXIS,
                                 mesh_lib.TENSOR_AXIS)
            return proj(up, m["w2" if cfg.gated_mlp else "wo"], dt), {}

    def _block(self, h, lp, positions, op: str, ff: str, dropout_rng=None):
        """One layer: ``h + op(norm(h))``, then ``+ ff(norm(.))``; a
        part of kind "none" is absent, with its norm and residual.  With
        ``cfg.post_norms`` each part's output is normed too before it is
        added (``h + norm(op(norm(h)))``).
        Returns ``(h, stats, kv)``: the feed-forward's counters (``{}``
        for a dense or absent one) and the attention operator's ``(k,
        v)`` (None for any other).  The residual adds and dropout belong
        to this frame, outside the operators' scopes."""
        dt = self.compute_dtype
        cfg = self.cfg
        r_op, kv, stats = None, None, {}
        if dropout_rng is not None:
            dropout_rng, r_op = jax.random.split(dropout_rng)
        if op != "none":
            x = self._rms_norm(h, lp["ln1"])
            if op == "conv":
                with jax.named_scope("gpt/conv"):
                    c = lp["conv"]
                    y = gated_short_conv(
                        x, self._wt(c["w_in"], dt), c["conv_w"],
                        self._wt(c["w_out"], dt))
            elif op == "mamba":
                with jax.named_scope("gpt/ssm"):
                    y = mamba2_mixer(
                        x, lp["ssm"], heads=cfg.ssm_held[0],
                        head_dim=cfg.ssm_head_dim, groups=cfg.ssm_held[1],
                        state=cfg.ssm_state, chunk=cfg.ssm_chunk,
                        eps=cfg.norm_eps, compute_dtype=dt)
            else:
                y, kv = self._self_attention(x, lp["attn"], positions)
            if cfg.post_norms:
                y = self._rms_norm(y, lp["ln1_post"])
            h = h + self._dropout(y, r_op)
        if ff != "none":
            x = self._rms_norm(h, lp["ln2"])
            y, stats = self._feed_forward(x, lp["mlp"], ff,
                                          self._mlp_train_matmul)
            if cfg.post_norms:
                y = self._rms_norm(y, lp["ln2_post"])
            h = h + self._dropout(y, dropout_rng)
        h = self._constrain(h, mesh_lib.BATCH_AXES, mesh_lib.SEQUENCE_AXIS,
                            None)
        return h, stats, kv

    def _run_stacks(self, params, h, dropout_rng=None):
        """The layer stack: one ``lax.scan`` per run, in order, each
        layer under ``jax.checkpoint`` with ``cfg.remat``.  Returns
        ``(h, aux)``; ``aux`` holds what the expert layers report and is
        empty without one: the capacity path's ``moe_aux_loss`` summed
        over layers, the dropless path's counters summed over layers
        (``moe_rounds`` and ``moe_load_max_over_mean`` averaged) and its
        chosen expert ids.

        A looped stack (``cfg.post_norms``) runs its one run
        ``cfg.loop_passes`` times on the same weights, each pass ending
        in the final norm: a ``lax.scan`` over the passes around the
        run's scan, so the program holds one layer body whatever the
        count.  It returns the LAST pass's normed state and ``aux =
        {"loop_hidden": [passes, b, s, d]}``, every pass's."""
        cfg = self.cfg
        keys = cfg.run_keys()
        if dropout_rng is not None and keys != ("layers",):
            raise NotImplementedError(
                "dropout in a mixed layer stack (TransformerConfig"
                ".layer_types ...) is not supported; set dropout=0")
        piped = self.mesh is not None and mesh_lib.mesh_axis_size(
            self.mesh, mesh_lib.PIPELINE_AXIS) > 1
        if cfg.post_norms and self.mesh is not None:
            for axis in (mesh_lib.SEQUENCE_AXIS, mesh_lib.TENSOR_AXIS):
                if mesh_lib.mesh_axis_size(self.mesh, axis) > 1:
                    raise NotImplementedError(
                        f"a looped stack (TransformerConfig.post_norms) "
                        f"over a sharded {axis!r} mesh axis is not "
                        "supported: its exit-weighted loss streams whole "
                        "rows against the whole head; use data / fsdp "
                        "axes")
        if piped:
            self._uniform_stack_only("pipeline parallelism")
            if cfg.num_experts > 1:
                raise NotImplementedError(
                    "MoE layers under pipeline parallelism are not supported "
                    "yet; use expert/tensor/data axes (set pipeline=1)")
            if dropout_rng is not None:
                raise NotImplementedError(
                    "dropout under pipeline parallelism is not supported "
                    "(per-stage rng would correlate masks); set dropout=0")
        carry, per_layer = (h, dropout_rng), []
        for key, (op, ff, _) in zip(keys, cfg.layer_runs()):
            # overlap-aware FSDP (Trainer(gather_mode="scan")): inside the
            # scan-gather train-step trace this hook all-gathers ONE
            # layer's bf16 shards at the top of the scan body — XLA
            # overlaps layer k+1's gather with layer k's matmuls, and the
            # gather's autodiff transpose reduce-scatters the layer's
            # gradient into its shard owner inside the backward.  It sits
            # INSIDE the remat body, so a policy that drops the gathered
            # weights re-gathers layer-by-layer in the backward instead
            # of holding the replicated tree live.  None outside that
            # trace (eval/decode/pipeline see plain params).
            gather = collectives_lib.current_layer_gather(key)

            def run(carry, layers, op=op, ff=ff, gather=gather):
                # positions derive from the (static) seq length; made
                # here so the pipeline stage body closes over no
                # outer-context tracers
                pos = jnp.arange(carry[0].shape[1])

                def block(carry, lp):
                    h_c, r = carry      # the dropout key rides the carry
                    if gather is not None:
                        lp = gather(lp)
                    sub = None
                    if r is not None:
                        r, sub = jax.random.split(r)
                    h_c, stats, _ = self._block(h_c, lp, pos, op, ff, sub)
                    return (h_c, r), stats

                if cfg.remat:
                    block = jax.checkpoint(block, policy=_remat_policy(
                        cfg.remat_policy))
                with jax.named_scope("gpt/layers"):
                    return jax.lax.scan(block, carry, layers)

            if piped:
                from ..parallel.pipeline import pipeline_apply
                carry = (pipeline_apply(
                    lambda lp, hm: run((hm, None), lp)[0][0], params[key],
                    carry[0], self.mesh, cfg.pipeline_microbatches), None)
                continue
            if cfg.post_norms:
                # the pass loop: a loop in the program.  The weights are
                # closed over, so the backward adds each one's gradient
                # over the passes, and what remat keeps of a layer (its
                # input, the named residuals) is kept per application
                def one_pass(h_c, _):
                    (h_c, _), _ = run((h_c, None), params[key])
                    h_c = self._rms_norm(h_c, params["ln_f"])
                    return h_c, h_c

                with jax.named_scope("gpt/loop"):
                    h, hs = jax.lax.scan(one_pass, carry[0], None,
                                         length=cfg.loop_passes)
                return h, {"loop_hidden": hs}
            carry, stats = run(carry, params[key])
            if stats:
                per_layer.append(stats)
        h = carry[0]
        if not per_layer:
            return h, {}
        stats = {k: jnp.concatenate([s[k] for s in per_layer])
                 for k in per_layer[0]}
        if "moe_aux_loss" in stats:
            return h, {"moe_aux_loss": jnp.sum(stats["moe_aux_loss"])}
        return h, {
            "moe_rows_routed": jnp.sum(stats["rows_routed"]),
            "moe_rows_computed": jnp.sum(stats["rows_computed"]),
            "moe_rounds": jnp.mean(stats["rounds"]),
            "moe_load_max_over_mean": jnp.mean(
                stats["load_max_over_mean"]),
            # [sparse layers, b, s, top_k]: for a comparison of the
            # routing itself (forward(return_aux=True)); no metric
            "moe_selected": stats["selected"]}

    @staticmethod
    def _tokens_of(batch):
        tokens = batch["input_ids"] if isinstance(batch, dict) else batch
        if isinstance(tokens, (tuple, list)):
            tokens = tokens[0]
        return tokens

    def _trunk(self, params, tokens, dropout_rng=None):
        """Embedding and the layer stack, up to the final norm:
        ``(hidden, what the expert layers report)``, the second a dict
        (``_run_stacks``), empty for a dense stack."""
        if dropout_rng is not None and self.cfg.dropout <= 0:
            dropout_rng = None
        h = self._embed_lookup(params, tokens)
        h = self._constrain(h, mesh_lib.BATCH_AXES,
                            mesh_lib.SEQUENCE_AXIS, None)
        return self._run_stacks(params, h, dropout_rng)

    def _head(self, params, h):
        """Final norm and LM head: f32 logits.  A looped stack's state
        comes normed out of its last pass."""
        if not self.cfg.post_norms:
            h = self._rms_norm(h, params["ln_f"])
        logits = jnp.einsum("bsd,dv->bsv", h,
                            self._unembed_w(params, self.compute_dtype))
        return logits.astype(jnp.float32)

    def forward(self, params, batch, return_aux: bool = False,
                dropout_rng=None):
        """``dropout_rng``: per-step PRNG key enabling dropout (train
        mode); None (eval/decode) makes the forward deterministic."""
        h, aux = self._trunk(params, self._tokens_of(batch), dropout_rng)
        logits = self._head(params, h)
        if return_aux and "loop_hidden" in aux:
            # the last pass's logits are the model's; for a comparison,
            # the exit distribution at every position, [passes, b, s]
            with jax.named_scope("gpt/loop_exit"):
                aux = {"loop_exit_p": self._exit_distribution(
                    params, aux["loop_hidden"])[0]}
        return (logits, aux) if return_aux else logits

    def _use_fused_loss(self) -> bool:
        """Batch (data/fsdp) sharding is handled inside the op via
        shard_map; seq/tensor/pipeline sharding of the hidden states or the
        unembedding is not, so those fall back to the materialized path."""
        if not self.cfg.fused_loss:
            return False
        if self.mesh is None:
            return True
        return all(
            mesh_lib.mesh_axis_size(self.mesh, ax) == 1
            for ax in (mesh_lib.SEQUENCE_AXIS, mesh_lib.TENSOR_AXIS,
                       mesh_lib.PIPELINE_AXIS))

    # ------------------------------------------------------------------ #
    # Steps                                                              #
    # ------------------------------------------------------------------ #
    def _lm_loss(self, params, batch, rng=None):
        """The head (final norm, LM head) is booked with the loss it
        feeds: scope ``gpt/loss`` here, none in ``forward``."""
        tokens = self._tokens_of(batch)
        h, aux = self._trunk(params, tokens, rng)
        if "loop_hidden" in aux:
            return self._loop_loss(params, aux["loop_hidden"], tokens)
        if self._use_fused_loss():
            from ..ops.losses import fused_linear_cross_entropy
            with jax.named_scope("gpt/loss"):
                h = self._rms_norm(h, params["ln_f"])
                d = h.shape[-1]
                rows = h[:, :-1].reshape(-1, d)
                targets = tokens[:, 1:].reshape(-1).astype(jnp.int32)
                loss, acc = fused_linear_cross_entropy(
                    rows, self._unembed_w(params, self.compute_dtype),
                    targets, self.cfg.loss_chunk_rows, mesh=self.mesh,
                    label_smoothing=self.cfg.label_smoothing,
                    z_loss=self.cfg.z_loss)
            return loss, acc, aux
        with jax.named_scope("gpt/loss"):
            logits = self._head(params, h)
            logits, targets = logits[:, :-1], tokens[:, 1:]
            eps, zl = self.cfg.label_smoothing, self.cfg.z_loss
            lse = jax.nn.logsumexp(logits, axis=-1)
            tgt_logit = jnp.take_along_axis(logits, targets[..., None],
                                            axis=-1)[..., 0]
            loss = lse - (1.0 - eps) * tgt_logit
            if eps:
                loss -= (eps / logits.shape[-1]) * jnp.sum(logits, -1)
            if zl:
                loss += zl * lse * lse
            loss = loss.mean()
            acc = jnp.mean(jnp.argmax(logits, -1) == targets)
        return loss, acc, aux

    def _exit_distribution(self, params, hs):
        """``(p, log p)``, each ``[passes, b, s]`` float32, of the normed
        states ``hs`` [passes, b, s, d]: the gate ``g_t = sigmoid(h_t .
        w + b)`` of every pass but the last, ``p_t = g_t prod_{j<t} (1 -
        g_j)`` and the survival ``p_T = prod_{j<T} (1 - g_j)``; made in
        logarithms, so that the entropy's ``p log p`` needs no guard.
        One pass: it takes everything."""
        if hs.shape[0] == 1:
            zero = jnp.zeros(hs.shape[:3], jnp.float32)
            return zero + 1.0, zero
        gate = params["exit_gate"]
        z = jnp.einsum("tbsd,do->tbso", hs[:-1],
                       self._wt(gate["w"], self.compute_dtype),
                       preferred_element_type=jnp.float32)[..., 0] \
            + gate["b"].astype(jnp.float32)[0]
        stayed = jnp.cumsum(jax.nn.log_sigmoid(-z), 0)  # sum_{j<=t}
        log_p = jnp.concatenate(
            [jax.nn.log_sigmoid(z) + jnp.pad(stayed[:-1],
                                             ((1, 0), (0, 0), (0, 0))),
             stayed[-1:]], 0)
        return jnp.exp(log_p), log_p

    def _loop_loss(self, params, hs, tokens):
        """A looped stack's training loss from its passes' normed states
        ``hs`` [passes, b, s, d]: per position with a target, ``sum_t
        p_t l_t - exit_beta H(p)`` with ``l_t`` pass t's cross-entropy
        and ``p`` the exit distribution, mean over positions.  The head
        runs over every pass's rows in ONE fused call (each row weighted
        by its ``p_t``, whose gradient is the row's own loss), booked to
        ``gpt/loss``; the gates, the entropy and the counters to
        ``gpt/loop_exit``.  Returns ``(loss, the last pass's accuracy,
        the logged scalars)``."""
        cfg = self.cfg
        passes, b, _, d = hs.shape
        hs, targets = hs[:, :, :-1], tokens[:, 1:].astype(jnp.int32)
        n = targets.size
        with jax.named_scope("gpt/loop_exit"):
            p, log_p = self._exit_distribution(params, hs)
            entropy = -jnp.sum(p * log_p, 0)                    # [b, s-1]
        w = self._unembed_w(params, self.compute_dtype)
        if self._use_fused_loss():
            from ..ops.losses import fused_linear_cross_entropy
            with jax.named_scope("gpt/loss"):
                # batch-major rows, so that a batch sharded over data /
                # fsdp axes keeps its rows
                weighted, row_loss, hit = fused_linear_cross_entropy(
                    jnp.moveaxis(hs, 0, 1).reshape(-1, d), w,
                    jnp.broadcast_to(targets[:, None], (b, passes)
                                     + targets.shape[1:]).reshape(-1),
                    cfg.loss_chunk_rows, mesh=self.mesh,
                    row_weights=jnp.moveaxis(p, 0, 1).reshape(-1))
            row_loss, hit = (jnp.moveaxis(x.reshape(b, passes, -1), 1, 0)
                             for x in (row_loss, hit))
        else:
            with jax.named_scope("gpt/loss"):
                logits = jnp.einsum("tbsd,dv->tbsv", hs, w
                                    ).astype(jnp.float32)
                row_loss = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
                    logits, jnp.broadcast_to(targets, logits.shape[:3])[
                        ..., None], -1)[..., 0]
                hit = jnp.argmax(logits, -1) == targets
            with jax.named_scope("gpt/loop_exit"):
                weighted = jnp.sum(p * row_loss)
        with jax.named_scope("gpt/loop_exit"):
            loss = (weighted - cfg.exit_beta * jnp.sum(entropy)) / n
            row_loss, p = (jax.lax.stop_gradient(x) for x in (row_loss, p))
            aux = {f"loop_loss_pass_{t + 1}": jnp.mean(row_loss[t])
                   for t in range(passes)}
            aux["loop_exit_mean_pass"] = jnp.sum(
                p * jnp.arange(1, passes + 1, dtype=jnp.float32)[
                    :, None, None]) / n
            # as a share of its most, ln(passes); one pass has none
            aux["loop_exit_entropy"] = jax.lax.stop_gradient(
                jnp.sum(entropy)) / (n * float(np.log(max(passes, 2))))
        return loss, jnp.mean(hit[-1]), aux

    def training_step(self, params, batch, rng):
        loss, acc, aux = self._lm_loss(params, batch, rng=rng)
        # the expert layers' scalars ride the logged metrics, through the
        # scanned epoch too; only the capacity path has an auxiliary
        # loss (the dropless router is steered by its bias)
        metrics = {"loss": loss, "accuracy": acc,
                   **{k: v for k, v in aux.items() if v.ndim == 0}}
        if "moe_aux_loss" in aux:
            loss = loss + self.cfg.moe_aux_weight * aux["moe_aux_loss"]
        return loss, metrics

    def validation_step(self, params, batch):
        loss, acc, _ = self._lm_loss(params, batch)
        return {"val_loss": loss, "val_accuracy": acc,
                "val_perplexity": jnp.exp(loss)}

    def predict_step(self, params, batch):
        return self.forward(params, batch)

    def configure_optimizers(self):
        runs = self.cfg.layer_runs()
        # leaves that are no matrices and take no weight decay, by the
        # families' conventions: the state-space layers' decay rates,
        # step biases and skips; a looped stack's norm scales and its
        # gate's bias
        spared = ()
        if any(op == "mamba" for op, _, _ in runs):
            spared = ("a_log", "dt_bias", "d_skip")
        elif self.cfg.post_norms:
            spared = ("ln1", "ln1_post", "ln2", "ln2_post", "ln_f", "b")
        if spared:
            def decayed(params):
                return jax.tree_util.tree_map_with_path(
                    lambda path, _: getattr(path[-1], "key", None)
                    not in spared, params)

            tx = optax.adamw(self.lr, weight_decay=0.01, mask=decayed)
        else:
            tx = optax.adamw(self.lr, weight_decay=0.01)
        if not any(ff in ("sparse", "latent") for _, ff, _ in runs):
            return tx

        # the selection bias is a buffer: no update (AdamW's decay would
        # shrink it on a zero gradient) and no optimizer state
        def labels(params):
            return jax.tree_util.tree_map_with_path(
                lambda path, _: "buffer" if getattr(
                    path[-1], "key", None) == "expert_bias" else "train",
                params)

        return optax.multi_transform(
            {"train": tx, "buffer": optax.set_to_zero()}, labels)

    # ------------------------------------------------------------------ #
    # Weight-only int8 quantization (inference)                          #
    # ------------------------------------------------------------------ #
    # Decode is HBM-bandwidth-bound: every generated token re-reads every
    # weight.  Symmetric per-out-channel int8 halves the bytes per read vs
    # bf16 -- but only if HBM never sees a widened copy: the decode
    # matmuls stream int8 through the Pallas kernels in ops/quant.py and
    # widen in VMEM/registers.  (Letting XLA dequantize-then-dot instead
    # materializes the bf16 dequant in HBM and erases the win: measured
    # 1.03x, round 3.)  Quantized trees are for generate()/predict paths
    # only (training keeps full precision).

    @staticmethod
    def quantize_weights(params):
        """Return a params tree where matmul weights become
        {"q8": int8, "scale": f32} with per-out-channel symmetric scales.

        Structure-aware: leaves under ``layers`` are layer-STACKED
        ([L, ...]), so their scales keep the leading layer axis (the layer
        scan unstacks q8 and scale together) and only ndim>=3 leaves
        quantize (the [L, d] norm scales stay dense).  Top-level
        embed/unembed quantize at ndim>=2; 1D norms stay dense.
        """
        def quant(arr, keep_first: bool):
            arr = jnp.asarray(arr)
            min_ndim = 3 if keep_first else 2
            if arr.ndim < min_ndim or \
                    not jnp.issubdtype(arr.dtype, jnp.floating):
                return arr
            axes = tuple(range(1 if keep_first else 0, arr.ndim - 1))
            amax = jnp.max(jnp.abs(arr.astype(jnp.float32)),
                           axis=axes, keepdims=True)
            scale = jnp.where(amax > 0, amax / 127.0, 1.0)
            q = jnp.clip(jnp.round(arr.astype(jnp.float32) / scale),
                         -127, 127).astype(jnp.int8)
            return {"q8": q, "scale": scale.astype(jnp.float32)}

        if "ln1_post" in params.get("layers", ()):
            raise NotImplementedError(
                "GPT.quantize_weights walks params['layers'] as one "
                "uniform stack run once; a looped stack (TransformerConfig"
                ".post_norms / loop_passes / exit_gate) trains only")
        if "layers" not in params:
            raise NotImplementedError(
                "GPT.quantize_weights walks params['layers'] as one "
                "uniform stack; a mixed layer stack (TransformerConfig"
                ".layer_types / hybrid_pattern ...: params['layers_<i>']) "
                "trains only")
        out = {k: v for k, v in params.items()}
        out["layers"] = jax.tree.map(lambda a: quant(a, True),
                                     params["layers"])
        out["embed"] = quant(params["embed"], False)
        if "unembed" in params:
            out["unembed"] = quant(params["unembed"], False)
        return out

    @staticmethod
    def _is_q8(w) -> bool:
        return isinstance(w, dict) and "q8" in w

    def _wt(self, w, dt):
        """Weight fetch: dequantize an int8 leaf or cast a dense one."""
        if self._is_q8(w):
            return (w["q8"].astype(jnp.float32) * w["scale"]).astype(dt)
        return w.astype(dt)

    # -- int8 kernel dispatch (decode matmuls) ------------------------- #
    # XLA's dequantize-then-dot on scanned weight stacks materializes the
    # bf16 dequant in HBM, erasing the bandwidth win int8 storage exists
    # for (measured: 1.03x).  The decode matmuls therefore route q8
    # leaves through ops/quant.py Pallas kernels that stream int8 into
    # VMEM and widen in-registers.  ``_force_q8_kernel``: None = auto
    # (kernels on TPU), "interpret" = interpreter-mode kernels (CPU
    # tests), False = always the XLA dequant fallback.
    _force_q8_kernel = None

    def _q8_kernel_mode(self):
        forced = self._force_q8_kernel
        if forced == "interpret":
            return "interpret"
        if forced is None and self.mesh is not None and (
                mesh_lib.mesh_axis_size(self.mesh,
                                        mesh_lib.TENSOR_AXIS) > 1
                or mesh_lib.mesh_axis_size(self.mesh,
                                           mesh_lib.SEQUENCE_AXIS) > 1):
            # pallas_call carries no GSPMD sharding rule: on a tensor- or
            # sequence-sharded mesh the q8 weights would be all-gathered
            # or fail to partition, erasing the bandwidth win the kernel
            # exists for -- keep the shardable XLA dequant path instead
            # (mirrors the _embed_lookup t_size gate above)
            return None
        if forced is None and jax.default_backend() == "tpu" \
                and not knobs.get_flag("RLA_TPU_DISABLE_Q8_KERNEL"):
            return "compiled"
        return None

    def _q8_mm(self, rows, q8_2d, scale_vec, dt):
        """Shared kernel dispatch: ``rows [M,K] @ q8_2d [K,N]`` with
        per-out-column ``scale_vec``, or (``scale_vec=None``)
        ``rows [M,K] @ q8_2d[N,K]^T`` scale-free.  Returns None when the
        kernel isn't engaged (wrong backend, unsupported shapes) -- the
        caller falls back to the XLA dequant path."""
        mode = self._q8_kernel_mode()
        if mode is None:
            return None
        from ..ops import quant
        interp = mode == "interpret"
        if scale_vec is None:
            n, k = q8_2d.shape
            if not quant.supported(rows.shape[0], k, n):
                self._q8_decline(rows.shape[0], k, n)
                return None
            return quant.int8_matmul_nt(rows.astype(dt), q8_2d,
                                        interpret=interp)
        k, n = q8_2d.shape
        if not quant.supported(rows.shape[0], k, n):
            self._q8_decline(rows.shape[0], k, n)
            return None
        return quant.int8_matmul(rows.astype(dt), q8_2d, scale_vec,
                                 interpret=interp)

    _q8_declined_shapes: set = set()

    @classmethod
    def _q8_decline(cls, m, k, n):
        """Warn once per shape when a q8 matmul falls back to XLA dequant
        (measured ~1.03x, i.e. the int8 storage buys ~nothing there) --
        a silently declined shape would look identical to a working
        kernel in user-observed throughput."""
        if (m, k, n) not in cls._q8_declined_shapes:
            cls._q8_declined_shapes.add((m, k, n))
            log.warning(
                "int8 kernel declined shape M=%d K=%d N=%d (needs M<=1024"
                " and block-divisible K/N); using XLA dequant fallback "
                "for this matmul -- expect bf16-class bandwidth", m, k, n)

    def _qkv_proj_decode(self, x, w, dt):
        """[b,n,d] @ w[d,h,k] -> [b,h,n,k], q8-kernel aware."""
        if self._is_q8(w):
            q8 = w["q8"]
            d, hh, kk = q8.shape
            b, n, _ = x.shape
            sv = jnp.broadcast_to(w["scale"], (1, hh, kk)).reshape(-1)
            out = self._q8_mm(x.reshape(b * n, d),
                              q8.reshape(d, hh * kk), sv, dt)
            if out is not None:
                return out.reshape(b, n, hh, kk).transpose(0, 2, 1, 3)
        return jnp.einsum("bsd,dhk->bhsk", x, self._wt(w, dt))

    def _attn_out_proj_decode(self, attn, w, dt):
        """[b,h,n,k] @ w[h,k,d] -> [b,n,d], q8-kernel aware."""
        if self._is_q8(w):
            q8 = w["q8"]
            hh, kk, d = q8.shape
            b, _, n, _ = attn.shape
            rows = attn.transpose(0, 2, 1, 3).reshape(b * n, hh * kk)
            out = self._q8_mm(rows, q8.reshape(hh * kk, d),
                              w["scale"].reshape(-1), dt)
            if out is not None:
                return out.reshape(b, n, d)
        return jnp.einsum("bhsk,hkd->bsd", attn, self._wt(w, dt))

    def _mlp_proj_decode(self, x, w, dt):
        """[b,n,din] @ w[din,dout] -> [b,n,dout], q8-kernel aware."""
        if self._is_q8(w):
            q8 = w["q8"]
            b, n, _ = x.shape
            out = self._q8_mm(x.reshape(b * n, q8.shape[0]), q8,
                              w["scale"].reshape(-1), dt)
            if out is not None:
                return out.reshape(b, n, q8.shape[1])
        return jnp.einsum("bsd,df->bsf", x, self._wt(w, dt))

    def _unembed_matmul(self, h2, params, dt):
        """[M,d] @ unembed [d,V] -> [M,V] f32, q8-kernel aware.

        Tied embeddings store q8 as [V,d] with scales along d (the
        CONTRACTION dim), so the scales fold into the activation and the
        transposed-weight kernel runs scale-free."""
        if self.cfg.tie_embeddings and self._is_q8(params["embed"]):
            sv = params["embed"]["scale"].reshape(-1)       # [d]
            xs = h2.astype(jnp.float32) * sv
            out = self._q8_mm(xs, params["embed"]["q8"], None, dt)
            if out is not None:
                return out.astype(jnp.float32)
        if not self.cfg.tie_embeddings and self._is_q8(params.get("unembed")):
            out = self._q8_mm(h2, params["unembed"]["q8"],
                              params["unembed"]["scale"].reshape(-1), dt)
            if out is not None:
                return out.astype(jnp.float32)
        return (h2.astype(dt) @ self._unembed_w(params, dt)
                ).astype(jnp.float32)

    def _dequant_q8_leaves(self, tree, dt):
        """Dequantize ONLY int8 leaves in a subtree; dense leaves pass
        through untouched so downstream code keeps its own dtype policy
        (moe_mlp deliberately routes in f32 master precision)."""
        return jax.tree.map(
            lambda w: self._wt(w, dt) if self._is_q8(w) else w, tree,
            is_leaf=self._is_q8)

    def _unembed_w(self, params, dt) -> jax.Array:
        """Dequant-aware unembedding matrix [d, V]."""
        if self.cfg.tie_embeddings:
            return self._wt(params["embed"], dt).T
        return self._wt(params["unembed"], dt)

    # ------------------------------------------------------------------ #
    # Autoregressive generation (KV cache)                               #
    # ------------------------------------------------------------------ #
    # TPU-first decode: everything is static-shaped — the cache is
    # allocated at [L, B, H, total_len, D] up front, the decode loop is a
    # single lax.scan (one trace, one compile regardless of token count),
    # and per-step cache writes are dynamic_update_slice at a traced
    # position.  No reference analog (predict there is plain model(x),
    # reference: ray_lightning/tests/utils.py:137-152).

    def _prefill(self, params, tokens, cache_len, last_index=None):
        """Run the prompt once; returns (last-position hidden [B,d],
        cache dict with k/v [L,B,H,cache_len,D]).

        ``cache_len < prompt_len`` (the sliding-window rolling cache) keeps
        only the last ``cache_len`` positions, scattered to their ring
        slots ``p % cache_len``.

        ``last_index`` ([B] or scalar int32): return the hidden state at
        that position instead of the final one — the serve engine right-
        pads prompts into fixed length buckets (bounded compile count) and
        needs the hidden at the TRUE last prompt token.  Pad positions
        write garbage k/v beyond ``last_index``, which is safe for linear
        decode: slot p is rewritten by the decode step at position p
        before any mask ever lets it be attended."""
        ff = self._uniform_stack_only(
            "GPT._prefill (generate, generate_beam, ServeEngine, "
            "speculative_generate)")
        h = self._embed_lookup(params, tokens)
        pos = jnp.arange(tokens.shape[1])

        def block(carry, lp):
            h_new, _, kv = self._block(carry, lp, pos, "attn", ff)
            return h_new, kv

        h, (ks, vs) = jax.lax.scan(block, h, params["layers"])
        s0 = tokens.shape[1]
        if s0 <= cache_len:
            pad = cache_len - s0
            cache = {
                "k": jnp.pad(ks, ((0, 0),) * 3 + ((0, pad), (0, 0))),
                "v": jnp.pad(vs, ((0, 0),) * 3 + ((0, pad), (0, 0))),
            }
        else:
            slots = jnp.arange(s0 - cache_len, s0) % cache_len
            zk = jnp.zeros(ks.shape[:3] + (cache_len, ks.shape[-1]),
                           ks.dtype)
            cache = {
                "k": zk.at[:, :, :, slots, :].set(ks[:, :, :, -cache_len:]),
                "v": zk.at[:, :, :, slots, :].set(vs[:, :, :, -cache_len:]),
            }
        h = self._rms_norm(h, params["ln_f"])
        if last_index is None:
            return h[:, -1], cache
        idx = jnp.asarray(last_index, jnp.int32)
        return h[jnp.arange(h.shape[0]), idx], cache

    def _decode_attn_block(self, h, lp, ck, cv, pos0, ring: bool,
                           row_positions=None):
        """One layer, n cached-decode tokens at positions pos0..pos0+n-1.
        h: [B,n,d]; ck/cv: [B,H,W,D].

        ``ring=True`` (single-token path, n==1): the cache is a ring
        buffer over slots ``p % W`` with wrap-around validity — W == max
        length degenerates to the plain linear cache.  ``ring=False``
        (speculative chunk scoring): linear slots, causal within the
        chunk and over the prefix.  ``row_positions`` ([B] int32, n==1,
        ring must be False): continuous-batching serve step — every batch
        row decodes at its OWN position into linear slots.  One
        implementation so the three decode paths cannot drift apart
        (speculative and serve exactness both depend on it).
        """
        cfg = self.cfg
        dt = self.compute_dtype
        a = lp["attn"]
        n = h.shape[1]
        x = self._rms_norm(h, lp["ln1"])
        q = self._qkv_proj_decode(x, a["wq"], dt)
        k = self._qkv_proj_decode(x, a["wk"], dt)
        v = self._qkv_proj_decode(x, a["wv"], dt)
        W = ck.shape[2]
        if row_positions is not None:
            q = _rope(q, row_positions[:, None], cfg.rope_theta)
            k = _rope(k, row_positions[:, None], cfg.rope_theta)

            # per-row slot write: row b's k/v land at ITS position (a
            # batched scatter; joining/retiring is never a recompile)
            def upd(c, kk, p):
                return jax.lax.dynamic_update_slice(c, kk, (0, p, 0))

            ck = jax.vmap(upd)(ck, k.astype(ck.dtype), row_positions)
            cv = jax.vmap(upd)(cv, v.astype(cv.dtype), row_positions)
        else:
            positions = pos0 + jnp.arange(n)
            q = _rope(q, positions, cfg.rope_theta)
            k = _rope(k, positions, cfg.rope_theta)
            slot = jax.lax.rem(pos0, W) if ring else pos0
            ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype),
                                              (0, 0, slot, 0))
            cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype),
                                              (0, 0, slot, 0))
        # grouped query attention over the (unrepeated) KV cache; groups=1
        # is plain MHA
        b = q.shape[0]
        kvh = ck.shape[1]
        groups = cfg.n_heads // kvh
        qg = q.astype(jnp.float32).reshape(b, kvh, groups, n, cfg.head_dim)
        s = jnp.einsum("bkgqd,bktd->bkgqt", qg, ck.astype(jnp.float32)
                       ) * cfg.head_dim ** -0.5
        t = jnp.arange(W)[None, None, None, None]
        if row_positions is not None:
            rows = row_positions[:, None, None, None, None]
        else:
            rows = positions[None, None, None, :, None]
        if ring:
            # once a row's position >= W every slot holds a position in
            # (pos-W, pos] — exactly the attention span (the cache is
            # sized to min(total, sliding_window)); before that, only
            # slots <= pos are written
            mask = (t <= rows) | (rows >= W)
        else:
            mask = t <= rows
        s = jnp.where(mask, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        attn = jnp.einsum("bkgqt,bktd->bkgqd", p, cv.astype(jnp.float32))
        attn = attn.reshape(b, cfg.n_heads, n, cfg.head_dim).astype(dt)
        h = h + self._attn_out_proj_decode(attn, a["wo"], dt)
        ff = self._uniform_stack_only("GPT._decode_attn_block")
        y, _ = self._feed_forward(self._rms_norm(h, lp["ln2"]), lp["mlp"],
                                  ff, self._mlp_proj_decode)
        return h + y, ck, cv

    def _decode_chunk(self, params, cache, tokens, pos0):
        """Score a chunk of n tokens against the cache in one pass.
        tokens: [B,n] fed at positions pos0..pos0+n-1.  Returns (logits
        [B,n,V] f32, updated cache) — logits[:, i] predicts position
        pos0+i+1.  Requires the linear (non-rolling) cache."""
        self._uniform_stack_only("GPT._decode_chunk (speculative scoring)")
        dt = self.compute_dtype
        h = self._embed_lookup(params, tokens)

        def layer(carry, xs):
            lp, ck, cv = xs
            h_out, ck2, cv2 = self._decode_attn_block(carry, lp, ck, cv,
                                                      pos0, ring=False)
            return h_out, (ck2, cv2)

        h, (cks, cvs) = jax.lax.scan(
            layer, h, (params["layers"], cache["k"], cache["v"]))
        h = self._rms_norm(h, params["ln_f"])
        b, n, d = h.shape
        logits = self._unembed_matmul(h.reshape(b * n, d), params, dt
                                      ).reshape(b, n, -1)
        return logits, {"k": cks, "v": cvs}

    def _decode_token(self, params, cache, token, pos):
        """Full-depth single-token step.  token: [B] int32.  Returns
        (logits [B,V] f32, updated cache)."""
        self._uniform_stack_only("GPT._decode_token (generate)")
        dt = self.compute_dtype
        h = self._embed_lookup(params, token)[:, None]  # [B,1,d]

        def layer(carry, xs):
            h_in = carry
            lp, ck, cv = xs
            h_out, ck2, cv2 = self._decode_attn_block(h_in, lp, ck, cv,
                                                      pos, ring=True)
            return h_out, (ck2, cv2)

        h, (cks, cvs) = jax.lax.scan(
            layer, h, (params["layers"], cache["k"], cache["v"]))
        h = self._rms_norm(h, params["ln_f"])
        logits = self._unembed_matmul(h[:, 0], params, dt)
        return logits, {"k": cks, "v": cvs}

    # ------------------------------------------------------------------ #
    # Continuous-batching decode (serve engine primitives)               #
    # ------------------------------------------------------------------ #
    # The cache is allocated [L, B, H, total_len, D] up front, so joining
    # a sequence mid-flight is a slot scatter and retiring one is a
    # host-side slot free -- never a reshape, never a recompile.  Rows
    # advance at PER-ROW positions (each slot is its own request).

    def decode_cache_alloc(self, batch: int, total_len: int):
        """Zeroed multi-slot KV cache [L, batch, kv_heads, total_len,
        head_dim] in the compute dtype — the serve engine's fixed decode
        slots."""
        self._uniform_stack_only("GPT.decode_cache_alloc")
        cfg = self.cfg
        shape = (cfg.n_layers, batch, cfg.kv_heads, total_len,
                 cfg.head_dim)
        return {"k": jnp.zeros(shape, self.compute_dtype),
                "v": jnp.zeros(shape, self.compute_dtype)}

    @staticmethod
    def cache_join(cache, row_cache, slot):
        """Scatter a single-request cache [L,1,H,P,D] into row ``slot`` of
        a multi-slot cache [L,B,H,W,D] (P <= W).  ``slot`` may be traced:
        a join is one dynamic_update_slice per k/v, so admitting a request
        never retraces.  Stale garbage past P in the target row is safe —
        linear decode rewrites slot p at position p before the causal mask
        ever exposes it."""

        def put(big, row):
            return jax.lax.dynamic_update_slice(
                big, row.astype(big.dtype), (0, slot, 0, 0, 0))

        return {"k": put(cache["k"], row_cache["k"]),
                "v": put(cache["v"], row_cache["v"])}

    def decode_step_rows(self, params, cache, tokens, positions):
        """Full-depth single-token step for EVERY cache row at once, each
        row at its own position (the continuous-batching primitive).
        tokens: [B] int32 (the token each row feeds); positions: [B]
        int32 (that token's sequence position).  Linear slots only — no
        sliding-window ring.  Rows the caller considers inactive may feed
        any token at any in-range position: their slot is fully rewritten
        by the next join before it is attended.  Returns (logits [B,V]
        f32, updated cache)."""
        self._uniform_stack_only("GPT.decode_step_rows")
        dt = self.compute_dtype
        positions = jnp.asarray(positions, jnp.int32)
        h = self._embed_lookup(params, tokens)[:, None]  # [B,1,d]

        def layer(carry, xs):
            lp, ck, cv = xs
            h_out, ck2, cv2 = self._decode_attn_block(
                carry, lp, ck, cv, 0, ring=False,
                row_positions=positions)
            return h_out, (ck2, cv2)

        h, (cks, cvs) = jax.lax.scan(
            layer, h, (params["layers"], cache["k"], cache["v"]))
        h = self._rms_norm(h, params["ln_f"])
        logits = self._unembed_matmul(h[:, 0], params, dt)
        return logits, {"k": cks, "v": cvs}

    # ------------------------------------------------------------------ #
    # Block-paged decode (serve engine's paged KV cache)                 #
    # ------------------------------------------------------------------ #
    # Instead of one dense [L, B, H, W, D] cache, the pool is a fixed set
    # of [L, n_blocks, H, block_len, D] KV blocks plus a per-row int32
    # block table mapping logical position p to physical block
    # table[p // block_len], offset p % block_len.  Tables are TRACED
    # operands: join/retire/grow is a host-side table write, never a
    # recompile — the PR 2 invariant, kept through the indirection.
    # Attention reads the pool through a gather over the table; masked
    # positions contribute exactly-zero softmax terms, so the arithmetic
    # per attended position is identical to the dense decode paths
    # (token-exactness vs generate() rides on that, test-asserted).

    def paged_cache_alloc(self, n_blocks: int, block_len: int):
        """Zeroed block pool [L, n_blocks, kv_heads, block_len, head_dim]
        in the compute dtype — the paged serve engine's fixed HBM
        footprint (block 0 is conventionally the engine's garbage block:
        inactive decode rows scatter there, it is never table-mapped)."""
        self._uniform_stack_only("GPT.paged_cache_alloc")
        cfg = self.cfg
        shape = (cfg.n_layers, n_blocks, cfg.kv_heads, block_len,
                 cfg.head_dim)
        return {"k": jnp.zeros(shape, self.compute_dtype),
                "v": jnp.zeros(shape, self.compute_dtype)}

    @staticmethod
    def paged_cache_join(pool, row_cache, blocks):
        """Scatter a single-request linear cache [L,1,H,P,D] into the
        physical ``blocks`` ([P // block_len] int32, traced) of a paged
        pool — the block-table analog of ``cache_join``.  P must be a
        multiple of the pool's block_len (the engine buckets prompts to
        block multiples)."""

        def put(pool_a, row):
            L, _, H, P, D = row.shape
            bl = pool_a.shape[3]
            r = row[:, 0].reshape(L, H, P // bl, bl, D
                                  ).transpose(0, 2, 1, 3, 4)
            return pool_a.at[:, blocks].set(r.astype(pool_a.dtype))

        return {"k": put(pool["k"], row_cache["k"]),
                "v": put(pool["v"], row_cache["v"])}

    @staticmethod
    def paged_blocks_gather(pool, blocks):
        """Read physical ``blocks`` ([W] int32, traced) out of a paged
        pool: ``(k, v)`` each [L, W, kv_heads, block_len, head_dim].
        The serve tier's KV-handoff EXPORT: a prefill-lane engine
        gathers a request's blocks wave-by-wave for the object-store
        copy to a decode replica.  Callers pad ``blocks`` to a fixed
        wave width with the garbage block 0 so one program covers every
        wave (a handoff must never recompile)."""
        return pool["k"][:, blocks], pool["v"][:, blocks]

    @staticmethod
    def paged_blocks_scatter(pool, blocks, k, v):
        """Write block payloads ``k``/``v`` ([L, W, H, block_len, D])
        into physical ``blocks`` ([W] int32, traced) of a paged pool —
        the KV-handoff IMPORT (the block-id remap made real: same
        bytes, new physical ids).  Pad entries target the garbage block
        0, where last-write-wins garbage is harmless by the same
        argument as inactive decode rows."""
        return {"k": pool["k"].at[:, blocks].set(k.astype(
                    pool["k"].dtype)),
                "v": pool["v"].at[:, blocks].set(v.astype(
                    pool["v"].dtype))}

    def _paged_attn_block(self, h, lp, pk, pv, tables, positions):
        """One layer over the block-paged pool.  h: [B, n, d]; pk/pv:
        [n_blocks, H, block_len, D] (ONE layer's pool); tables: [B, M]
        int32 physical block ids; positions: [B, n] int32 query
        positions.  Each query's k/v is scattered to its table-mapped
        slot first, then every row gathers its table's blocks into a
        [H, M*block_len, D] view and attends with mask t <= position —
        one implementation for both paged programs (batched step n == 1,
        chunk scoring B == 1) so they cannot drift apart.  Unmapped table
        entries (sentinel 0) only cover positions t > position, which the
        mask closes; the garbage block's values are finite (pool-zeroed,
        then finite writes), so masked lanes stay exactly zero."""
        cfg = self.cfg
        dt = self.compute_dtype
        a = lp["attn"]
        b, n, _ = h.shape
        bl = pk.shape[2]
        x = self._rms_norm(h, lp["ln1"])
        q = self._qkv_proj_decode(x, a["wq"], dt)        # [B, H, n, D]
        k = self._qkv_proj_decode(x, a["wk"], dt)
        v = self._qkv_proj_decode(x, a["wv"], dt)
        q = _rope(q, positions, cfg.rope_theta)
        k = _rope(k, positions, cfg.rope_theta)
        # per-query scatter: query (b, i) writes its k/v at physical
        # block tables[b, pos // bl], offset pos % bl (a traced scatter;
        # distinct live rows own distinct blocks, so writes never
        # collide — inactive rows all target the garbage block 0, where
        # last-write-wins garbage is harmless)
        phys = jnp.take_along_axis(tables, positions // bl, axis=1)
        off = positions % bl                             # [B, n]
        pk = pk.at[phys, :, off, :].set(
            k.transpose(0, 2, 1, 3).astype(pk.dtype))
        pv = pv.at[phys, :, off, :].set(
            v.transpose(0, 2, 1, 3).astype(pv.dtype))
        kvh = pk.shape[1]
        M = tables.shape[1]
        W = M * bl
        kb = pk[tables].transpose(0, 2, 1, 3, 4).reshape(b, kvh, W, -1)
        vb = pv[tables].transpose(0, 2, 1, 3, 4).reshape(b, kvh, W, -1)
        groups = cfg.n_heads // kvh
        qg = q.astype(jnp.float32).reshape(b, kvh, groups, n,
                                           cfg.head_dim)
        s = jnp.einsum("bkgqd,bktd->bkgqt", qg, kb.astype(jnp.float32)
                       ) * cfg.head_dim ** -0.5
        t = jnp.arange(W)[None, None, None, None, :]
        rows = positions[:, None, None, :, None]
        s = jnp.where(t <= rows, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        attn = jnp.einsum("bkgqt,bktd->bkgqd", p, vb.astype(jnp.float32))
        attn = attn.reshape(b, cfg.n_heads, n, cfg.head_dim).astype(dt)
        h = h + self._attn_out_proj_decode(attn, a["wo"], dt)
        ff = self._uniform_stack_only("GPT._paged_attn_block")
        y, _ = self._feed_forward(self._rms_norm(h, lp["ln2"]), lp["mlp"],
                                  ff, self._mlp_proj_decode)
        return h + y, pk, pv

    def decode_step_rows_paged(self, params, pool, tables, tokens,
                               positions):
        """``decode_step_rows`` through the block-table indirection: one
        full-depth single-token step for every row at once, each row
        reading/writing the pool via its own table row.  tables: [B, M]
        int32 (traced — join/retire/grow never recompiles); tokens /
        positions: [B] int32.  Rows the caller considers inactive must
        carry an all-zero table (the garbage block) and any in-range
        position.  Returns (logits [B, V] f32, updated pool)."""
        self._uniform_stack_only("GPT.decode_step_rows_paged")
        dt = self.compute_dtype
        positions = jnp.asarray(positions, jnp.int32)
        tables = jnp.asarray(tables, jnp.int32)
        h = self._embed_lookup(params, tokens)[:, None]  # [B, 1, d]

        def layer(carry, xs):
            lp, pk, pv = xs
            h_out, pk2, pv2 = self._paged_attn_block(
                carry, lp, pk, pv, tables, positions[:, None])
            return h_out, (pk2, pv2)

        h, (pks, pvs) = jax.lax.scan(
            layer, h, (params["layers"], pool["k"], pool["v"]))
        h = self._rms_norm(h, params["ln_f"])
        logits = self._unembed_matmul(h[:, 0], params, dt)
        return logits, {"k": pks, "v": pvs}

    def decode_chunk_paged(self, params, pool, table, tokens, pos0,
                           last_index=None):
        """Single-row chunk scoring/prefill through the paged pool: n
        tokens fed at positions pos0..pos0+n-1, attending to whatever the
        row's ``table`` ([M] int32) already maps (a shared prefix, prior
        rounds) plus causally to themselves; their k/v land in the
        table-mapped blocks.  This is both the paged prefill (the suffix
        after any shared-prefix blocks, with ``last_index`` selecting the
        true last prompt token's logits [1, V]) and the speculative chunk
        scorer (``last_index=None`` → logits [1, n, V]; logits[:, i]
        predicts position pos0+i+1).  Returns (logits, pool)."""
        self._uniform_stack_only("GPT.decode_chunk_paged")
        dt = self.compute_dtype
        n = tokens.shape[1]
        pos = (jnp.asarray(pos0, jnp.int32)
               + jnp.arange(n, dtype=jnp.int32))[None]  # [1, n]
        table = jnp.asarray(table, jnp.int32)
        h = self._embed_lookup(params, tokens)

        def layer(carry, xs):
            lp, pk, pv = xs
            h_out, pk2, pv2 = self._paged_attn_block(
                carry, lp, pk, pv, table[None], pos)
            return h_out, (pk2, pv2)

        h, (pks, pvs) = jax.lax.scan(
            layer, h, (params["layers"], pool["k"], pool["v"]))
        h = self._rms_norm(h, params["ln_f"])
        pool = {"k": pks, "v": pvs}
        if last_index is None:
            b, nn, d = h.shape
            logits = self._unembed_matmul(h.reshape(b * nn, d), params,
                                          dt).reshape(b, nn, -1)
            return logits, pool
        idx = jnp.asarray(last_index, jnp.int32)
        logits = self._unembed_matmul(
            h[jnp.arange(h.shape[0]), idx], params, dt)
        return logits, pool

    @staticmethod
    def _sample(logits, temperature, top_k, top_p, rng):
        if temperature == 0.0:
            return jnp.argmax(logits, -1).astype(jnp.int32)
        logits = logits / temperature
        if top_k:
            kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
            logits = jnp.where(logits < kth, -1e30, logits)
        if top_p < 1.0:
            # nucleus: drop the tail whose cumulative prob exceeds top_p.
            # sort descending once; a token survives if the cumulative mass
            # BEFORE it is < top_p (the head token always survives — the
            # max(..., 0) keeps it even for top_p=0, which is thus greedy)
            sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
            probs = jax.nn.softmax(sorted_logits, axis=-1)
            cum = jnp.cumsum(probs, axis=-1) - probs
            cutoff_idx = jnp.maximum(
                jnp.sum((cum < top_p).astype(jnp.int32), -1) - 1, 0)
            cutoff = jnp.take_along_axis(sorted_logits,
                                         cutoff_idx[:, None], axis=-1)
            logits = jnp.where(logits < cutoff, -1e30, logits)
        return jax.random.categorical(rng, logits).astype(jnp.int32)

    def generate_beam(self, params, prompt, max_new_tokens: int,
                      beam_size: int = 4) -> jax.Array:
        """Beam-search decode.  prompt: [1, S0]; returns the sequence
        [1, S0 + max_new_tokens] with the highest total log-probability.
        All beams decode the full length (no EOS termination), so no
        length normalization applies.

        Beams ride the batch dimension of the shared KV cache; each step
        re-gathers cache rows by surviving parents — a [beam] gather, not
        a copy of history.  Static shapes throughout (single scan).
        """
        prompt = jnp.asarray(prompt, jnp.int32)
        if prompt.shape[0] != 1:
            raise ValueError("beam search expects batch size 1")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        params = jax.tree.map(jnp.asarray, params)
        b, s0 = prompt.shape
        total = s0 + max_new_tokens
        if total > self.cfg.max_seq_len:
            raise ValueError(f"prompt + new tokens ({total}) exceeds "
                             f"max_seq_len ({self.cfg.max_seq_len})")
        window = self.cfg.sliding_window
        cache_len = total if window is None else min(total, window)
        mesh_saved, self.mesh = self.mesh, None
        try:
            h_last, cache = self._prefill(params, prompt, cache_len)
            dt = self.compute_dtype
            logp0 = jax.nn.log_softmax(
                self._unembed_matmul(h_last, params, dt))
            # seed beams from the top-k first tokens (pad with -inf beams
            # when beam_size exceeds the vocab; they can never win)
            k0 = min(beam_size, logp0.shape[-1])
            scores, tok0 = jax.lax.top_k(logp0[0], k0)
            if k0 < beam_size:
                scores = jnp.concatenate(
                    [scores, jnp.full((beam_size - k0,), -1e30)])
                tok0 = jnp.concatenate(
                    [tok0, jnp.zeros((beam_size - k0,), tok0.dtype)])
            cache = jax.tree.map(
                lambda c: jnp.broadcast_to(
                    c, c.shape[:1] + (beam_size,) + c.shape[2:]
                ).copy() if c.ndim >= 2 else c, cache)

            def step(carry, i):
                cache, toks, scores = carry
                logits, cache = self._decode_token(params, cache, toks,
                                                   s0 + i)
                logp = jax.nn.log_softmax(logits)          # [beam, V]
                totals = scores[:, None] + logp
                flat_scores, flat_idx = jax.lax.top_k(
                    totals.reshape(-1), beam_size)
                parents = flat_idx // logp.shape[1]
                new_toks = (flat_idx % logp.shape[1]).astype(jnp.int32)
                cache = jax.tree.map(
                    lambda c: jnp.take(c, parents, axis=1), cache)
                return (cache, new_toks, flat_scores), (parents, new_toks)

            (cache, last, scores), (parents, toks) = jax.lax.scan(
                step, (cache, tok0.astype(jnp.int32), scores),
                jnp.arange(max_new_tokens - 1))

            # backtrack the best beam through the parent pointers
            n_steps = max_new_tokens - 1
            best = jnp.argmax(scores)

            def back(beam, i):
                step_i = n_steps - 1 - i
                tok = toks[step_i, beam]
                return parents[step_i, beam], tok

            beam, rev = jax.lax.scan(back, best, jnp.arange(n_steps))
            seq = jnp.concatenate(
                [tok0[beam][None], rev[::-1]]) if n_steps else \
                tok0[best][None]
            return jnp.concatenate([prompt, seq[None]], axis=1)
        finally:
            self.mesh = mesh_saved

    def generate(self, params, prompt, max_new_tokens: int,
                 temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 1.0, repetition_penalty: float = 1.0,
                 rng: Optional[jax.Array] = None) -> jax.Array:
        """Greedy (temperature=0) or sampled decode.  prompt: [B, S0] int32.
        Returns [B, S0 + max_new_tokens].  Jit-compatible: wrap in jax.jit
        with static max_new_tokens/temperature/top_k for the compiled path.

        ``repetition_penalty > 1`` divides the logits of every token
        already present in the sequence (prompt included) by the penalty
        when positive and multiplies when negative — the CTRL formulation.
        """
        prompt = jnp.asarray(prompt, jnp.int32)
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        # post-fit params are host numpy (trainer re-hydration); numpy
        # leaves cannot be indexed by tracers inside the decode scan
        params = jax.tree.map(jnp.asarray, params)
        b, s0 = prompt.shape
        total = s0 + max_new_tokens
        if total > self.cfg.max_seq_len:
            raise ValueError(f"prompt + new tokens ({total}) exceeds "
                             f"max_seq_len ({self.cfg.max_seq_len})")
        if rng is None:
            rng = jax.random.PRNGKey(0)
        # decode replicated: a training-time sequence/tensor/pipeline mesh
        # must not carve up generation-step-sized activations (the prompt
        # length need not divide those axes)
        mesh_saved, self.mesh = self.mesh, None
        try:
            window = self.cfg.sliding_window
            cache_len = total if window is None else min(total, window)
            h_last, cache = self._prefill(params, prompt, cache_len)
            dt = self.compute_dtype
            # presence mask of tokens seen so far, for repetition penalty
            seen = jax.nn.one_hot(prompt, self.cfg.vocab_size,
                                  dtype=jnp.bool_).any(axis=1)

            def penalize(logits, seen):
                if repetition_penalty == 1.0:
                    return logits
                scaled = jnp.where(logits > 0,
                                   logits / repetition_penalty,
                                   logits * repetition_penalty)
                return jnp.where(seen, scaled, logits)

            logits0 = penalize(
                self._unembed_matmul(h_last, params, dt), seen)
            rng, r0 = jax.random.split(rng)
            tok0 = self._sample(logits0, temperature, top_k, top_p, r0)
            seen = seen | jax.nn.one_hot(tok0, self.cfg.vocab_size,
                                         dtype=jnp.bool_)

            def step(carry, i):
                cache, tok, rng, seen = carry
                logits, cache = self._decode_token(params, cache, tok, s0 + i)
                logits = penalize(logits, seen)
                rng, r = jax.random.split(rng)
                nxt = self._sample(logits, temperature, top_k, top_p, r)
                seen = seen | jax.nn.one_hot(nxt, self.cfg.vocab_size,
                                             dtype=jnp.bool_)
                return (cache, nxt, rng, seen), nxt

            (_, _, _, _), toks = jax.lax.scan(
                step, (cache, tok0, rng, seen),
                jnp.arange(max_new_tokens - 1))
            out = jnp.concatenate(
                [prompt, tok0[:, None], toks.transpose(1, 0)], axis=1)
            return out
        finally:
            self.mesh = mesh_saved
