"""What the chip's compiler says, asked without a chip.

The TPU compiler is installed here and compiles for a chip that is
*described* (``v5e:2x2``), not attached.  These tests hand it the main
path at the 124M-GPT widths ``chip_smoke.py`` runs: every Pallas kernel,
the whole jitted train step (one chip, and the four-chip FSDP layouts),
the serve engine's paged programs and the int8 ``generate``.  Interpret
mode on the CPU mesh cannot see what this sees: a tiling the compiler
refuses, a kernel it cannot partition, a program that does not fit HBM.

Nothing runs and nothing is timed: a compile that passes is not a chip
run.  Code that asks ``jax.default_backend()`` would take its CPU branch
here, so the ``chip_dispatch`` fixture steers it onto the kernel branch
*in the test* -- the program has no option for it.

The topology is described inside a module-scoped fixture (never at
import, in a ``skipif`` or in ``parametrize`` arguments): only the xdist
worker that is handed this file loads libtpu.  All such tests live in
this one file for the same reason.
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

import chip_smoke
from chip_smoke import kernels_in as _kernels
from ray_lightning_accelerators_tpu import RayTPUAccelerator, Trainer
from ray_lightning_accelerators_tpu.core.state import TrainState
from ray_lightning_accelerators_tpu.models.transformer import (
    GPT, TransformerConfig)
from ray_lightning_accelerators_tpu.ops import quant
from ray_lightning_accelerators_tpu.ops.attention import flash_attention
from ray_lightning_accelerators_tpu.ops.norms import layer_norm, rms_norm
from ray_lightning_accelerators_tpu.runtime import guardian
from ray_lightning_accelerators_tpu.serve import ServeEngine
from ray_lightning_accelerators_tpu.utils.seed import rng_from_seed

# the 124M GPT and the step shape chip_smoke.py runs on the chip
SIZE = chip_smoke.Size()
PER_CHIP_BATCH, SEQ = SIZE.batch, SIZE.seq
HBM_BYTES = 16 * 2 ** 30  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep it off around these
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def chip_dispatch(monkeypatch):
    """Steer every ``jax.default_backend()`` dispatch in the package
    (ops/attention.py, ops/norms.py, GPT._q8_kernel_mode, the serve
    engine's donation switch) onto its on-chip branch."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# --------------------------------------------------------------------- #
# Pallas kernels at the 124M widths                                      #
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("block", [512, 1024])
def test_flash_forward_compiles(one_chip, chip_dispatch, block):
    q = _sds((PER_CHIP_BATCH, 12, SEQ, 64), jnp.bfloat16, one_chip)
    fwd = jax.jit(functools.partial(flash_attention, causal=True,
                                    block_q=block, block_k=block))
    lowered = fwd.lower(q, q, q)
    assert _kernels(lowered) == ["flash_fwd"]
    lowered.compile()


@pytest.mark.parametrize("block,bwd_kernels", [
    (512, ["flash_bwd_kwalk"]),   # the k-walk: one pass, dq held a head
    (1024, ["flash_bwd_fused"]),  # k_len == block_k: one pass
])
def test_flash_backward_compiles(one_chip, chip_dispatch, block,
                                 bwd_kernels):
    q = _sds((PER_CHIP_BATCH, 12, SEQ, 64), jnp.bfloat16, one_chip)

    def loss(q, k, v):
        out = flash_attention(q, k, v, True, None, block, block)
        return out.astype(jnp.float32).sum()

    lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, q, q)
    assert _kernels(lowered) == sorted(["flash_fwd"] + bwd_kernels)
    lowered.compile()


@pytest.mark.parametrize("shape,bwd_kernels", [
    ((4, 32, 8192, 64), ["flash_bwd_kwalk"]),     # train-lfm2-moe-8k
    ((1, 16, 8192, 128), ["flash_bwd_kwalk"]),    # train-ouro-loop-8k
    ((1, 8, 16384, 128), ["flash_bwd_dkv", "flash_bwd_dq"]),
], ids=["lfm2-8k-64", "ouro-8k-128", "16k-128-split"])
def test_flash_k_walk_backward_compiles_at_the_cells_operands(
        one_chip, chip_dispatch, shape, bwd_kernels):
    """Blocks 1024 over several key blocks: at 8,192 positions one head's
    float32 dq (4 MiB) stays in VMEM and the backward is ONE kernel,
    compiled inside the default scoped VMEM (15.5 of 16 MiB at 8,192;
    10,240 positions were refused at 16.49); at 16,384 the split pair."""
    q = _sds(shape, jnp.bfloat16, one_chip)

    def loss(q, k, v):
        out = flash_attention(q, k, v, True, None, 1024, 1024)
        return out.astype(jnp.float32).sum()

    lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, q, q)
    assert _kernels(lowered) == sorted(["flash_fwd"] + bwd_kernels)
    lowered.compile()


def test_norm_kernels_compile(one_chip, chip_dispatch):
    x = _sds((8192, 768), jnp.bfloat16, one_chip)
    s = _sds((768,), jnp.float32, one_chip)
    rms = jax.jit(rms_norm).lower(x, s)
    assert _kernels(rms) == ["rms_norm"]
    rms.compile()
    ln = jax.jit(layer_norm).lower(x, s, s)
    assert _kernels(ln) == ["layer_norm"]
    ln.compile()


def _gathers_of(text: str, result: str) -> list:
    """The compiled program's gathers (the instruction, or the fusion
    the compiler wraps it in and names after it) with that result
    type."""
    return [line for line in text.splitlines()
            if f"= {result}" in line and (" gather(" in line
                                          or "/gather\"" in line)]


def test_dropless_expert_layer_compiles_at_the_benchmark_cells_shapes(
        one_chip, chip_dispatch):
    """``train-lfm2-moe-8k``: 32,768 rows, 8 of 32 experts held, each
    2048 x 1792, top-4 -- forward and backward hold the grouped-matmul
    kernel, never ``ragged_dot``, and fit the chip.  The sorted side is
    a window of 49,152 rows, not the 131,072 pairs: no gate, up or
    product of that many rows is left.  The token side is choice-major:
    the combine's forward and the dispatch's backward each gather
    ``[4, 32768, 2048]`` (131,072 rows, every choice a slab of whole
    tiles) and reduce it as written: no ``[32768, 4, 2048]`` copy into
    another tiling is left, and ``rows_computed`` is a comparison of the
    positions, so no gather of 131,072 scalars either.  The loss reads
    ``y`` (a plain sum's gradient does not, and the compiler drops the
    combine's forward with it): both gathers stand in the program, and
    its temporaries read 1.305 GB where the token-major layer had 1.401
    (1.170 and 1.266 under a plain sum; the one-buffer layer, PR 27,
    2.49 there)."""
    from ray_lightning_accelerators_tpu.ops import moe

    held = tuple(range(8))
    p = jax.tree.map(
        lambda a: _sds(a.shape, a.dtype, one_chip),
        jax.eval_shape(lambda k: moe.init_dropless_params(
            k, 2048, 1792, 32, 8), jax.random.PRNGKey(0)))
    x = _sds((4, 8192, 2048), jnp.bfloat16, one_chip)
    assert moe.window_rows(4 * 8192 * 4, 8, 32) == 49152

    def loss(p, x):
        y, stats = moe.dropless_moe(x, p, top_k=4, held=held,
                                    num_experts=32)
        return (jnp.square(y.astype(jnp.float32)).sum(),
                stats["rows_computed"])

    lowered = jax.jit(jax.grad(loss, argnums=(0, 1), has_aux=True)
                      ).lower(p, x)
    # megablox's gmm and tgmm both call their body ``kernel``; the scope
    # ``kernel/moe_gmm`` is what tells them apart from other kernels
    assert _kernels(lowered) == ["kernel"]
    text = lowered.as_text()
    assert text.count("tpu_custom_call") >= 3   # gmm, its transpose, tgmm
    assert "stablehlo.ragged_dot" not in text
    assert "kernel/moe_gmm" in lowered.as_text(debug_info=True)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "[131072,1792]" not in text
    assert "[32768,4,2048]" not in text
    # both token-side gathers stand in the program, and all that reads
    # either is a bitcast (into the sum over the choices)
    row_gathers = re.findall(r"(%fusion\.\d+) = bf16\[131072,2048\]\S* "
                             r"fusion\(.*/gather\"", text)
    assert len(row_gathers) == 2
    for name in row_gathers:
        readers = [line for line in text.splitlines()
                   if re.search(re.escape(name) + "[,)]", line)]
        assert readers and all(" bitcast(" in r for r in readers), readers
    assert not _gathers_of(text, "pred[131072]")
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1.44e9      # 1.305e9 read, + 10 %
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < HBM_BYTES // 2


def test_nemotron_operators_compile_at_the_benchmark_cells_shapes(
        one_chip, chip_dispatch):
    """``train-nemotron3-ssm-8k``: [1, 8192, 4096] rows.  The latent
    expert layer (8 of 512 experts held, top-22: 180,224 pairs in windows
    of 4,608 sorted rows, ReLU^2 experts of 2688 in a latent of 1024, a
    shared expert of 5376) holds the grouped-matmul kernel forward and
    backward and meets its tokens by scatter-add: no ``[180224, 1024]``
    gather of every pair's row is left (two such stood at 369 MB each,
    738 MB at two sequences, before the window's own rows were added into
    their tokens), and ``rows_computed`` compares the positions: no
    gather of 180,224 scalars is left.  The Mamba-2 mixer (16 heads of
    64 in one group, state 128, chunks of 128) compiles forward and
    backward.  Temporaries of a layer's gradient, by the compiler's
    account: under 0.4 GB each (0.124 and 0.163 read)."""
    from ray_lightning_accelerators_tpu.ops import moe, ssm

    held = tuple(range(8))
    x = _sds((1, 8192, 4096), jnp.bfloat16, one_chip)

    def shapes(init, *args):
        return jax.tree.map(
            lambda a: _sds(a.shape, a.dtype, one_chip),
            jax.eval_shape(lambda k: init(k, *args), jax.random.PRNGKey(0)))

    assert moe.window_rows(8192 * 22, 8, 512) == 4608
    assert moe._token_side_by_scatter(4608, 8192 * 22)

    def expert_loss(p, x):
        y, stats = moe.latent_moe(x, p, top_k=22, held=held,
                                  num_experts=512, norm_topk=True,
                                  scale=5.0)
        return y.astype(jnp.float32).sum(), stats["rows_computed"]

    lowered = jax.jit(jax.grad(expert_loss, argnums=(0, 1), has_aux=True)
                      ).lower(shapes(moe.init_latent_moe_params, 4096, 1024,
                                     2688, 5376, 512, 8), x)
    assert _kernels(lowered) == ["kernel"]
    assert "stablehlo.ragged_dot" not in lowered.as_text()
    compiled = lowered.compile()
    assert "[180224,1024]" not in compiled.as_text()
    assert not _gathers_of(compiled.as_text(), "pred[180224]")
    assert compiled.memory_analysis().temp_size_in_bytes < 0.4e9

    def mixer_loss(p, x):
        return ssm.mamba2_mixer(x, p, heads=16, head_dim=64, groups=1,
                                state=128, chunk=128, eps=1e-5
                                ).astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(mixer_loss, argnums=(0, 1))).lower(
        shapes(ssm.init_mamba2_params, 4096, 16, 64, 1, 128, 4), x
    ).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 0.4e9


@pytest.mark.parametrize("rows", [4, 57, 1000])
def test_rms_norm_compiles_at_row_counts_off_the_sublane_tile(
        one_chip, chip_dispatch, rows):
    """Decode at 4 slots, a 57-token prompt, 1000 = 8 * 125 rows: the
    TPU lowering takes row blocks that are a multiple of 8 or the whole
    array (57 one-row blocks were refused on the chip, PR 22)."""
    x = _sds((1, rows, 768), jnp.bfloat16, one_chip)
    s = _sds((768,), jnp.float32, one_chip)
    lowered = jax.jit(rms_norm).lower(x, s)
    assert _kernels(lowered) == ["rms_norm"]
    lowered.compile()


def test_rms_norm_compiles_at_the_widest_cells_width(one_chip, chip_dispatch):
    """``train-nemotron3-ssm-8k``: 8,192 rows of 4096.  A 512-row block
    ran the kernel out of VMEM there (in and out double-buffered beside
    the float32 working copy); the block shrinks with the width."""
    x = _sds((1, 8192, 4096), jnp.bfloat16, one_chip)
    s = _sds((4096,), jnp.float32, one_chip)
    lowered = jax.jit(functools.partial(rms_norm, eps=1e-5)).lower(x, s)
    assert _kernels(lowered) == ["rms_norm"]
    lowered.compile()


@pytest.mark.parametrize("k,n", [(768, 768), (768, 3072), (3072, 768)])
def test_int8_matmul_decode_shapes_compile(one_chip, k, n):
    """q/k/v/o, MLP-in and MLP-out projections at decode batch 16."""
    x = _sds((16, k), jnp.bfloat16, one_chip)
    wq = _sds((k, n), jnp.int8, one_chip)
    scale = _sds((n,), jnp.float32, one_chip)
    lowered = quant.int8_matmul.lower(x, wq, scale)
    assert _kernels(lowered) == ["q8_matmul"]
    lowered.compile()


def test_int8_matmul_nt_unembed_compiles(one_chip):
    """The tied-embedding unembed, 16 x 768 x 50304."""
    x = _sds((16, 768), jnp.bfloat16, one_chip)
    wq = _sds((50304, 768), jnp.int8, one_chip)
    lowered = quant.int8_matmul_nt.lower(x, wq)
    assert _kernels(lowered) == ["q8_matmul_nt"]
    lowered.compile()


# --------------------------------------------------------------------- #
# The whole jitted train step                                            #
# --------------------------------------------------------------------- #
def _abstract_train_step(devices, per_chip_batch, use_fsdp=False,
                         config=None, **trainer_kw):
    """The Trainer's own jitted train step for ``devices``, with every
    operand a shape: what ``Trainer._fit_local`` sets up before
    ``_compile``, with ``jax.eval_shape`` in place of real arrays (a
    described device cannot hold one).  ``config``: the 124M GPT unless
    given."""
    config = config or chip_smoke._model_config(SIZE)
    module = GPT(config, lr=3e-4)
    trainer = Trainer(
        max_epochs=1, precision="bf16", enable_checkpointing=False, seed=0,
        accelerator=RayTPUAccelerator(num_workers=len(devices),
                                      use_fsdp=use_fsdp,
                                      devices=list(devices)),
        **trainer_kw)
    trainer.module, module.trainer = module, trainer
    module.compute_dtype = trainer.compute_dtype
    trainer._mesh = trainer.accelerator.build_mesh()
    trainer._tx = trainer._build_tx(module)

    def make_state():
        init_rng, state_rng = jax.random.split(rng_from_seed(trainer.seed))
        params = module.init_params(init_rng)
        state = TrainState.create(params, trainer._tx, state_rng)
        if trainer.grad_compression is not None:
            residual, grad_accum = trainer._fresh_exchange_buffers(
                module, params, trainer._mesh)
            state = state.replace(residual=residual, grad_accum=grad_accum)
        return state.replace(guard_ema=jnp.asarray(guardian.fresh_state()))

    state = jax.eval_shape(make_state)
    batch = jax.ShapeDtypeStruct(
        (per_chip_batch * len(devices), config.max_seq_len), jnp.int32)
    trainer._compile(module, state, batch)
    state = jax.tree.map(lambda s, sh: _sds(s.shape, s.dtype, sh),
                         state, trainer._state_shardings)
    batch = _sds(batch.shape, batch.dtype, trainer._batch_sharding)
    return trainer, trainer._train_step_fn.lower(state, batch)


def _per_device_bytes(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


_STEP_KERNELS = ["flash_bwd_fused", "flash_fwd", "rms_norm"]


def test_single_chip_train_step_compiles(topo, chip_dispatch):
    """custom_vjp kernels inside the layer scan, fused loss, guard tail,
    AdamW and donation, composed -- at bench.py's batch 16 x 1024."""
    _, lowered = _abstract_train_step(topo.devices[:1], PER_CHIP_BATCH)
    assert _kernels(lowered) == _STEP_KERNELS
    compiled = lowered.compile()
    assert _per_device_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("trainer_kw", [
    {},
    {"grad_compression": "int8", "gather_mode": "scan"},
], ids=["fsdp", "fsdp-int8-scan-gather"])
def test_four_chip_fsdp_train_step_compiles(topo, chip_dispatch,
                                            trainer_kw):
    """One program across the 2x2 mesh.  Under plain jit the compiler
    refuses a Pallas call it would have to partition ("Mosaic kernels
    cannot be automatically partitioned"), so the model runs its kernels
    per shard (parallel/sharding.shard_local); the compressed exchange
    runs them inside its own full-manual shard_map."""
    trainer, lowered = _abstract_train_step(
        topo.devices, PER_CHIP_BATCH // 4, use_fsdp=True, **trainer_kw)
    if trainer_kw:
        assert trainer._gather_mode_eff == "scan"
    assert _kernels(lowered) == _STEP_KERNELS
    compiled = lowered.compile()
    assert _per_device_bytes(compiled) < HBM_BYTES
    # params and Adam moments really are 1/4 per chip
    wq = trainer._state_shardings.params["layers"]["attn"]["wq"]
    assert wq.shard_shape((12, 768, 12, 64)) == (12, 192, 12, 64)


def _forward_kernel_calls(hlo: str) -> int:
    return sum("tpu_custom_call" in line and "flash_fwd" in line
               for line in hlo.splitlines() if "custom-call(" in line)


def test_four_chip_fsdp_remat_step_keeps_its_kernels_and_bytes(
        topo, chip_dispatch):
    """``train-xl-fsdp4``'s guard: one k block a sequence, no expert
    layer, so no value in the block carries a name and a remat policy
    that keeps the named residuals keeps what ``nothing_saveable``
    kept: the forward kernel twice (forward and remat) and the bytes
    this step had before any name existed (PR 31's tree, described
    compile: 1,493,542,912; 96.6 MB more since PR 34, whose fused loss
    holds float32 dh and dw from its forward where it held the bfloat16
    ``h`` and ``w``)."""
    config = chip_smoke._model_config(SIZE)
    config.remat = True
    _, lowered = _abstract_train_step(topo.devices, PER_CHIP_BATCH // 4,
                                      use_fsdp=True, config=config)
    compiled = lowered.compile()
    assert _forward_kernel_calls(compiled.as_text()) == 2
    assert _per_device_bytes(compiled) == 1_590_152_704


@pytest.mark.parametrize("block,forward_kernels", [(1024, 1), (2048, 2)],
                         ids=["k-walk", "one-k-block"])
def test_remat_step_runs_the_k_walk_and_the_routing_once(
        topo, chip_dispatch, block, forward_kernels):
    """A ``remat=True`` step with an attention layer and two sparse
    runs (LFM2-shaped, three layers).  At two k blocks a sequence the
    kernel's output and log-sum-exp are kept by name: ONE forward kernel
    in the program.  At one block nothing is named and it runs twice.
    Either way the plan of the expert layer's windows is kept: the
    router's ``top_k`` and the two ``argsort``s stand once a sparse run
    (six ``sort``s), none under the backward's or the remat's op names
    (PR 31's tree: twelve, six of them there)."""
    config = TransformerConfig(
        vocab_size=2048, d_model=256, n_heads=4, n_kv_heads=2, d_ff=512,
        n_layers=3, max_seq_len=2048, conv_kernel=3, moe_router="sigmoid",
        layer_types=["conv", "full_attention", "conv"], num_dense_layers=1,
        num_experts=8, moe_top_k=2, moe_d_ff=128, moe_experts_held=[0, 1],
        gated_mlp=True, qk_norm=True, rope_style="half", norm_eps=1e-5,
        remat=True, flash_block_q=block, flash_block_k=block,
        loss_chunk_rows=2048)
    _, lowered = _abstract_train_step(topo.devices[:1], 2, config=config)
    hlo = lowered.compile().as_text()
    assert _forward_kernel_calls(hlo) == forward_kernels
    sorts = [line for line in hlo.splitlines()
             if " sort(" in line.split("metadata")[0]]
    assert len(sorts) == 6
    assert [line for line in sorts
            if "transpose(" in line or "rematted_computation" in line] == []


def test_looped_step_fits_the_chip_and_holds_one_layer_body(
        topo, chip_dispatch):
    """``train-ouro-loop-8k``: 6 sandwich-norm layers of 16 heads of 128
    run 4 times on the same weights at [1, 8192], remat, flash blocks
    1024, the whole 49,152-id vocabulary through the weighted fused loss.
    The pass loop is a loop in the program: ONE forward kernel (its
    output kept by name for all 24 applications, so none under the
    backward) and ONE backward kernel, the k-walk's one pass; the step
    stays under the chip's 15.75 GiB with room for what the scanned
    epoch holds over it
    (15.00 GB described, PR 33, the scanned epoch 1.42 more; 15.07 and
    0.44 more since PR 34, the fused loss's forward rule keeping dh and
    dw where it kept its operands; 8 layers read 18.48)."""
    config = TransformerConfig(
        vocab_size=49152, d_model=2048, n_heads=16, attn_head_dim=128,
        d_ff=5632, n_layers=6, max_seq_len=8192, tie_embeddings=False,
        rope_theta=1e6, gated_mlp=True, rope_style="half", post_norms=True,
        loop_passes=4, exit_gate=True, exit_beta=0.05, remat=True,
        flash_block_q=1024, flash_block_k=1024, loss_chunk_rows=2048)
    _, lowered = _abstract_train_step(topo.devices[:1], 1, config=config)
    assert _kernels(lowered) == ["flash_bwd_kwalk", "flash_fwd", "rms_norm"]
    compiled = lowered.compile()
    hlo = compiled.as_text()
    assert _forward_kernel_calls(hlo) == 1
    assert sum("tpu_custom_call" in line and "flash_bwd_kwalk" in line
               for line in hlo.splitlines() if "custom-call(" in line) == 1
    assert _per_device_bytes(compiled) < 15.3e9
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    assert any(re.search(r"gpt/loop\b.*gpt/layers", n) for n in names)
    assert any("gpt/loop_exit" in n for n in names)


# --------------------------------------------------------------------- #
# The attention operator at the benchmark cells' shapes                  #
# --------------------------------------------------------------------- #
_LFM2_ATTENTION = dict(n_kv_heads=8, qk_norm=True, rope_style="half",
                       gated_mlp=True, layer_types=("full_attention",),
                       rope_theta=1e6, norm_eps=1e-5)


@pytest.mark.parametrize("shape,heads,block", [
    ((8, 1024, 1600), 25, {}),              # train-xl-fsdp4, one chip's
    ((4, 1024, 1024), 16, {}),              # train-medium-1k
    ((4, 1024, 2048), 32, _LFM2_ATTENTION),  # rotate-half, GQA 32 / 8
], ids=["xl-25-heads", "medium-16-heads", "rotate-half-gqa"])
def test_attention_operator_rotates_without_splitting_the_head(
        one_chip, chip_dispatch, shape, heads, block):
    """``GPT._self_attention`` forward + backward, bf16 over f32
    weights, flash block 1024.  ``_rope`` keeps the 64-wide head whole
    on the lanes: a strided lane slice compiles to a gather, its
    gradient to a scatter-add into a zero-filled buffer, the stack /
    reshape to relayouts through ``[.., 32, 2]`` arrays (six passes over
    q and over k a layer where one does, PR 30).  None of them may come
    back."""
    b, s, d = shape
    model = GPT(TransformerConfig(
        vocab_size=512, d_model=d, n_heads=heads, d_ff=4 * d, n_layers=1,
        max_seq_len=s, flash_block_q=1024, flash_block_k=1024, **block))
    model.compute_dtype = jnp.bfloat16
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    attn = jax.tree.map(
        lambda p: _sds(p.shape[1:], p.dtype, one_chip),
        params[model.cfg.run_keys()[0]]["attn"])

    def loss(a, x):
        out, _ = model._self_attention(x, a, jnp.arange(s))
        return out.astype(jnp.float32).sum()

    lowered = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        attn, _sds(shape, jnp.bfloat16, one_chip))
    assert set(_kernels(lowered)) >= {"flash_bwd_fused", "flash_fwd"}
    text = lowered.compile().as_text()
    under_attn = [line for line in text.splitlines() if "gpt/attn" in line]
    assert under_attn                   # the scope reached the text
    moved = [line.split(" = ")[0].strip() for line in under_attn
             if re.search(r"\b(gather|scatter)", line.split("metadata")[0])]
    assert moved == []
    assert re.findall(r"\[[0-9,]*\b32,[12]\]", text) == []


# --------------------------------------------------------------------- #
# Serve engine programs and int8 generate                                #
# --------------------------------------------------------------------- #
@pytest.fixture
def gpt_bf16(chip_dispatch):
    model = GPT(chip_smoke._model_config(SIZE))
    model.compute_dtype = jnp.bfloat16
    shapes = jax.eval_shape(lambda: jax.tree.map(
        lambda p: p.astype(jnp.bfloat16),
        model.init_params(jax.random.PRNGKey(0))))
    return model, shapes


def test_paged_serve_programs_compile(one_chip, gpt_bf16):
    """The engine's own jitted programs -- the paged decode step and the
    paged prefill chunk (smallest and largest bucket) -- with the pool
    operand donated, as it is on the chip and never is on CPU."""
    model, shapes = gpt_bf16

    def on_chip(tree):
        return jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip), tree)

    # the engine never inspects its params; the programs take them as an
    # operand, so placeholders stand in for 124M real weights
    engine = ServeEngine(model, jax.tree.map(
        lambda s: np.zeros((1,), s.dtype), shapes))
    assert engine.paged and engine._donate
    params = on_chip(shapes)
    pool = on_chip(jax.eval_shape(
        lambda: model.paged_cache_alloc(engine.n_blocks, engine.block_len)))
    pool_bytes = sum(x.size * x.dtype.itemsize
                     for x in jax.tree.leaves(pool))

    def i32(*shape):
        return _sds(shape, jnp.int32, one_chip)

    B, M = engine.max_slots, engine.table_blocks
    step = engine._step.lower(params, pool, i32(B, M), i32(B), i32(B))
    assert _kernels(step) == ["rms_norm"]
    # donated: the pool is aliased to the output, not copied (>=: the
    # chip's tiled layout pads the [.., block_len, head_dim] minor dims)
    compiled = step.compile()
    assert compiled.memory_analysis().alias_size_in_bytes >= pool_bytes
    for bucket in (engine.block_len,
                   engine._chunk_blocks * engine.block_len):
        chunk = engine._chunk_prefill_fn(bucket).lower(
            params, pool, i32(M), i32(1, bucket), i32(), i32())
        compiled = chunk.compile()
        assert compiled.memory_analysis().alias_size_in_bytes >= pool_bytes


def test_int8_generate_compiles_on_the_kernel_path(one_chip, gpt_bf16):
    """Batch-16 greedy generate over ``quantize_weights`` params: every
    decode matmul of the model's own shapes takes the compiled q8
    kernels (none declined), composed with prefill's flash kernel."""
    model, shapes = gpt_bf16
    q8 = jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip),
                      jax.eval_shape(GPT.quantize_weights, shapes))
    assert model._q8_kernel_mode() == "compiled"
    declined = set(GPT._q8_declined_shapes)
    gen = jax.jit(functools.partial(model.generate, max_new_tokens=32,
                                    temperature=0.0))
    lowered = gen.lower(q8, _sds((16, 128), jnp.int32, one_chip))
    assert GPT._q8_declined_shapes == declined
    assert _kernels(lowered) == ["flash_fwd", "q8_matmul",
                                 "q8_matmul_nt", "rms_norm"]
    lowered.compile()
