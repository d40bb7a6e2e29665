"""Vision Transformer: the attention-based vision model family.

No reference analog (the reference ships only the MNIST MLP example,
reference: examples/ray_ddp_example.py:18-59); this rounds out the model
zoo beside the conv family (models/resnet.py) and the LM flagship
(models/transformer.py), sharing their TPU-first machinery:

- **patchify = one matmul**: images are reshaped into [n_patches,
  patch_dim] host of the MXU rather than convolved — identical math to the
  usual conv-with-stride=patch stem, expressed as the layout XLA tiles
  best;
- **pre-norm blocks with the Pallas flash-attention kernel**
  (ops/attention.py) and fused RMSNorm (ops/norms.py);
- **stacked + scanned layers** (`lax.scan`, optional `jax.checkpoint`):
  one compile regardless of depth;
- **logical axis names** on every parameter so the accelerator's sharding
  rules give dp/fsdp/tp layouts for free (parallel/sharding.py);
- **mean pooling** instead of a CLS token: keeps the sequence length a
  clean power-of-two multiple for attention block tiling and drops the
  one-token concat special case.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import optax

from ..core.module import TpuModule
from ..ops.attention import flash_attention
from ..ops.norms import rms_norm
from ..parallel import mesh as mesh_lib
from ..parallel import sharding as sharding_lib


@dataclasses.dataclass
class ViTConfig:
    image_size: int = 32
    patch_size: int = 4
    channels: int = 3
    d_model: int = 256
    n_heads: int = 4
    d_ff: int = 1024
    n_layers: int = 6
    n_classes: int = 10
    remat: bool = False

    @property
    def n_patches(self) -> int:
        assert self.image_size % self.patch_size == 0
        return (self.image_size // self.patch_size) ** 2

    @property
    def patch_dim(self) -> int:
        return self.patch_size * self.patch_size * self.channels

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads


class ViT(TpuModule):
    """Images [B, H, W, C] (NHWC) -> class logits [B, n_classes]."""

    def __init__(self, config: Optional[ViTConfig] = None, lr: float = 1e-3,
                 **cfg_overrides):
        super().__init__()
        if config is None:
            config = ViTConfig(**cfg_overrides)
        elif isinstance(config, dict):
            # hparams round-trip: load_from_checkpoint calls cls(**hparams)
            config = ViTConfig(**config)
        self.cfg = config
        lr = self.coerce_checkpoint_lr(lr, 1e-3, "ViT")
        self.lr = lr
        if callable(lr):
            self.lr_schedule = lr
        self.save_hyperparameters(config=dataclasses.asdict(config),
                                  lr=repr(lr) if callable(lr) else lr)

    # ------------------------------------------------------------------ #
    def init_params(self, rng) -> Dict[str, Any]:
        cfg = self.cfg
        d, h, hd, f = cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.d_ff
        ks = jax.random.split(rng, 4)

        def dense(key, shape, fan_in):
            return (jax.random.normal(key, shape, jnp.float32)
                    * (fan_in ** -0.5))

        def layer(key):
            k = jax.random.split(key, 6)
            return {
                "attn": {
                    "wq": dense(k[0], (d, h, hd), d),
                    "wk": dense(k[1], (d, h, hd), d),
                    "wv": dense(k[2], (d, h, hd), d),
                    "wo": dense(k[3], (h, hd, d), d),
                },
                "mlp": {"wi": dense(k[4], (d, f), d),
                        "wo": dense(k[5], (f, d), f)},
                "ln1": jnp.ones((d,), jnp.float32),
                "ln2": jnp.ones((d,), jnp.float32),
            }

        layer_keys = jax.random.split(ks[2], cfg.n_layers)
        return {
            "patch_embed": dense(ks[0], (cfg.patch_dim, d), cfg.patch_dim),
            "pos_embed": jax.random.normal(
                ks[1], (cfg.n_patches, d), jnp.float32) * 0.02,
            "layers": jax.vmap(layer)(layer_keys),
            "ln_f": jnp.ones((d,), jnp.float32),
            "head": dense(ks[3], (d, cfg.n_classes), d),
        }

    def param_logical_axes(self) -> Dict[str, Any]:
        return {
            "patch_embed": (None, "embed"),
            "pos_embed": (None, "embed"),
            "layers": {
                "attn": {
                    "wq": ("layers", "embed", "heads", "kv"),
                    "wk": ("layers", "embed", "heads", "kv"),
                    "wv": ("layers", "embed", "heads", "kv"),
                    "wo": ("layers", "heads", "kv", "embed"),
                },
                "mlp": {"wi": ("layers", "embed", "mlp"),
                        "wo": ("layers", "mlp", "embed")},
                "ln1": ("layers", None),
                "ln2": ("layers", None),
            },
            "ln_f": (None,),
            "head": ("embed", None),
        }

    # ------------------------------------------------------------------ #
    def _patchify(self, x: jax.Array) -> jax.Array:
        """[B,H,W,C] -> [B, n_patches, patch_dim] (row-major patch order)."""
        p = self.cfg.patch_size
        b, hh, ww, c = x.shape
        x = x.reshape(b, hh // p, p, ww // p, p, c)
        x = x.transpose(0, 1, 3, 2, 4, 5)
        return x.reshape(b, (hh // p) * (ww // p), p * p * c)

    def _constrain(self, x, *spec):
        if self.mesh is not None:
            return sharding_lib.shard_constraint(
                # constraint shim: the spec entries come from the
                # inventoried logical rules (parallel/sharding.py)
                # graftlint: ok(sharding-inventory) — only tuple->P here
                x, self.mesh, jax.sharding.PartitionSpec(*spec))
        return x

    def _rms_norm(self, h, scale):
        # per-shard on a multi-device mesh: the Pallas kernel behind
        # rms_norm cannot be partitioned automatically (shard_local)
        rows = ("batch", None, None)
        return sharding_lib.shard_local(
            rms_norm, self.mesh, (rows, (None,)), rows)(h, scale)

    def _attention(self, q, k, v):
        qkv = ("batch", "heads", None, None)
        return sharding_lib.shard_local(
            functools.partial(flash_attention, causal=False),
            self.mesh, (qkv, qkv, qkv), qkv)(q, k, v)

    def _block(self, h, lp):
        dt = self.compute_dtype
        a = lp["attn"]
        x = self._rms_norm(h, lp["ln1"])
        q = jnp.einsum("bsd,dhk->bhsk", x, a["wq"].astype(dt))
        k = jnp.einsum("bsd,dhk->bhsk", x, a["wk"].astype(dt))
        v = jnp.einsum("bsd,dhk->bhsk", x, a["wv"].astype(dt))
        attn = self._attention(q, k, v)
        h = h + jnp.einsum("bhsk,hkd->bsd", attn, a["wo"].astype(dt))
        x = self._rms_norm(h, lp["ln2"])
        m = lp["mlp"]
        up = jax.nn.gelu(jnp.einsum("bsd,df->bsf", x, m["wi"].astype(dt)))
        up = self._constrain(up, mesh_lib.BATCH_AXES, None,
                             mesh_lib.TENSOR_AXIS)
        h = h + jnp.einsum("bsf,fd->bsd", up, m["wo"].astype(dt))
        return self._constrain(h, mesh_lib.BATCH_AXES, None, None), None

    def forward(self, params, batch):
        x = batch[0] if isinstance(batch, (tuple, list)) else batch
        dt = self.compute_dtype
        patches = self._patchify(x.astype(dt))
        h = patches @ params["patch_embed"].astype(dt)
        h = h + params["pos_embed"].astype(dt)[None]
        h = self._constrain(h, mesh_lib.BATCH_AXES, None, None)

        def block(carry, lp):
            return self._block(carry, lp)

        if self.cfg.remat:
            block = jax.checkpoint(block)
        h, _ = jax.lax.scan(block, h, params["layers"])
        h = self._rms_norm(h, params["ln_f"])
        pooled = jnp.mean(h, axis=1)
        return (pooled @ params["head"].astype(dt)).astype(jnp.float32)

    # ------------------------------------------------------------------ #
    def _loss_acc(self, params, batch):
        x, y = batch
        logits = self.forward(params, x)
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()
        acc = jnp.mean(jnp.argmax(logits, -1) == y)
        return loss, acc

    def training_step(self, params, batch, rng):
        loss, acc = self._loss_acc(params, batch)
        return loss, {"loss": loss, "accuracy": acc}

    def validation_step(self, params, batch):
        loss, acc = self._loss_acc(params, batch)
        return {"val_loss": loss, "val_accuracy": acc}

    def predict_step(self, params, batch):
        x = batch[0] if isinstance(batch, (tuple, list)) else batch
        return jnp.argmax(self.forward(params, x), -1)

    def configure_optimizers(self):
        return optax.adamw(self.lr, weight_decay=0.05)
