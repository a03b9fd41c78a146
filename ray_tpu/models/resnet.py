"""ResNet-50 — the convnet benchmark model (BASELINE.json config 1:
"DataParallelTrainer ResNet-50"; reference throughput targets in
BASELINE.md from doc/source/train/benchmarks.rst).

Flax linen implementation, NHWC layout (TPU-native conv layout), bf16
compute / f32 BatchNorm statistics. v1.5 variant (stride in the 3x3)
matching torchvision's resnet50 so images/sec comparisons are like-for-like.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

ModuleDef = Any

BN_EPS = 1e-5  # single source of truth — fold_batch_norm must match


class Bottleneck(nn.Module):
    """`folded=True` is the inference variant with BatchNorm absorbed into
    the convs (bias + relu epilogue only, consuming fold_batch_norm's
    params); one structural definition serves both paths so the trees map
    conv-for-conv by construction."""

    features: int
    strides: int = 1
    dtype: Any = jnp.bfloat16
    folded: bool = False

    @nn.compact
    def __call__(self, x, train: bool = True):
        conv = partial(nn.Conv, use_bias=self.folded, dtype=self.dtype)
        bn = partial(
            nn.BatchNorm,
            use_running_average=not train,
            momentum=0.9,
            epsilon=BN_EPS,
            dtype=self.dtype,
            param_dtype=jnp.float32,
        )

        def norm(y, **kw):
            return y if self.folded else bn(**kw)(y)

        residual = x
        y = conv(self.features, (1, 1))(x)
        y = nn.relu(norm(y))
        y = conv(self.features, (3, 3), strides=(self.strides, self.strides))(y)
        y = nn.relu(norm(y))
        y = conv(self.features * 4, (1, 1))(y)
        # zero-init the last BN scale: identity residual at init
        y = norm(y, scale_init=nn.initializers.zeros)
        if residual.shape != y.shape:
            residual = conv(
                self.features * 4, (1, 1), strides=(self.strides, self.strides),
                name="downsample_conv",
            )(x)
            residual = norm(residual, name="downsample_bn")
        return nn.relu(y + residual)


class ResNet(nn.Module):
    stage_sizes: Sequence[int]
    num_classes: int = 1000
    dtype: Any = jnp.bfloat16
    folded: bool = False  # inference variant: BN folded into the convs

    @nn.compact
    def __call__(self, x, train: bool = True):
        x = x.astype(self.dtype)
        x = nn.Conv(
            64, (7, 7), strides=(2, 2), padding=[(3, 3), (3, 3)],
            use_bias=self.folded, dtype=self.dtype, name="conv_init",
        )(x)
        if not self.folded:
            x = nn.BatchNorm(
                use_running_average=not train, momentum=0.9, epsilon=BN_EPS,
                dtype=self.dtype, param_dtype=jnp.float32, name="bn_init",
            )(x)
        x = nn.relu(x)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=[(1, 1), (1, 1)])
        for i, block_count in enumerate(self.stage_sizes):
            for j in range(block_count):
                strides = 2 if i > 0 and j == 0 else 1
                x = Bottleneck(64 * 2**i, strides=strides, dtype=self.dtype,
                               folded=self.folded)(x, train=train)
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, dtype=jnp.float32, name="head")(x)
        return x


def ResNet50(num_classes: int = 1000, dtype=jnp.bfloat16) -> ResNet:
    return ResNet(stage_sizes=(3, 4, 6, 3), num_classes=num_classes, dtype=dtype)


def resnet_init(key: jax.Array, model: ResNet, image_size: int = 224):
    variables = model.init(
        key, jnp.zeros((1, image_size, image_size, 3), jnp.float32), train=True
    )
    return variables["params"], variables["batch_stats"]


def FoldedResNet(stage_sizes, num_classes: int = 1000,
                 dtype=jnp.bfloat16) -> ResNet:
    """BN-free inference variant (W' = W * gamma/sqrt(var+eps) per
    out-channel, b' = beta - mean * gamma/sqrt(var+eps)); consumes
    fold_batch_norm's params. Removes every BN read-modify-write pass from
    the serving graph — the conv epilogue is just bias+relu, which XLA
    fuses into the convolution (unfused BN is the ResNet
    HBM ceiling; the training-time equivalent needs running stats and
    stays unfolded)."""
    return ResNet(stage_sizes=stage_sizes, num_classes=num_classes,
                  dtype=dtype, folded=True)


def _fold_one(conv_p: dict, bn_p: dict, bn_s: dict, eps: float) -> dict:
    """Absorb one BatchNorm (scale/bias + running stats) into the conv that
    feeds it."""
    inv = bn_p["scale"] / jnp.sqrt(bn_s["var"] + eps)
    kernel = conv_p["kernel"] * inv  # broadcast over the out-channel axis
    bias = bn_p["bias"] - bn_s["mean"] * inv
    return {"kernel": kernel, "bias": bias}


def fold_batch_norm(params: dict, batch_stats: dict,
                    eps: float = BN_EPS) -> dict:
    """Trained (params, batch_stats) -> folded (ResNet(folded=True)) param
    tree. Pure tree surgery; numerical equivalence to
    model.apply(train=False) is exact up to dtype rounding
    (tests/test_models.py). `eps` must match the model's BatchNorm epsilon
    (BN_EPS for the in-tree ResNet)."""
    out: dict = {
        "conv_init": _fold_one(params["conv_init"], params["bn_init"],
                               batch_stats["bn_init"], eps),
        "head": params["head"],
    }
    for name, block in params.items():
        if not name.startswith("Bottleneck_"):
            continue
        stats = batch_stats[name]
        folded: dict = {}
        for k in range(3):
            folded[f"Conv_{k}"] = _fold_one(
                block[f"Conv_{k}"], block[f"BatchNorm_{k}"],
                stats[f"BatchNorm_{k}"], eps)
        if "downsample_conv" in block:
            folded["downsample_conv"] = _fold_one(
                block["downsample_conv"], block["downsample_bn"],
                stats["downsample_bn"], eps)
        out[name] = folded
    return out


def resnet_loss(params, batch_stats, model, batch, train: bool = True):
    """Cross-entropy + new batch stats. batch: {'image' NHWC, 'label' int}."""
    if train:
        logits, mutated = model.apply(
            {"params": params, "batch_stats": batch_stats},
            batch["image"],
            train=True,
            mutable=["batch_stats"],
        )
        new_stats = mutated["batch_stats"]
    else:
        logits = model.apply(
            {"params": params, "batch_stats": batch_stats},
            batch["image"],
            train=False,
        )
        new_stats = batch_stats
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    ll = jnp.take_along_axis(logp, batch["label"][:, None], axis=-1)[:, 0]
    acc = jnp.mean((jnp.argmax(logits, -1) == batch["label"]).astype(jnp.float32))
    return -jnp.mean(ll), (new_stats, acc)
