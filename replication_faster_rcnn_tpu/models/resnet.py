"""ResNet backbones in flax — TPU-native (NHWC, bfloat16 compute).

Capability parity with the reference's three backbone files
(`nets/resnet_torch.py` — the one actually used; `nets/resnet50.py`;
`nets/resnet.py` unused CIFAR variant): BasicBlock/Bottleneck residual
stacks with the Faster-R-CNN split of reference `nets/resnet_torch.py:392-409`
—  a stride-16 **trunk** (conv1..layer3) producing the shared feature map,
and a **tail** (layer4 + global average pool) reused as the detection head's
feature extractor on pooled ROI crops (reference `nets/heads.py:51-52`).

TPU-first design choices (not translations):
  * NHWC layout throughout — XLA's native conv layout on TPU; the MXU tiles
    [spatial, C_in] x [C_in, C_out] matmuls directly.
  * bfloat16 activations/conv compute with float32 params and BatchNorm
    statistics — the v5e MXU's native mixed precision.
  * Padding tuples mirror torch's exact arithmetic (7x7/s2/p3 stem,
    3x3/s2/p1 maxpool and downsample convs) so a converted torch checkpoint
    reproduces reference features and shapes (600 -> 38 at stride 16).
  * Parameter tree names mirror the torch module names (conv1, bn1,
    layer1.0.conv2, ...) so the torch->flax weight converter
    (`models/convert.py`) is a pure name mapping.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from flax.linen.normalization import _compute_stats

from replication_faster_rcnn_tpu.ops.pool_ops import norm_relu_max_pool

Array = jnp.ndarray


def _norm(
    dtype: Any,
    train: bool,
    name: str,
    axis_name: Any = None,
    kind: str = "batch",
    terms: bool = False,
):
    """Normalization layer at the reference's BN sites.

    ``kind='batch'`` (default): BatchNorm matching torch defaults (eps
    1e-5, momentum 0.1 — i.e. running = 0.9 * running + 0.1 * batch).
    Stats/scale kept in float32. ``axis_name`` enables cross-replica
    (sync) BN under the explicit shard_map backend: batch statistics
    pmean over that mesh axis, matching what jit auto-partitioning
    computes on a globally-sharded batch.

    ``kind='group'``: GroupNorm(32) — the BN-free structural lever:
    train-mode BN's batch-stats reductions are fusion breaks + HBM
    round-trips XLA cannot elide (bn1's backward sums read the 300x300x64
    map and its cotangent once more until `_stem_pool` took them from the
    pooled arrays: PERF.md section 6, PR 30), while GN normalizes within each
    sample — no mutable state, no cross-batch coupling, shard-invariant
    by construction. Parameter
    names stay at the BN sites' names (scale/bias under e.g. 'bn1') so
    the tree layout is stable; there are no running statistics, so
    torch-pretrained BN checkpoints do NOT convert onto a GN model.

    ``terms=True``: the same layer, same parameters and statistics, whose
    call returns the terms of its affine and leaves applying them to the
    caller (`_stem_pool`)."""
    if kind == "group":
        return (_GroupNormTerms if terms else nn.GroupNorm)(
            num_groups=32,
            epsilon=1e-5,
            dtype=dtype,
            param_dtype=jnp.float32,
            name=name,
        )
    return (_BatchNormTerms if terms else nn.BatchNorm)(
        use_running_average=not train,
        momentum=0.9,
        epsilon=1e-5,
        dtype=dtype,
        param_dtype=jnp.float32,
        axis_name=axis_name,
        name=name,
    )


def _affine_terms(mdl: nn.Module, mean: Array, var: Array):
    """The three terms of flax's `_normalize`, ``(x - mean) * mul + bias``,
    with the layer's ``scale`` and ``bias`` made under `mdl` as flax makes
    them; `mean` and `var` already broadcast against the map."""
    features = (mean.shape[-1],)
    mul = jax.lax.rsqrt(var + mdl.epsilon)
    mul *= mdl.param("scale", mdl.scale_init, features, mdl.param_dtype).reshape(1, 1, 1, -1)
    bias = mdl.param("bias", mdl.bias_init, features, mdl.param_dtype).reshape(1, 1, 1, -1)
    return mean, mul, bias


class _BatchNormTerms(nn.BatchNorm):
    """`nn.BatchNorm` up to its last step: the statistics, their running
    averages, parameters and `batch_stats` as flax has them, and then the
    terms of the affine in place of the normalised map (`_stem_pool`)."""

    @nn.compact
    def __call__(self, x: Array):
        features = (x.shape[-1],)
        ra_mean = self.variable("batch_stats", "mean", jnp.zeros, features, jnp.float32)
        ra_var = self.variable("batch_stats", "var", jnp.ones, features, jnp.float32)
        if self.use_running_average:
            mean, var = ra_mean.value, ra_var.value
        else:
            mean, var = _compute_stats(
                x, (0, 1, 2), dtype=self.dtype,
                axis_name=None if self.is_initializing() else self.axis_name,
                use_fast_variance=self.use_fast_variance,
            )
            if not self.is_initializing():
                ra_mean.value = self.momentum * ra_mean.value + (1 - self.momentum) * mean
                ra_var.value = self.momentum * ra_var.value + (1 - self.momentum) * var
        return _affine_terms(self, mean.reshape(1, 1, 1, -1), var.reshape(1, 1, 1, -1))


class _GroupNormTerms(nn.GroupNorm):
    """`nn.GroupNorm` up to its last step, as `_BatchNormTerms`: a sample's
    own statistics, so the terms are ``[N, 1, 1, C]``."""

    @nn.compact
    def __call__(self, x: Array):
        size = x.shape[-1] // self.num_groups
        mean, var = _compute_stats(
            x.reshape(x.shape[:-1] + (self.num_groups, size)), (1, 2, 4), self.dtype,
            use_fast_variance=self.use_fast_variance,
        )
        mean, var = (jnp.repeat(s, size, axis=-1)[:, None, None, :] for s in (mean, var))
        return _affine_terms(self, mean, var)


def _stem_pool(x: Array, dtype: Any, train: bool, axis_name: Any, kind: str) -> Array:
    """bn1, ReLU and the 3x3 / stride 2 / pad 1 max-pool of the ImageNet stem
    as one function (`ops/pool_ops.py`): the layer hands over its affine's
    terms, and the affine is applied where the pool reads the map."""
    terms = _norm(dtype, train, "bn1", axis_name, kind, terms=True)(x)
    return norm_relu_max_pool(x, *terms, dtype)


class GroupedConv(nn.Module):
    """Grouped KxK conv as patch extraction + per-group batched einsum.

    ResNeXt's grouped 3x3 (reference `nets/resnet_torch.py:10-12,100`,
    torch ``groups=``) cannot use ``feature_group_count`` here: XLA's TPU
    grouped-convolution lowering stalls on this backend for any group count
    > 1. The TPU-native formulation is a grouped GEMM: unroll the KxK taps
    into shifted slices (9 static slices — no gather), then contract each
    group's ``[HW, K*K*I/g] x [K*K*I/g, O/g]`` block as one batched einsum,
    which XLA maps straight onto the MXU. FLOPs are the true grouped count
    (1/g of dense).

    The parameter keeps nn.Conv's grouped-HWIO kernel shape
    ``[K, K, I/g, O]`` (torch layout transposed), so `models/convert.py`
    converts torch grouped weights with the same pure transpose it uses for
    dense convs, and fan-in (K*K*I/g) matches for initialization.
    """

    features: int
    kernel: int
    stride: int
    padding: int
    groups: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x: Array) -> Array:
        g, k, s, p = self.groups, self.kernel, self.stride, self.padding
        in_ch = x.shape[-1]
        assert in_ch % g == 0 and self.features % g == 0
        w = self.param(
            "kernel",
            nn.initializers.lecun_normal(),
            (k, k, in_ch // g, self.features),
            jnp.float32,
        )
        x = x.astype(self.dtype)
        w = w.astype(self.dtype)
        xp = jnp.pad(x, ((0, 0), (p, p), (p, p), (0, 0)))
        out_h = (x.shape[1] + 2 * p - k) // s + 1
        out_w = (x.shape[2] + 2 * p - k) // s + 1
        # taps: [N, out_h, out_w, k*k, in_ch] from k*k static strided slices
        taps = jnp.stack(
            [
                xp[:, dr : dr + (out_h - 1) * s + 1 : s, dc : dc + (out_w - 1) * s + 1 : s, :]
                for dr in range(k)
                for dc in range(k)
            ],
            axis=3,
        )
        taps = taps.reshape(*taps.shape[:4], g, in_ch // g)
        # kernel [k,k,I/g,O] -> [k*k, I/g, g, O/g]; output groups are
        # contiguous blocks of O/g channels (torch grouped-conv semantics)
        wg = w.reshape(k * k, in_ch // g, g, self.features // g)
        y = jnp.einsum("nhwpgi,pigo->nhwgo", taps, wg)
        return y.reshape(y.shape[0], out_h, out_w, self.features)


def _conv(
    features: int,
    kernel: int,
    stride: int,
    padding: int,
    dtype: Any,
    name: str,
    groups: int = 1,
):
    """Bias-free conv with explicit torch-style symmetric padding."""
    if groups > 1:
        return GroupedConv(
            features=features,
            kernel=kernel,
            stride=stride,
            padding=padding,
            groups=groups,
            dtype=dtype,
            name=name,
        )
    return nn.Conv(
        features=features,
        kernel_size=(kernel, kernel),
        strides=(stride, stride),
        padding=((padding, padding), (padding, padding)),
        use_bias=False,
        dtype=dtype,
        param_dtype=jnp.float32,
        name=name,
    )


class BasicBlock(nn.Module):
    """Two 3x3 convs + identity (reference `nets/resnet_torch.py:35-75`)."""

    features: int
    stride: int = 1
    downsample: bool = False
    dtype: Any = jnp.bfloat16
    bn_axis: Any = None
    norm: str = "batch"

    @nn.compact
    def __call__(self, x: Array, train: bool) -> Array:
        identity = x
        out = _conv(self.features, 3, self.stride, 1, self.dtype, "conv1")(x)
        out = _norm(self.dtype, train, "bn1", self.bn_axis, self.norm)(out)
        out = nn.relu(out)
        out = _conv(self.features, 3, 1, 1, self.dtype, "conv2")(out)
        out = _norm(self.dtype, train, "bn2", self.bn_axis, self.norm)(out)
        if self.downsample:
            identity = _conv(self.features, 1, self.stride, 0, self.dtype, "downsample_conv")(x)
            identity = _norm(self.dtype, train, "downsample_bn", self.bn_axis, self.norm)(identity)
        return nn.relu(out + identity)


class Bottleneck(nn.Module):
    """1x1 -> 3x3 -> 1x1(x4) bottleneck (reference `nets/resnet_torch.py:78-123`;
    torchvision-style stride on the 3x3). ``groups``/``base_width`` give the
    ResNeXt / wide-ResNet variants of the reference's constructor table
    (`nets/resnet_torch.py:13-23,299-390`): the inner width is
    ``features * base_width/64 * groups`` and the 3x3 is grouped; the block
    output stays ``features * 4`` for every variant."""

    features: int  # bottleneck planes; output is features * 4
    stride: int = 1
    downsample: bool = False
    dtype: Any = jnp.bfloat16
    groups: int = 1
    base_width: int = 64
    bn_axis: Any = None
    expansion: int = 4
    norm: str = "batch"

    @nn.compact
    def __call__(self, x: Array, train: bool) -> Array:
        identity = x
        width = int(self.features * (self.base_width / 64.0)) * self.groups
        out = _conv(width, 1, 1, 0, self.dtype, "conv1")(x)
        out = _norm(self.dtype, train, "bn1", self.bn_axis, self.norm)(out)
        out = nn.relu(out)
        out = _conv(width, 3, self.stride, 1, self.dtype, "conv2", self.groups)(out)
        out = _norm(self.dtype, train, "bn2", self.bn_axis, self.norm)(out)
        out = nn.relu(out)
        out = _conv(self.features * self.expansion, 1, 1, 0, self.dtype, "conv3")(out)
        out = _norm(self.dtype, train, "bn3", self.bn_axis, self.norm)(out)
        if self.downsample:
            identity = _conv(
                self.features * self.expansion, 1, self.stride, 0, self.dtype, "downsample_conv"
            )(x)
            identity = _norm(self.dtype, train, "downsample_bn", self.bn_axis, self.norm)(identity)
        return nn.relu(out + identity)


# name -> (block class, blocks per stage, groups, width_per_group) — the full
# constructor table of reference `nets/resnet_torch.py:271-390` (resnet152 at
# :313, resnext50_32x4d/resnext101_32x8d at :327-350, wide_resnet50_2/101_2
# at :353-390).
_SPECS = {
    "resnet18": (BasicBlock, (2, 2, 2, 2), 1, 64),
    "resnet34": (BasicBlock, (3, 4, 6, 3), 1, 64),
    "resnet50": (Bottleneck, (3, 4, 6, 3), 1, 64),
    "resnet101": (Bottleneck, (3, 4, 23, 3), 1, 64),
    "resnet152": (Bottleneck, (3, 8, 36, 3), 1, 64),
    "resnext50_32x4d": (Bottleneck, (3, 4, 6, 3), 32, 4),
    "resnext101_32x8d": (Bottleneck, (3, 4, 23, 3), 32, 8),
    "wide_resnet50_2": (Bottleneck, (3, 4, 6, 3), 1, 128),
    "wide_resnet101_2": (Bottleneck, (3, 4, 23, 3), 1, 128),
}
_WIDTHS = (64, 128, 256, 512)


def _stage(
    arch: str,
    x: Array,
    features: int,
    n_blocks: int,
    stride: int,
    dtype: Any,
    train: bool,
    name: str,
    bn_axis: Any = None,
    remat: bool = False,
    norm: str = "batch",
) -> Array:
    block, _, groups, base_width = _spec(arch)
    # per-block jax.checkpoint: the backward pass recomputes each residual
    # block's activations instead of keeping them in HBM — trades ~1/3 more
    # FLOPs for activation memory, buying batch/backbone headroom at 600x600.
    # Parameter trees are unchanged (remat is a lifted transform).
    cls = nn.remat(block, static_argnums=(2,)) if remat else block
    out_ch = features * (4 if block is Bottleneck else 1)
    for i in range(n_blocks):
        s = stride if i == 0 else 1
        down = s != 1 or x.shape[-1] != out_ch
        kw = {"groups": groups, "base_width": base_width} if block is Bottleneck else {}
        x = cls(
            features=features,
            stride=s,
            downsample=down,
            dtype=dtype,
            name=f"{name}.{i}",
            bn_axis=bn_axis,
            norm=norm,
            **kw,
        )(x, train)
    return x


class ResNetTrunk(nn.Module):
    """conv1..layer3: the shared stride-16 feature extractor
    (reference split at `nets/resnet_torch.py:399-401`).

    Input NHWC [N, H, W, 3]; output [N, ceil(H/16), ceil(W/16), C] with
    C = 256 (resnet18/34) or 1024 (resnet50/101).

    ``stem='cifar'`` swaps the 7x7/s2 + maxpool ImageNet stem for a 3x3/s1
    conv — the reference's hand-written CIFAR variant (`nets/resnet.py:
    109-114`), used for small-image backbone pretraining; output stride is
    then 4 instead of 16.
    """

    arch: str = "resnet18"
    dtype: Any = jnp.bfloat16
    stem: str = "imagenet"  # "imagenet" | "cifar"
    bn_axis: Any = None  # mesh axis for sync-BN under shard_map
    remat: bool = False  # jax.checkpoint each residual block
    # run every BN with its stored statistics even in train mode (no
    # batch-stats reductions: each BN becomes a fusable affine).
    # DELIBERATE deviation from torchvision's FrozenBatchNorm2d: the
    # affine scale/bias stay TRAINABLE here (torchvision freezes them as
    # buffers); this is the affine-fine-tuning variant, chosen so the
    # optimizer/param tree is identical with the flag on or off.
    frozen_bn: bool = False
    norm: str = "batch"  # "batch" | "group" — see _norm

    @nn.compact
    def __call__(self, x: Array, train: bool = False) -> Array:
        depths = _spec(self.arch)[1]
        train = train and not self.frozen_bn  # `train` only gates BN here
        x = x.astype(self.dtype)
        if self.stem == "cifar":
            x = _conv(64, 3, 1, 1, self.dtype, "conv1")(x)
            x = _norm(self.dtype, train, "bn1", self.bn_axis, self.norm)(x)
            x = nn.relu(x)
        else:
            x = _conv(64, 7, 2, 3, self.dtype, "conv1")(x)
            x = _stem_pool(x, self.dtype, train, self.bn_axis, self.norm)
        ax, rm, nm = self.bn_axis, self.remat, self.norm
        x = _stage(self.arch, x, _WIDTHS[0], depths[0], 1, self.dtype, train, "layer1", ax, rm, nm)
        x = _stage(self.arch, x, _WIDTHS[1], depths[1], 2, self.dtype, train, "layer2", ax, rm, nm)
        x = _stage(self.arch, x, _WIDTHS[2], depths[2], 2, self.dtype, train, "layer3", ax, rm, nm)
        return x


class ResNetTail(nn.Module):
    """layer4 + global average pool: the reference's `classifier`
    (`nets/resnet_torch.py:403`), applied to pooled ROI crops by the
    detection head (`nets/heads.py:51-52`).

    Input NHWC [R, h, w, C_trunk]; output [R, C_out] with C_out = 512
    (resnet18/34) or 2048 (resnet50/101).
    """

    arch: str = "resnet18"
    dtype: Any = jnp.bfloat16
    bn_axis: Any = None
    frozen_bn: bool = False  # see ResNetTrunk.frozen_bn
    norm: str = "batch"  # see ResNetTrunk.norm

    @nn.compact
    def __call__(self, x: Array, train: bool = False) -> Array:
        depths = _spec(self.arch)[1]
        train = train and not self.frozen_bn  # `train` only gates BN here
        x = x.astype(self.dtype)
        x = _stage(
            self.arch, x, _WIDTHS[3], depths[3], 2, self.dtype, train, "layer4",
            self.bn_axis, norm=self.norm,
        )
        return jnp.mean(x, axis=(1, 2))  # global avg pool == AdaptiveAvgPool2d(1)


class ResNetClassifier(nn.Module):
    """Full classifier (trunk + tail + fc) — capability parity with the
    reference's standalone ResNets: the torchvision-style ImageNet model
    (`nets/resnet_torch.py:126-258`) with the default stem, and the
    hand-written CIFAR variant the author pretrained to ~0.93 on CIFAR10
    (`nets/resnet.py`, `readme.md:15`) with ``stem='cifar'``. Used for
    backbone pretraining/verification rather than detection; the
    trunk/tail split matches the detector's, so pretrained weights carry
    over directly."""

    arch: str = "resnet18"
    num_classes: int = 1000
    dtype: Any = jnp.bfloat16
    stem: str = "imagenet"
    norm: str = "batch"  # see ResNetTrunk.norm — "group" pretrains the
    # GN backbone whose checkpoint grafts onto a norm="group" detector

    @nn.compact
    def __call__(self, x: Array, train: bool = False) -> Array:
        x = ResNetTrunk(
            self.arch, self.dtype, self.stem, norm=self.norm, name="trunk"
        )(x, train)
        x = ResNetTail(self.arch, self.dtype, norm=self.norm, name="tail")(x, train)
        return nn.Dense(self.num_classes, param_dtype=jnp.float32, name="fc")(
            x.astype(jnp.float32)
        )


def _spec(arch: str):
    try:
        return _SPECS[arch]
    except KeyError:
        raise ValueError(f"unknown resnet arch {arch!r}; choices: {sorted(_SPECS)}") from None


def trunk_channels(arch: str) -> int:
    return 256 * (4 if _spec(arch)[0] is Bottleneck else 1)


def tail_channels(arch: str) -> int:
    return 512 * (4 if _spec(arch)[0] is Bottleneck else 1)
