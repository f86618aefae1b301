"""Core layer library — TPU-native equivalents of the reference's gserver layers.

The reference implements ~110 C++ ``Layer`` classes
(``/root/reference/paddle/gserver/layers/``; Python surface
``python/paddle/trainer_config_helpers/layers.py``). Here each layer is a thin
:class:`~paddle_tpu.core.module.Module` emitting jax.numpy/lax ops; XLA handles
fusion and MXU tiling, so layers carry no device-specific code (the analog of the
reference's CPU/GPU kernel pairs collapsing into one implementation).

Conventions:
  - Images are NHWC (TPU-native layout; the reference is NCHW — transposed at
    the data boundary). Conv kernels are HWIO.
  - Dense compute may run in bf16 per the active dtype policy; params stay f32.
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence, Tuple, Union

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..core import initializers as I
from ..core.dtypes import current_policy
from ..core.module import Module, current_rng
from . import activations

__all__ = [
    "Linear", "Embedding", "Conv2D", "Conv2DTranspose", "DepthwiseConv2D",
    "Pool2D", "GlobalPool", "BatchNorm", "LayerNorm", "GroupNorm", "Dropout",
    "Maxout", "Bias", "ScaleShift", "CrossChannelNorm", "SpatialPyramidPool",
    "FeatureMapExpand", "BlockExpand", "Interpolation", "Multiplex", "RowL2Norm",
    "SumToOneNorm", "DataNorm", "L2Distance", "CosSim", "OuterProd", "ConvShift",
    "SlopeIntercept", "Pad2D", "Crop2D", "Resize", "Rotate", "Addto", "Concat",
    "MixedLayer", "FullMatrixProjection", "TableProjection", "IdentityProjection",
    "DotMulProjection", "ContextProjection", "CrossMapNormal", "RowConv",
    "Conv3D", "Conv3DTranspose", "Pool3D", "SelectiveFC", "SamplingId",
    "ScaleSubRegion", "Power", "Scaling", "DotProd", "ConvexCombination",
    "CosSimVecMat", "BilinearInterp", "EosIdCheck", "PRelu",
    "ScalingProjection", "SliceProjection", "TransposedFullMatrixProjection",
    "SwitchOrder", "MaxPoolWithMask", "RMSNorm", "GatedFFN",
]

Pair = Union[int, Tuple[int, int]]


def _pair(v: Pair) -> Tuple[int, int]:
    return (v, v) if isinstance(v, int) else tuple(v)


def _conv_padding(padding):
    """Normalize padding: "SAME"/"VALID", int p, (pad_h, pad_w), or explicit
    [(lo,hi),(lo,hi)] — matching the kernel/stride (h, w) convention."""
    if isinstance(padding, str):
        return padding
    if isinstance(padding, int):
        return [(padding, padding), (padding, padding)]
    padding = list(padding)
    if all(isinstance(p, (list, tuple)) for p in padding):
        return [tuple(p) for p in padding]
    ph, pw = padding
    return [(ph, ph), (pw, pw)]


class Linear(Module):
    """Fully-connected layer (reference: ``FullyConnectedLayer``,
    ``gserver/layers/FullyConnectedLayer.cpp``; fluid ``mul_op`` + bias)."""

    def __init__(self, features: int, act="", use_bias: bool = True,
                 w_init=I.fan_in_uniform, b_init=I.zeros, name=None):
        super().__init__(name=name)
        self.features = features
        self.act = activations.get(act)
        self.use_bias = use_bias
        self.w_init = w_init
        self.b_init = b_init

    def forward(self, x):
        pol = current_policy()
        w = self.param("w", self.w_init, (x.shape[-1], self.features))
        y = jnp.dot(pol.cast_compute(x), pol.cast_compute(w),
                    preferred_element_type=pol.accum_dtype)
        if self.use_bias:
            b = self.param("b", self.b_init, (self.features,))
            y = y + b
        return self.act(y)


class Embedding(Module):
    """Embedding lookup (reference: ``TableProjection``,
    ``gserver/layers/TableProjection.cpp``; fluid ``lookup_table_op``).
    ``ids`` may be any-int shape; output appends the embedding dim.
    Out-of-range ids (e.g. padding = -1) return zeros."""

    def __init__(self, vocab: int, dim: int, w_init=None, name=None):
        super().__init__(name=name)
        self.vocab = vocab
        self.dim = dim
        self.w_init = w_init or I.normal(1.0 / np.sqrt(dim))

    def table(self):
        """Fetch the table from within this module's own scope (callable from a
        parent's forward — pushes this module's path so the param is shared
        with lookups, enabling tied softmax weights)."""
        with self.scope():
            return self.param("w", self.w_init, (self.vocab, self.dim))

    def forward(self, ids):
        w = self.param("w", self.w_init, (self.vocab, self.dim))
        valid = (ids >= 0) & (ids < self.vocab)
        safe = jnp.clip(ids, 0, self.vocab - 1)
        out = jnp.take(w, safe, axis=0)
        return out * valid[..., None].astype(out.dtype)

    def attend(self, x):
        """Project activations back onto the table (tied softmax weights)."""
        return jnp.dot(x, self.table().T)


# How 1x1 convs lower: "conv" = lax.conv_general_dilated; "matmul" =
# reshape + dot (XLA's matmul path — different tiling than its conv path);
# "pallas" = matmul forward + Pallas dW reduction kernel
# (nn/pallas_conv.py).
_CONV1X1_IMPL = "conv"


def set_conv1x1_impl(impl: str) -> str:
    """Select the 1x1-conv lowering globally; returns the previous value.

    TRACE-TIME semantics: the global is read when a step is traced, and jit
    caches do NOT key on it — any function already jitted keeps the lowering
    it was traced with. Call this BEFORE building/jitting the step; toggling
    after compilation silently has no effect on cached executables."""
    global _CONV1X1_IMPL
    assert impl in ("conv", "matmul", "pallas"), impl
    prev, _CONV1X1_IMPL = _CONV1X1_IMPL, impl
    return prev


class Conv2D(Module):
    """2-D convolution, NHWC/HWIO (reference: ``ExpandConvLayer`` /
    ``CudnnConvLayer``, ``gserver/layers/ExpandConvLayer.cpp``; function-layer
    ``GemmConvOp``). XLA lowers this onto the MXU directly; 1x1 convs can
    route through the matmul/Pallas path (:func:`set_conv1x1_impl`)."""

    def __init__(self, features: int, kernel: Pair, stride: Pair = 1,
                 padding="SAME", dilation: Pair = 1, groups: int = 1, act="",
                 use_bias: bool = True, w_init=I.msra_normal, name=None):
        super().__init__(name=name)
        self.features = features
        self.kernel = _pair(kernel)
        self.stride = _pair(stride)
        self.padding = _conv_padding(padding)
        self.dilation = _pair(dilation)
        self.groups = groups
        self.act = activations.get(act)
        self.use_bias = use_bias
        self.w_init = w_init

    def forward(self, x):
        pol = current_policy()
        kh, kw = self.kernel
        cin = x.shape[-1]
        w = self.param("w", self.w_init,
                       (kh, kw, cin // self.groups, self.features))
        # Output stays in compute dtype (the MXU accumulates f32 internally
        # for bf16 operands); upcasting via preferred_element_type would break
        # the conv rhs-transpose rule, which requires operand dtypes to match.
        # for a 1x1 kernel SAME == VALID == zero padding; only explicit
        # nonzero padding keeps the conv path
        pad_free = (self.padding in ("SAME", "VALID")
                    or all(p == (0, 0) for p in self.padding))
        if ((kh, kw) == (1, 1) and self.dilation == (1, 1)
                and self.groups == 1 and pad_free
                and _CONV1X1_IMPL != "conv"):
            from . import pallas_conv
            xc = pol.cast_compute(x)
            wc = pol.cast_compute(w).reshape(cin, self.features)
            if _CONV1X1_IMPL == "pallas":
                y = pallas_conv.conv1x1_strided(xc, wc, self.stride)
            else:
                sh, sw = self.stride
                if (sh, sw) != (1, 1):
                    xc = xc[:, ::sh, ::sw, :]
                b_, h_, w_, _ = xc.shape
                y = (xc.reshape(b_ * h_ * w_, cin) @ wc).reshape(
                    b_, h_, w_, self.features)
        elif ((kh, kw) == (7, 7) and self.stride == (2, 2)
                and self.padding == "SAME" and self.dilation == (1, 1)
                and self.groups == 1 and cin <= 4
                and x.shape[1] % 2 == 0 and x.shape[2] % 2 == 0):
            # Tiny-C_in strided stem (the classic 7x7/2 ImageNet stem): the
            # MXU pads 3 input channels to a full tile and runs at ~12%.
            # EXACT space-to-depth rewrite (input 2x2 patches -> channels,
            # end-zero-padded weights re-indexed w2[a,b,(dy,dx,c)] =
            # w[2a+dy, 2b+dx, c], conv 4x4/1 pad (1,2)): same math to f32
            # roundoff, 1.9x faster measured (PERF.md (older installation) "Round
            # 5: 3x3 campaign"; the MLPerf-ResNet TPU trick, done
            # weight-compatibly).
            xc, wc = pol.cast_compute(x), pol.cast_compute(w)
            n, h, ww_, c = xc.shape
            x2 = xc.reshape(n, h // 2, 2, ww_ // 2, 2, c)
            x2 = x2.transpose(0, 1, 3, 2, 4, 5).reshape(
                n, h // 2, ww_ // 2, 4 * c)
            wp = jnp.pad(wc, ((0, 1), (0, 1), (0, 0), (0, 0)))
            w2 = wp.reshape(4, 2, 4, 2, c, self.features)
            w2 = w2.transpose(0, 2, 1, 3, 4, 5).reshape(
                4, 4, 4 * c, self.features)
            y = lax.conv_general_dilated(
                x2, w2, window_strides=(1, 1), padding=[(1, 2), (1, 2)],
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
        else:
            y = lax.conv_general_dilated(
                pol.cast_compute(x), pol.cast_compute(w),
                window_strides=self.stride, padding=self.padding,
                rhs_dilation=self.dilation, feature_group_count=self.groups,
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
        if self.use_bias:
            y = y + self.param("b", I.zeros, (self.features,)).astype(y.dtype)
        return self.act(y)


class DepthwiseConv2D(Conv2D):
    """Depthwise conv (reference: ``DepthwiseConvOp``, function layer)."""

    def __init__(self, multiplier: int, kernel: Pair, stride: Pair = 1,
                 padding="SAME", act="", use_bias=True, name=None):
        # features resolved at call time: cin * multiplier, groups = cin
        super().__init__(features=multiplier, kernel=kernel, stride=stride,
                         padding=padding, act=act, use_bias=use_bias, name=name)
        self.multiplier = multiplier

    def forward(self, x):
        pol = current_policy()
        kh, kw = self.kernel
        cin = x.shape[-1]
        features = cin * self.multiplier
        w = self.param("w", self.w_init, (kh, kw, 1, features))
        y = lax.conv_general_dilated(
            pol.cast_compute(x), pol.cast_compute(w),
            window_strides=self.stride, padding=self.padding,
            rhs_dilation=self.dilation, feature_group_count=cin,
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        if self.use_bias:
            y = y + self.param("b", I.zeros, (features,)).astype(y.dtype)
        return self.act(y)


class Conv2DTranspose(Module):
    """Transposed conv (reference: ``ExpandConvTransLayer``, ``DeConv3DLayer``)."""

    def __init__(self, features: int, kernel: Pair, stride: Pair = 1,
                 padding="SAME", act="", use_bias=True,
                 w_init=I.msra_normal, name=None):
        super().__init__(name=name)
        self.features = features
        self.kernel = _pair(kernel)
        self.stride = _pair(stride)
        self.padding = _conv_padding(padding)
        self.act = activations.get(act)
        self.use_bias = use_bias
        self.w_init = w_init

    def forward(self, x):
        pol = current_policy()
        kh, kw = self.kernel
        w = self.param("w", self.w_init, (kh, kw, x.shape[-1], self.features))
        y = lax.conv_transpose(
            pol.cast_compute(x), pol.cast_compute(w),
            strides=self.stride, padding=self.padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        if self.use_bias:
            y = y + self.param("b", I.zeros, (self.features,))
        return self.act(pol.cast_accum(y))


class Pool2D(Module):
    """Max/avg pooling (reference: ``PoolLayer``/``CudnnPoolLayer``,
    ``gserver/layers/PoolLayer.cpp``; function ``Pool2DOp``)."""

    def __init__(self, kind: str, window: Pair, stride: Optional[Pair] = None,
                 padding="VALID", name=None):
        super().__init__(name=name)
        assert kind in ("max", "avg")
        self.kind = kind
        self.window = _pair(window)
        self.stride = _pair(stride if stride is not None else window)
        self.padding = padding

    def forward(self, x):
        wh, ww = self.window
        sh, sw = self.stride
        dims = (1, wh, ww, 1)
        strides = (1, sh, sw, 1)
        if self.kind == "max":
            return lax.reduce_window(x, -jnp.inf, lax.max, dims, strides,
                                     self.padding)
        s = lax.reduce_window(x, 0.0, lax.add, dims, strides, self.padding)
        if self.padding == "VALID":
            return s / (wh * ww)
        ones = jnp.ones(x.shape[:3] + (1,), x.dtype)
        cnt = lax.reduce_window(ones, 0.0, lax.add, dims, strides, self.padding)
        return s / jnp.maximum(cnt, 1.0)


class GlobalPool(Module):
    """Global spatial pooling to [N, C]."""

    def __init__(self, kind: str = "avg", name=None):
        super().__init__(name=name)
        assert kind in ("max", "avg"), kind
        self.kind = kind

    def forward(self, x):
        return (jnp.max if self.kind == "max" else jnp.mean)(x, axis=(1, 2))


@jax.custom_vjp
def _bn_train_norm(x, mean, inv, gamma, beta):
    """Training-mode BN normalization with a hand-written VJP.

    The autodiff backward of the mean/var formulation emits 3-4 reductions
    over the activation per BN layer; the closed-form BN backward needs
    exactly two (sum(dy), sum(dy*xhat)) plus one elementwise pass:

        dx = gamma*inv * (dy - sum(dy)/n - xhat*sum(dy*xhat)/n)

    This is the *total* derivative (the mean/inv dependence on x is folded
    in), so the bwd returns zero cotangents for mean/inv and the upstream
    stats-backward graph dead-code-eliminates. (On the ResNet-50 step XLA's
    fusion already absorbed most of the difference — measured perf-neutral,
    experiments/ round 3 — but the backward HLO is structurally minimal and
    numerically pinned by test_batchnorm_custom_vjp_matches_autodiff.) Do
    not differentiate through mean/inv from elsewhere — they are treated as
    x-derived here.
    """
    xhat = (x - mean.astype(x.dtype)) * inv.astype(x.dtype)
    return xhat * gamma.astype(x.dtype) + beta.astype(x.dtype)


def _bn_train_norm_fwd(x, mean, inv, gamma, beta):
    return _bn_train_norm(x, mean, inv, gamma, beta), (x, mean, inv, gamma)


def _bn_train_norm_bwd(res, dy):
    x, mean, inv, gamma = res
    axes = tuple(range(x.ndim - 1))
    n = x.size // x.shape[-1]
    xhat = (x - mean.astype(x.dtype)) * inv.astype(x.dtype)
    dbeta = jnp.sum(dy, axis=axes, dtype=jnp.float32)
    dgamma = jnp.sum(dy * xhat, axis=axes, dtype=jnp.float32)
    scale = (gamma * inv).astype(x.dtype)
    dx = scale * (dy - (dbeta / n).astype(x.dtype)
                  - xhat * (dgamma / n).astype(x.dtype))
    return (dx, jnp.zeros_like(mean), jnp.zeros_like(inv),
            dgamma.astype(gamma.dtype), dbeta.astype(gamma.dtype))


_bn_train_norm.defvjp(_bn_train_norm_fwd, _bn_train_norm_bwd)


class BatchNorm(Module):
    """Batch normalization with running stats (reference:
    ``BatchNormalizationLayer``/``CudnnBatchNormLayer``,
    ``gserver/layers/BatchNormalizationLayer.cpp``; running mean/var kept as
    non-trainable state, the analog of PARAMETER_VALUE-typed stat buffers)."""

    def __init__(self, momentum: float = 0.9, eps: float = 1e-5,
                 use_scale_shift: bool = True, name=None):
        super().__init__(name=name)
        self.momentum = momentum
        self.eps = eps
        self.use_scale_shift = use_scale_shift

    def forward(self, x, train: bool = False):
        c = x.shape[-1]
        axes = tuple(range(x.ndim - 1))
        mean_s = self.state("mean", I.zeros, (c,))
        var_s = self.state("var", I.ones, (c,))
        # Moment statistics in float32 regardless of the compute policy
        # (bf16 batch moments are too coarse); the normalization itself runs
        # in the activation dtype — see below. Moments use the one-pass
        # E[x^2]-E[x]^2 form: sum and sum-of-squares are independent
        # reductions XLA multi-output-fuses into a single read of x, where
        # mean-then-var would read the activation twice (measured ~2x BN
        # stat cost on the ResNet-50 step, v5e).
        xf = x.astype(jnp.float32)
        if train:
            n = x.size // c
            s1 = jnp.sum(xf, axis=axes)
            s2 = jnp.sum(xf * xf, axis=axes)
            mean = s1 / n
            var = jnp.maximum(s2 / n - mean * mean, 0.0)
            m = self.momentum
            self.update_state("mean", m * mean_s + (1 - m) * mean)
            self.update_state("var", m * var_s + (1 - m) * var)
        else:
            mean, var = mean_s, var_s
        # Normalization itself rides the activation dtype (halves the HBM
        # traffic of the fused elementwise under bf16); only the moment
        # reductions above need f32.
        inv = lax.rsqrt(var + self.eps)
        if train and self.use_scale_shift:
            # custom-VJP path: closed-form BN backward (2 reductions
            # instead of autodiff's 3-4 — see _bn_train_norm)
            return _bn_train_norm(x, mean, inv,
                                  self.param("scale", I.ones, (c,)),
                                  self.param("shift", I.zeros, (c,)))
        y = (x - mean.astype(x.dtype)) * inv.astype(x.dtype)
        if self.use_scale_shift:
            y = y * self.param("scale", I.ones, (c,)).astype(x.dtype) + \
                self.param("shift", I.zeros, (c,)).astype(x.dtype)
        return y


class LayerNorm(Module):
    """Layer normalization (beyond the reference's set; required by the modern
    attention stack — SURVEY.md §5 notes transformer-era additions)."""

    def __init__(self, eps: float = 1e-6, use_scale: bool = True,
                 use_bias: bool = True, name=None):
        super().__init__(name=name)
        self.eps = eps
        self.use_scale = use_scale
        self.use_bias = use_bias

    def forward(self, x):
        dtype = x.dtype
        x32 = x.astype(jnp.float32)
        mean = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.var(x32, axis=-1, keepdims=True)
        y = (x32 - mean) * lax.rsqrt(var + self.eps)
        c = x.shape[-1]
        if self.use_scale:
            y = y * self.param("scale", I.ones, (c,))
        if self.use_bias:
            y = y + self.param("bias", I.zeros, (c,))
        return y.astype(dtype)


class RMSNorm(Module):
    """Root-mean-square normalization with a learned scale and no bias or
    mean: ``x / sqrt(mean(x^2) + eps) * scale``, computed in float32
    whatever ``x`` is and handed back in ``x``'s dtype."""

    def __init__(self, eps: float = 1e-5, name=None):
        super().__init__(name=name)
        self.eps = eps

    def forward(self, x):
        x32 = x.astype(jnp.float32)
        ms = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        scale = self.param("scale", I.ones, (x.shape[-1],))
        return (x32 * lax.rsqrt(ms + self.eps) * scale).astype(x.dtype)


class GatedFFN(Module):
    """SiLU-gated feed-forward, no biases:
    ``down(silu(gate(x)) * up(x))`` with ``gate``, ``up`` of width
    ``hidden`` and ``down`` back to ``dim``."""

    def __init__(self, dim: int, hidden: int, w_init=I.fan_in_uniform,
                 name=None):
        super().__init__(name=name)
        self.gate = Linear(hidden, use_bias=False, w_init=w_init)
        self.up = Linear(hidden, use_bias=False, w_init=w_init)
        self.down = Linear(dim, use_bias=False, w_init=w_init)

    def forward(self, x):
        return self.down(jax.nn.silu(self.gate(x)) * self.up(x))


class GroupNorm(Module):
    def __init__(self, groups: int = 32, eps: float = 1e-5, name=None):
        super().__init__(name=name)
        self.groups = groups
        self.eps = eps

    def forward(self, x):
        c = x.shape[-1]
        g = min(self.groups, c)
        if c % g:
            raise ValueError(f"GroupNorm: {c} channels not divisible by "
                             f"{g} groups")
        shape = x.shape[:-1] + (g, c // g)
        xg = x.reshape(shape)
        axes = tuple(range(1, x.ndim - 1)) + (x.ndim,)
        mean = jnp.mean(xg, axis=axes, keepdims=True)
        var = jnp.var(xg, axis=axes, keepdims=True)
        y = ((xg - mean) * lax.rsqrt(var + self.eps)).reshape(x.shape)
        return y * self.param("scale", I.ones, (c,)) + \
            self.param("bias", I.zeros, (c,))


class Dropout(Module):
    """Inverted dropout (reference: ``drop_rate`` layer attr applied via
    ``Layer::forwardDropOut``, ``gserver/layers/Layer.cpp``)."""

    def __init__(self, rate: float, name=None):
        super().__init__(name=name)
        self.rate = rate

    def forward(self, x, train: bool = False):
        if not train or self.rate <= 0.0:
            return x
        keep = 1.0 - self.rate
        mask = jax.random.bernoulli(current_rng("dropout"), keep, x.shape)
        return jnp.where(mask, x / keep, 0.0)


class Maxout(Module):
    """Maxout over channel groups (reference: ``MaxOutLayer``)."""

    def __init__(self, groups: int, name=None):
        super().__init__(name=name)
        self.groups = groups

    def forward(self, x):
        c = x.shape[-1]
        return jnp.max(x.reshape(x.shape[:-1] + (c // self.groups, self.groups)),
                       axis=-1)


class Bias(Module):
    """Standalone bias (reference: ``BiasLayer`` / shared biases)."""

    def forward(self, x):
        return x + self.param("b", I.zeros, (x.shape[-1],))


class ScaleShift(Module):
    """Per-channel learned scale+shift (reference: ``ScaleShiftLayer``)."""

    def forward(self, x):
        return x * self.param("scale", I.ones, (x.shape[-1],)) + \
            self.param("shift", I.zeros, (x.shape[-1],))


class CrossChannelNorm(Module):
    """L2 norm across channels with learned per-channel scale
    (reference: ``CrossChannelNormLayer``, SSD's Norm layer)."""

    def forward(self, x):
        scale = self.param("scale", I.constant(20.0), (x.shape[-1],))
        norm = jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-12)
        return x / norm * scale


class SpatialPyramidPool(Module):
    """SPP (reference: ``SpatialPyramidPoolLayer.cpp``) — concat of pyramid
    max-pools to a fixed-size vector regardless of input HW."""

    def __init__(self, levels: int = 3, kind: str = "max", name=None):
        super().__init__(name=name)
        self.levels = levels
        self.kind = kind

    def forward(self, x):
        n, h, w, c = x.shape
        outs = []
        for lvl in range(self.levels):
            bins = 2 ** lvl
            # Static pyramid: split into bins x bins cells (requires h, w >= bins)
            hs = [h * i // bins for i in range(bins + 1)]
            ws = [w * i // bins for i in range(bins + 1)]
            for i in range(bins):
                for j in range(bins):
                    cell = x[:, hs[i]:hs[i + 1], ws[j]:ws[j + 1], :]
                    red = jnp.max if self.kind == "max" else jnp.mean
                    outs.append(red(cell, axis=(1, 2)))
        return jnp.concatenate(outs, axis=-1)


class FeatureMapExpand(Module):
    """Expand [N, C] vector across spatial dims of a reference map
    (reference: ``FeatureMapExpandLayer``)."""

    def __init__(self, as_map_of=None, name=None):
        super().__init__(name=name)

    def forward(self, x, like):
        return jnp.broadcast_to(x[:, None, None, :],
                                like.shape[:3] + (x.shape[-1],))


class BlockExpand(Module):
    """im2col as a layer (reference: ``BlockExpandLayer`` — conv patches to
    sequence, used for OCR)."""

    def __init__(self, block: Pair, stride: Pair, padding="VALID", name=None):
        super().__init__(name=name)
        self.block = _pair(block)
        self.stride = _pair(stride)
        self.padding = padding

    def forward(self, x):
        bh, bw = self.block
        patches = lax.conv_general_dilated_patches(
            x, filter_shape=(bh, bw), window_strides=self.stride,
            padding=self.padding, dimension_numbers=("NHWC", "HWIO", "NHWC"))
        n, oh, ow, d = patches.shape
        return patches.reshape(n, oh * ow, d)


class Interpolation(Module):
    """out = w*a + (1-w)*b with per-sample weight (reference:
    ``InterpolationLayer``)."""

    def forward(self, w, a, b):
        w = w.reshape(w.shape[0], *([1] * (a.ndim - 1)))
        return w * a + (1.0 - w) * b


class Multiplex(Module):
    """Row-wise select among K inputs by index (reference: ``MultiplexLayer``)."""

    def forward(self, index, *xs):
        stacked = jnp.stack(xs, axis=0)          # [K, N, ...]
        return jnp.take_along_axis(
            stacked, index.reshape(1, -1, *([1] * (stacked.ndim - 2))),
            axis=0)[0]


class RowL2Norm(Module):
    """Row-wise L2 normalize (reference: ``RowL2NormLayer``)."""

    def forward(self, x):
        return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-12)


class SumToOneNorm(Module):
    """Row-wise sum-to-one normalize (reference: ``SumToOneNormLayer``)."""

    def forward(self, x):
        return x / jnp.maximum(jnp.sum(x, axis=-1, keepdims=True), 1e-12)


class DataNorm(Module):
    """Input feature normalization from precomputed stats (reference:
    ``DataNormLayer`` — z-score / min-max / decimal scaling)."""

    def __init__(self, strategy: str = "z-score", name=None):
        super().__init__(name=name)
        self.strategy = strategy

    def forward(self, x):
        c = x.shape[-1]
        if self.strategy == "z-score":
            mean = self.state("mean", I.zeros, (c,))
            std = self.state("std", I.ones, (c,))
            return (x - mean) / jnp.maximum(std, 1e-12)
        if self.strategy == "min-max":
            mn = self.state("min", I.zeros, (c,))
            mx = self.state("max", I.ones, (c,))
            return (x - mn) / jnp.maximum(mx - mn, 1e-12)
        raise ValueError(self.strategy)


class L2Distance(Module):
    """Row-wise L2 distance between two inputs (reference: ``L2DistanceLayer``)."""

    def forward(self, a, b):
        return jnp.sqrt(jnp.sum((a - b) ** 2, axis=-1, keepdims=True) + 1e-12)


class CosSim(Module):
    """Row-wise cosine similarity * scale (reference: ``CosSimLayer``,
    function ``CosSimOp``)."""

    def __init__(self, scale: float = 1.0, name=None):
        super().__init__(name=name)
        self.scale = scale

    def forward(self, a, b):
        na = jnp.sqrt(jnp.sum(a * a, axis=-1) + 1e-12)
        nb = jnp.sqrt(jnp.sum(b * b, axis=-1) + 1e-12)
        return (self.scale * jnp.sum(a * b, axis=-1) / (na * nb))[..., None]


class OuterProd(Module):
    """Row-wise outer product flattened (reference: ``OuterProdLayer``)."""

    def forward(self, a, b):
        return (a[:, :, None] * b[:, None, :]).reshape(a.shape[0], -1)


class ConvShift(Module):
    """Circular 1-D correlation of rows (reference: ``ConvShiftLayer`` — NTM
    shift addressing)."""

    def forward(self, a, b):
        n, m = a.shape
        k = b.shape[-1]
        half = k // 2
        idx = (jnp.arange(m)[:, None] + jnp.arange(-half, k - half)[None, :]) % m
        gathered = a[:, idx]                     # [N, M, K]
        return jnp.einsum("nmk,nk->nm", gathered, b)


class SlopeIntercept(Module):
    """y = slope*x + intercept, fixed scalars (reference:
    ``SlopeInterceptLayer``)."""

    def __init__(self, slope: float = 1.0, intercept: float = 0.0, name=None):
        super().__init__(name=name)
        self.slope = slope
        self.intercept = intercept

    def forward(self, x):
        return self.slope * x + self.intercept


class Pad2D(Module):
    """Zero-pad NHWC (reference: ``PadLayer``, function ``PadOp``)."""

    def __init__(self, pad: Sequence[int], name=None):
        super().__init__(name=name)
        self.pad = pad  # (top, bottom, left, right)

    def forward(self, x):
        t, b, l, r = self.pad
        return jnp.pad(x, ((0, 0), (t, b), (l, r), (0, 0)))


class Crop2D(Module):
    """Static crop NHWC (reference: ``CropLayer``, function ``CropOp``)."""

    def __init__(self, offset: Tuple[int, int], size: Tuple[int, int], name=None):
        super().__init__(name=name)
        self.offset = offset
        self.size = size

    def forward(self, x):
        (oh, ow), (h, w) = self.offset, self.size
        return x[:, oh:oh + h, ow:ow + w, :]


class Resize(Module):
    """Reshape rows to a new width (reference: ``ResizeLayer``)."""

    def __init__(self, size: int, name=None):
        super().__init__(name=name)
        self.size = size

    def forward(self, x):
        return x.reshape(-1, self.size)


class Rotate(Module):
    """Rotate feature maps 90° (reference: ``RotateLayer``)."""

    def forward(self, x):
        return jnp.rot90(x, k=1, axes=(1, 2))


class Addto(Module):
    """Elementwise sum of inputs + optional bias/activation (reference:
    ``AddtoLayer``)."""

    def __init__(self, act="", use_bias: bool = False, name=None):
        super().__init__(name=name)
        self.act = activations.get(act)
        self.use_bias = use_bias

    def forward(self, *xs):
        y = xs[0]
        for x in xs[1:]:
            y = y + x
        if self.use_bias:
            y = y + self.param("b", I.zeros, (y.shape[-1],))
        return self.act(y)


class Concat(Module):
    """Feature concat (reference: ``ConcatenateLayer``)."""

    def __init__(self, axis: int = -1, act="", name=None):
        super().__init__(name=name)
        self.axis = axis
        self.act = activations.get(act)

    def forward(self, *xs):
        return self.act(jnp.concatenate(xs, axis=self.axis))


# ---------------------------------------------------------------------------
# MixedLayer & projections — the reference's composable projection system
# (``gserver/layers/MixedLayer.cpp`` + projections; config surface
# ``trainer_config_helpers/layers.py mixed_layer``). A MixedLayer sums the
# outputs of K projections, then bias + activation.
# ---------------------------------------------------------------------------

class FullMatrixProjection(Module):
    """Dense projection (reference: ``FullMatrixProjection.cpp``)."""

    def __init__(self, features: int, w_init=I.fan_in_uniform, name=None):
        super().__init__(name=name)
        self.features = features
        self.w_init = w_init

    def forward(self, x):
        w = self.param("w", self.w_init, (x.shape[-1], self.features))
        return jnp.dot(x, w)


class TableProjection(Module):
    """Embedding projection (reference: ``TableProjection.cpp``)."""

    def __init__(self, vocab: int, dim: int, name=None):
        super().__init__(name=name)
        self.emb = Embedding(vocab, dim, name="table")

    def forward(self, ids):
        return self.emb(ids)


class IdentityProjection(Module):
    """Identity / scaled identity (reference: ``IdentityProjection.cpp``)."""

    def __init__(self, scale: float = 1.0, offset: int = 0, size=None, name=None):
        super().__init__(name=name)
        self.scale = scale
        self.offset = offset
        self.size = size

    def forward(self, x):
        if self.size is not None:
            x = x[..., self.offset:self.offset + self.size]
        return self.scale * x


class DotMulProjection(Module):
    """Elementwise learned-weight product (reference: ``DotMulProjection.cpp``)."""

    def forward(self, x):
        w = self.param("w", I.uniform(1.0), (x.shape[-1],))
        return x * w


class ContextProjection(Module):
    """Sliding context window concat over time (reference:
    ``ContextProjection.cpp``; function ``ContextProjectionOp``) — concatenates
    [t+start, t+start+len) frames per step; out-of-range frames are zero (or
    trainable boundary vectors when ``trainable_pads``)."""

    def __init__(self, context_len: int, context_start: Optional[int] = None,
                 trainable_pads: bool = False, name=None):
        super().__init__(name=name)
        self.len = context_len
        self.start = -(context_len // 2) if context_start is None else context_start
        self.trainable_pads = trainable_pads

    def forward(self, x):  # x: [B, T, D]
        b, t, d = x.shape
        n_left = max(-self.start, 0)
        n_right = max(self.start + self.len - 1, 0)
        idx = jnp.arange(t)
        cols = []
        for k in range(self.len):
            off = self.start + k
            shifted = jnp.roll(x, -off, axis=1)
            valid = ((idx + off >= 0) & (idx + off < t))[None, :, None]
            if self.trainable_pads and off < 0:
                # missing frame t+off ∈ [-n_left, -1] maps to begin-pad row
                # n_left + (t+off), varying per timestep (reference:
                # ContextProjection begin_pad semantics).
                rows = jnp.clip(n_left + idx + off, 0, n_left - 1)
                fill = self.param("pad_l", I.zeros, (n_left, d))[rows]
                cols.append(jnp.where(valid, shifted, fill[None, :, :]))
            elif self.trainable_pads and off > 0:
                # missing frame t+off ∈ [T, T+n_right-1] maps to end-pad row
                # t+off-T, varying per timestep.
                rows = jnp.clip(idx + off - t, 0, n_right - 1)
                fill = self.param("pad_r", I.zeros, (n_right, d))[rows]
                cols.append(jnp.where(valid, shifted, fill[None, :, :]))
            else:
                cols.append(jnp.where(valid, shifted, 0.0))
        return jnp.concatenate(cols, axis=-1)


class MixedLayer(Module):
    """Sum of projections + bias + activation (reference: ``MixedLayer.cpp``)."""

    def __init__(self, projections: Sequence[Module], act="", use_bias=True,
                 name=None):
        super().__init__(name=name)
        self.projections = list(projections)
        self.act = activations.get(act)
        self.use_bias = use_bias

    def forward(self, *inputs):
        assert len(inputs) == len(self.projections)
        y = None
        for proj, x in zip(self.projections, inputs):
            o = proj(x)
            y = o if y is None else y + o
        if self.use_bias:
            y = y + self.param("b", I.zeros, (y.shape[-1],))
        return self.act(y)


class CrossMapNormal(Module):
    """Local response normalisation across channel maps (reference:
    ``function/CrossMapNormalOp.cpp`` — ``f(x) = x * (1 + scale *
    SUM_window(x^2))^(-pow)`` with the window of ``size`` maps centred at
    each channel; layer wrapper ``CMRProjectionNormLayer``). NHWC.

    The config-helper surface (``img_cmrnorm_layer``) passes
    ``scale = alpha / size``; this module takes ``scale``/``power`` directly
    like the function layer does.
    """

    def __init__(self, size: int = 5, scale: float = 0.0001,
                 power: float = 0.75, name=None):
        super().__init__(name=name)
        self.size = size
        self.scale = scale
        self.power = power

    def forward(self, x):
        half = (self.size - 1) // 2
        sq = x * x
        # sum over a channel window: pad C then window-sum via cumsum diff
        pad = jnp.pad(sq, [(0, 0)] * (x.ndim - 1) +
                      [(half, self.size - 1 - half)])
        csum = jnp.cumsum(pad, axis=-1)
        csum = jnp.pad(csum, [(0, 0)] * (x.ndim - 1) + [(1, 0)])
        win = csum[..., self.size:] - csum[..., :-self.size]
        denom = (1.0 + self.scale * win) ** (-self.power)
        return x * denom


class RowConv(Module):
    """Lookahead row convolution over packed sequences (reference:
    ``function/RowConvOp.cpp`` — ``out[t] = sum_k filter[k] * in[t+k]``
    elementwise per feature, truncated at each sequence end; from the
    DeepSpeech2 architecture).

    ``forward(x [B, T, D], lengths [B])``; context rows beyond a sequence's
    length contribute zero, matching the reference's per-sequence truncation.
    """

    def __init__(self, context: int, w_init=I.zeros, name=None):
        super().__init__(name=name)
        self.context = context
        self.w_init = w_init

    def forward(self, x, lengths=None):
        B, T, D = x.shape
        if lengths is None:
            lengths = jnp.full((B,), T)
        w = self.param("w", self.w_init, (self.context, D))
        idx = jnp.arange(T)
        out = jnp.zeros_like(x)
        for k in range(self.context):
            shifted = jnp.roll(x, -k, axis=1)
            valid = (idx + k < lengths[:, None])[..., None]
            out = out + jnp.where(valid, shifted, 0.0) * w[k]
        return out


class Conv3D(Module):
    """3-D convolution, NDHWC/DHWIO (reference: ``Conv3DLayer.cpp``). One
    ``lax.conv_general_dilated`` call — XLA tiles it onto the MXU the same
    way as 2-D convs."""

    def __init__(self, features: int, kernel, stride=1, padding="SAME",
                 act="", use_bias=True, w_init=I.fan_in_uniform,
                 b_init=I.zeros, name=None):
        super().__init__(name=name)
        self.features = features
        self.kernel = (kernel,) * 3 if isinstance(kernel, int) else tuple(kernel)
        self.stride = (stride,) * 3 if isinstance(stride, int) else tuple(stride)
        self.padding = padding
        self.act = activations.get(act)
        self.use_bias = use_bias
        self.w_init = w_init
        self.b_init = b_init

    def forward(self, x):
        pol = current_policy()
        w = self.param("w", self.w_init,
                       self.kernel + (x.shape[-1], self.features))
        pad = self.padding
        if isinstance(pad, int):
            pad = [(pad, pad)] * 3
        # No preferred_element_type on convs: the rhs-transpose rule in the
        # conv gradient requires operand dtypes to match (same constraint as
        # Conv2D above).
        y = lax.conv_general_dilated(
            pol.cast_compute(x), pol.cast_compute(w),
            window_strides=self.stride, padding=pad,
            dimension_numbers=("NDHWC", "DHWIO", "NDHWC"))
        if self.use_bias:
            y = y + self.param("b", self.b_init,
                               (self.features,)).astype(y.dtype)
        return self.act(y)


class Conv3DTranspose(Module):
    """3-D transposed convolution (reference: ``DeConv3DLayer.cpp``)."""

    def __init__(self, features: int, kernel, stride=1, padding="SAME",
                 act="", use_bias=True, w_init=I.fan_in_uniform,
                 b_init=I.zeros, name=None):
        super().__init__(name=name)
        self.features = features
        self.kernel = (kernel,) * 3 if isinstance(kernel, int) else tuple(kernel)
        self.stride = (stride,) * 3 if isinstance(stride, int) else tuple(stride)
        self.padding = padding
        self.act = activations.get(act)
        self.use_bias = use_bias
        self.w_init = w_init
        self.b_init = b_init

    def forward(self, x):
        pol = current_policy()
        w = self.param("w", self.w_init,
                       self.kernel + (x.shape[-1], self.features))
        pad = self.padding
        if isinstance(pad, int):
            pad = [(pad, pad)] * 3
        y = lax.conv_transpose(
            pol.cast_compute(x), pol.cast_compute(w),
            strides=self.stride, padding=pad,
            dimension_numbers=("NDHWC", "DHWIO", "NDHWC"))
        if self.use_bias:
            y = y + self.param("b", self.b_init,
                               (self.features,)).astype(y.dtype)
        return self.act(y)


class Pool3D(Module):
    """3-D max/avg pooling, NDHWC (reference: ``Pool3DLayer.cpp``)."""

    def __init__(self, kind: str, window, stride=None, padding="VALID",
                 name=None):
        super().__init__(name=name)
        assert kind in ("max", "avg")
        self.kind = kind
        self.window = (window,) * 3 if isinstance(window, int) else tuple(window)
        stride = stride if stride is not None else window
        self.stride = (stride,) * 3 if isinstance(stride, int) else tuple(stride)
        self.padding = padding

    def forward(self, x):
        dims = (1,) + self.window + (1,)
        strides = (1,) + self.stride + (1,)
        if self.kind == "max":
            return lax.reduce_window(x, -jnp.inf, lax.max, dims, strides,
                                     self.padding)
        s = lax.reduce_window(x, 0.0, lax.add, dims, strides, self.padding)
        ones = jnp.ones_like(x)
        cnt = lax.reduce_window(ones, 0.0, lax.add, dims, strides,
                                self.padding)
        return s / cnt


class SelectiveFC(Module):
    """Fully-connected over a per-sample subset of output columns (reference:
    ``SelectiveFullyConnectedLayer.cpp`` — used for large-vocab softmax where
    only sampled columns are computed).

    ``forward(x [B, D], sel [B, K])`` computes ``x @ W[:, sel[b]] + b[sel[b]]``
    per sample — a gather of weight columns followed by a batched matvec
    (einsum), instead of the reference's sparse-matrix product. ``sel`` ids
    < 0 yield zeros. ``forward(x)`` without ``sel`` is a plain Linear (the
    reference's full-matrix mode at inference)."""

    def __init__(self, features: int, act="", use_bias=True,
                 w_init=I.fan_in_uniform, b_init=I.zeros, name=None):
        super().__init__(name=name)
        self.features = features
        self.act = activations.get(act)
        self.use_bias = use_bias
        self.w_init = w_init
        self.b_init = b_init

    def forward(self, x, sel=None):
        pol = current_policy()
        w = self.param("w", self.w_init, (x.shape[-1], self.features))
        b = self.param("b", self.b_init, (self.features,)) \
            if self.use_bias else None
        if sel is None:
            y = jnp.dot(pol.cast_compute(x), pol.cast_compute(w),
                        preferred_element_type=pol.accum_dtype)
            if b is not None:
                y = y + b
            return self.act(y)
        valid = sel >= 0
        safe = jnp.clip(sel, 0, self.features - 1)
        w_sel = jnp.take(w, safe, axis=1)          # [D, B, K]
        w_sel = jnp.moveaxis(w_sel, 1, 0)          # [B, D, K]
        y = jnp.einsum("bd,bdk->bk", pol.cast_compute(x),
                       pol.cast_compute(w_sel),
                       preferred_element_type=pol.accum_dtype)
        if b is not None:
            y = y + jnp.take(b, safe)
        return jnp.where(valid, self.act(y), 0.0)


class SamplingId(Module):
    """Sample an id per row from a (softmax) distribution (reference:
    ``SamplingIdLayer.cpp`` + ``MultinomialSampler``). Input is logits by
    default (``from_logits=False`` for probabilities). Needs an ``rngs=
    {'sample': key}`` stream under apply."""

    def __init__(self, from_logits: bool = True, name=None):
        super().__init__(name=name)
        self.from_logits = from_logits

    def forward(self, x):
        logits = x if self.from_logits else jnp.log(jnp.maximum(x, 1e-30))
        key = current_rng("sample")
        return jax.random.categorical(key, logits, axis=-1)


class ScaleSubRegion(Module):
    """Scale a per-sample sub-region of an image by a constant (reference:
    ``function/ScaleSubRegionOp.cpp`` — 1-based inclusive region indices
    ``[c1, c2, h1, h2, w1, w2]`` per sample, forward multiplies the region
    by ``value``). NHWC here; region built as a boolean mask so the op (and
    its gradient, which scales only in-region, ``:73``) stays jit-safe."""

    def __init__(self, value: float, name=None):
        super().__init__(name=name)
        self.value = value

    def forward(self, x, indices):
        B, H, W, C = x.shape
        idx = indices.astype(jnp.int32)          # [B, 6], 1-based inclusive
        cc = jnp.arange(C)[None, :]
        hh = jnp.arange(H)[None, :]
        ww = jnp.arange(W)[None, :]
        cm = (cc >= idx[:, 0:1] - 1) & (cc <= idx[:, 1:2] - 1)   # [B, C]
        hm = (hh >= idx[:, 2:3] - 1) & (hh <= idx[:, 3:4] - 1)   # [B, H]
        wm = (ww >= idx[:, 4:5] - 1) & (ww <= idx[:, 5:6] - 1)   # [B, W]
        mask = hm[:, :, None, None] & wm[:, None, :, None] & cm[:, None, None, :]
        return jnp.where(mask, x * self.value, x)


class Power(Module):
    """Per-sample power: ``y[b] = x[b] ** w[b]`` with the exponent coming
    from another layer (reference: ``PowerLayer.cpp`` — two inputs, scalar
    exponent per sample)."""

    def forward(self, exponent, x):
        e = exponent.reshape(exponent.shape[0], *([1] * (x.ndim - 1)))
        return jnp.power(x, e)


class Scaling(Module):
    """Per-sample scaling: ``y[b] = w[b] * x[b]`` with the scale from
    another layer (reference: ``ScalingLayer.cpp``)."""

    def forward(self, weight, x):
        w = weight.reshape(weight.shape[0], *([1] * (x.ndim - 1)))
        return w * x


class DotProd(Module):
    """Row-wise dot product of two inputs -> [B, 1] (reference:
    ``DotProdLayer.cpp``)."""

    def forward(self, a, b):
        return jnp.sum(a * b, axis=-1, keepdims=True)


class ConvexCombination(Module):
    """Weighted sum of K stacked rows: weights [B, K], data [B, K, D] (or
    flat [B, K*D]) -> [B, D] (reference: ``ConvexCombinationLayer`` in
    ``LinearChainCRF``-era naming, a.k.a. ``linear_comb_layer``)."""

    def __init__(self, size: Optional[int] = None, name=None):
        super().__init__(name=name)
        self.size = size

    def forward(self, weights, data):
        B, K = weights.shape
        if data.ndim == 2:
            data = data.reshape(B, K, -1)
        return jnp.einsum("bk,bkd->bd", weights, data)


class CosSimVecMat(Module):
    """Cosine similarity of a vector against each of K stacked rows:
    vec [B, D], mat [B, K, D] (or flat [B, K*D]) -> [B, K] (reference:
    ``CosSimVecMatLayer.cpp``)."""

    def __init__(self, scale: float = 1.0, name=None):
        super().__init__(name=name)
        self.scale = scale

    def forward(self, vec, mat):
        B = vec.shape[0]
        if mat.ndim == 2:
            mat = mat.reshape(B, -1, vec.shape[-1])
        num = jnp.einsum("bd,bkd->bk", vec, mat)
        den = (jnp.linalg.norm(vec, axis=-1, keepdims=True)
               * jnp.linalg.norm(mat, axis=-1) + 1e-12)
        return self.scale * num / den


class BilinearInterp(Module):
    """Bilinear up/down-sampling of NHWC feature maps (reference:
    ``BilinearInterpLayer.cpp``). Deviation: uses half-pixel sampling
    (``jax.image.resize``) rather than the reference's align-corners
    ratios — border pixels differ slightly from the legacy layer."""

    def __init__(self, out_h: int, out_w: int, name=None):
        super().__init__(name=name)
        self.out_h = out_h
        self.out_w = out_w

    def forward(self, x):
        B, H, W, C = x.shape
        return jax.image.resize(x, (B, self.out_h, self.out_w, C),
                                method="bilinear")


class EosIdCheck(Module):
    """1 where the id equals ``eos_id`` (reference: ``EosIdCheckLayer.cpp``
    — the stop signal inside generation groups)."""

    def __init__(self, eos_id: int, name=None):
        super().__init__(name=name)
        self.eos_id = eos_id

    def forward(self, ids):
        return (ids == self.eos_id).astype(jnp.float32)


class PRelu(Module):
    """Parametric ReLU with learned negative slope (reference:
    ``ParameterReluLayer.cpp``; ``partial_sum`` groups channels sharing one
    slope — ``channels`` slopes here, 1 = fully shared)."""

    def __init__(self, channels: int = 1, init_slope: float = 0.25,
                 name=None):
        super().__init__(name=name)
        self.channels = channels
        self.init_slope = init_slope

    def forward(self, x):
        a = self.param("a", I.constant(self.init_slope), (self.channels,))
        if self.channels > 1:
            assert x.shape[-1] % self.channels == 0
            a = jnp.repeat(a, x.shape[-1] // self.channels)
        return jnp.where(x >= 0, x, a * x)


class ScalingProjection(Module):
    """One learned scalar times the input (reference:
    ``ScalingProjection.cpp``)."""

    def forward(self, x):
        w = self.param("w", I.ones, (1,))
        return w * x


class SliceProjection(Module):
    """Column slice [start, end) of the input (reference:
    ``SliceProjection.cpp``)."""

    def __init__(self, start: int, end: int, name=None):
        super().__init__(name=name)
        self.start = start
        self.end = end

    def forward(self, x):
        return x[..., self.start:self.end]


class TransposedFullMatrixProjection(Module):
    """``y = x @ W.T`` (reference: ``TransposedFullMatrixProjection.cpp`` —
    weight shared transposed with another projection). The weight is stored
    ``(features, in)`` so it can be shared with a forward projection; the
    init scales by the true fan-in (``in``, shape[1]) — the generic
    fan-in initializer would read shape[0]."""

    def __init__(self, features: int, w_init=None, name=None):
        super().__init__(name=name)
        self.features = features
        self.w_init = w_init

    def forward(self, x):
        fan_in = x.shape[-1]

        def default_init(rng, shape, dtype=jnp.float32):
            bound = 1.0 / np.sqrt(fan_in)
            return jax.random.uniform(rng, shape, dtype, -bound, bound)

        w = self.param("w", self.w_init or default_init,
                       (self.features, fan_in))
        return x @ w.T


class SwitchOrder(Module):
    """NCHW <-> NHWC layout switch (reference: function-layer ``SwitchOp``
    / ``SwitchOrderLayer.cpp``). The package is NHWC-native; this exists
    for interop at data boundaries."""

    def __init__(self, to: str = "NHWC", name=None):
        super().__init__(name=name)
        assert to in ("NHWC", "NCHW")
        self.to = to

    def forward(self, x):
        if self.to == "NHWC":
            return jnp.transpose(x, (0, 2, 3, 1))
        return jnp.transpose(x, (0, 3, 1, 2))


class MaxPoolWithMask(Module):
    """Max pooling that also returns the argmax mask (reference:
    ``MaxPoolWithMaskLayer.cpp`` — the mask holds each output's flat input
    index, consumed by unpooling). Non-overlapping windows
    (stride == window), NHWC; mask indices are flat over (H, W) per channel,
    matching the reference's row-major convention."""

    def __init__(self, window: int, name=None):
        super().__init__(name=name)
        self.window = window

    def forward(self, x):
        B, H, W, C = x.shape
        w = self.window
        assert H % w == 0 and W % w == 0, "window must tile the input"
        Ho, Wo = H // w, W // w
        t = x.reshape(B, Ho, w, Wo, w, C)
        t = jnp.moveaxis(t, 2, 3).reshape(B, Ho, Wo, w * w, C)
        pooled = jnp.max(t, axis=3)
        local = jnp.argmax(t, axis=3).astype(jnp.int32)   # [B,Ho,Wo,C]
        # local window index -> flat (H, W) input index
        ly, lx = local // w, local % w
        gy = jnp.arange(Ho)[None, :, None, None] * w + ly
        gx = jnp.arange(Wo)[None, None, :, None] * w + lx
        return pooled, gy * W + gx
