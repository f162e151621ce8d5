"""Op registry: pure jnp/lax emitter functions for every SameDiff op.

This is the TPU-native collapse of libnd4j's declarable-op layer
(SURVEY.md §2.1 "Declarable (custom) ops", ~500-700 CUDA/C++ kernels in
libnd4j/include/ops/declarable/): each entry is a pure function XLA fuses
and differentiates, replacing {generic impl + cuda helper + cudnn platform
helper + hand-written doDiff} per op.

Conventions:
  - fn(*inputs, **attrs) -> jnp array or tuple of arrays
  - ops in RANDOM_OPS receive a `key=` jax PRNG key kwarg at execution
  - ops in TRAINING_AWARE_OPS receive `training=` bool kwarg
  - conv/pool use NCHW activations and [out, in, kH, kW] weights, matching
    DL4J's layout (libnd4j conv2d); lowered to lax.conv_general_dilated which
    XLA maps onto the MXU.
"""

from __future__ import annotations

import math as _math

import jax
import jax.numpy as jnp
from jax import lax

# ---------------------------------------------------------------------------
# elementwise / transforms
# ---------------------------------------------------------------------------

def _identity(x):
    return x


def _axis(dims, ndim):
    if dims is None or dims == () or dims == []:
        return None
    if isinstance(dims, int):
        dims = (dims,)
    return tuple(d % ndim for d in dims)


OPS = {}


def op(name=None, random=False, training_aware=False):
    def deco(fn):
        OPS[name or fn.__name__] = fn
        if random:
            RANDOM_OPS.add(name or fn.__name__)
        if training_aware:
            TRAINING_AWARE_OPS.add(name or fn.__name__)
        return fn

    return deco


RANDOM_OPS: set = set()
TRAINING_AWARE_OPS: set = set()

# binary
OPS["add"] = lambda a, b: a + b
OPS["sub"] = lambda a, b: a - b
OPS["mul"] = lambda a, b: a * b
OPS["div"] = lambda a, b: a / b
OPS["rsub"] = lambda a, b: b - a
OPS["rdiv"] = lambda a, b: b / a
OPS["pow"] = lambda a, b: a**b
OPS["floordiv"] = lambda a, b: jnp.floor_divide(a, b)
OPS["mod"] = lambda a, b: jnp.mod(a, b)
OPS["squaredDifference"] = lambda a, b: (a - b) ** 2
OPS["maximum"] = jnp.maximum
OPS["minimum"] = jnp.minimum

# unary
OPS["identity"] = _identity
OPS["neg"] = jnp.negative
OPS["abs"] = jnp.abs
OPS["exp"] = jnp.exp
OPS["log"] = jnp.log
OPS["log1p"] = jnp.log1p
OPS["sqrt"] = jnp.sqrt
OPS["rsqrt"] = lax.rsqrt
OPS["square"] = jnp.square
OPS["reciprocal"] = jnp.reciprocal
OPS["sign"] = jnp.sign
OPS["floor"] = jnp.floor
OPS["ceil"] = jnp.ceil
OPS["round"] = jnp.round
OPS["sin"] = jnp.sin
OPS["cos"] = jnp.cos
OPS["tan"] = jnp.tan
OPS["asin"] = jnp.arcsin
OPS["acos"] = jnp.arccos
OPS["atan"] = jnp.arctan
OPS["sinh"] = jnp.sinh
OPS["cosh"] = jnp.cosh
OPS["tanh"] = jnp.tanh
OPS["erf"] = jax.scipy.special.erf
OPS["isnan"] = jnp.isnan
OPS["isinf"] = jnp.isinf

# activations
OPS["sigmoid"] = jax.nn.sigmoid
OPS["relu"] = jax.nn.relu
OPS["relu6"] = jax.nn.relu6
OPS["elu"] = jax.nn.elu
OPS["selu"] = jax.nn.selu
OPS["gelu"] = jax.nn.gelu
OPS["softplus"] = jax.nn.softplus
OPS["softsign"] = jax.nn.soft_sign
OPS["swish"] = jax.nn.silu
OPS["mish"] = lambda x: x * jnp.tanh(jax.nn.softplus(x))
OPS["hardSigmoid"] = jax.nn.hard_sigmoid
OPS["hardTanh"] = lambda x: jnp.clip(x, -1.0, 1.0)
OPS["leakyRelu"] = lambda x, alpha=0.01: jax.nn.leaky_relu(x, alpha)
OPS["prelu"] = lambda x, a: jnp.where(x >= 0, x, a * x)
OPS["rationalTanh"] = lambda x: 1.7159 * jnp.tanh(2.0 * x / 3.0)
OPS["rectifiedTanh"] = lambda x: jnp.maximum(jnp.tanh(x), 0.0)
OPS["thresholdRelu"] = lambda x, cutoff=0.0: jnp.where(x > cutoff, x, 0.0)
OPS["clipByValue"] = lambda x, clipValueMin=-1.0, clipValueMax=1.0: jnp.clip(
    x, clipValueMin, clipValueMax
)


@op("clipByNorm")
def _clip_by_norm(x, clipValue=1.0, dims=None):
    n = jnp.sqrt(jnp.sum(x * x, axis=_axis(dims, x.ndim), keepdims=True))
    return jnp.where(n > clipValue, x * (clipValue / jnp.maximum(n, 1e-12)), x)


@op("softmax")
def _softmax(x, dimension=-1):
    return jax.nn.softmax(x, axis=dimension)


@op("logSoftmax")
def _log_softmax(x, dimension=-1):
    return jax.nn.log_softmax(x, axis=dimension)


@op("softmaxDerivative")
def _softmax_deriv(x, wrt, dimension=-1):
    s = jax.nn.softmax(x, axis=dimension)
    return s * (wrt - jnp.sum(wrt * s, axis=dimension, keepdims=True))


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------

def _red(fn):
    def f(x, dimensions=None, keepDims=False):
        return fn(x, axis=_axis(dimensions, x.ndim), keepdims=keepDims)

    return f


OPS["sum"] = _red(jnp.sum)
OPS["mean"] = _red(jnp.mean)
OPS["max"] = _red(jnp.max)
OPS["min"] = _red(jnp.min)
OPS["prod"] = _red(jnp.prod)
OPS["any"] = _red(jnp.any)
OPS["all"] = _red(jnp.all)
OPS["norm1"] = _red(lambda x, **k: jnp.sum(jnp.abs(x), **k))
OPS["norm2"] = _red(lambda x, **k: jnp.sqrt(jnp.sum(x * x, **k)))
OPS["normMax"] = _red(lambda x, **k: jnp.max(jnp.abs(x), **k))
OPS["logSumExp"] = _red(jax.scipy.special.logsumexp)
OPS["countNonZero"] = _red(lambda x, **k: jnp.sum((x != 0), **k))
OPS["zeroFraction"] = lambda x: jnp.mean((x == 0).astype(jnp.float32))


@op("variance")
def _variance(x, dimensions=None, biasCorrected=True, keepDims=False):
    return jnp.var(
        x, axis=_axis(dimensions, x.ndim), ddof=1 if biasCorrected else 0,
        keepdims=keepDims,
    )


@op("standardDeviation")
def _std(x, dimensions=None, biasCorrected=True, keepDims=False):
    return jnp.std(
        x, axis=_axis(dimensions, x.ndim), ddof=1 if biasCorrected else 0,
        keepdims=keepDims,
    )


@op("argmax")
def _argmax(x, dimension=None, keepDims=False):
    r = jnp.argmax(x, axis=dimension, keepdims=keepDims)
    return r


@op("argmin")
def _argmin(x, dimension=None, keepDims=False):
    return jnp.argmin(x, axis=dimension, keepdims=keepDims)


@op("cumsum")
def _cumsum(x, axis=0, exclusive=False, reverse=False):
    a = x
    if reverse:
        a = jnp.flip(a, axis)
    r = jnp.cumsum(a, axis=axis)
    if exclusive:
        r = r - a
    if reverse:
        r = jnp.flip(r, axis)
    return r


@op("cumprod")
def _cumprod(x, axis=0):
    return jnp.cumprod(x, axis=axis)


@op("moments")
def _moments(x, dimensions=None, keepDims=False):
    ax = _axis(dimensions, x.ndim)
    return jnp.mean(x, ax, keepdims=keepDims), jnp.var(x, ax, keepdims=keepDims)


# ---------------------------------------------------------------------------
# linalg
# ---------------------------------------------------------------------------

@op("matmul")
def _matmul(a, b, transposeA=False, transposeB=False):
    if transposeA:
        a = jnp.swapaxes(a, -1, -2)
    if transposeB:
        b = jnp.swapaxes(b, -1, -2)
    return a @ b


@op("tensorMmul")
def _tensor_mmul(a, b, axesA=None, axesB=None):
    return jnp.tensordot(a, b, axes=(tuple(axesA), tuple(axesB)))


@op("batchMmul")
def _batch_mmul(a, b):
    return a @ b


@op("dot")
def _dot(a, b, dimensions=None):
    if dimensions is None:
        return jnp.sum(a * b)
    return jnp.sum(a * b, axis=_axis(dimensions, a.ndim))


# ---------------------------------------------------------------------------
# shape ops
# ---------------------------------------------------------------------------

@op("reshape")
def _reshape(x, shape=None):
    return x.reshape(tuple(shape))


@op("permute")
def _permute(x, dimensions=None):
    return jnp.transpose(x, tuple(dimensions))


@op("transpose")
def _transpose(x):
    return x.T


@op("expandDims")
def _expand_dims(x, axis=0):
    return jnp.expand_dims(x, axis)


@op("squeeze")
def _squeeze(x, axis=None):
    return jnp.squeeze(x, axis=axis)


@op("concat")
def _concat(*xs, dimension=0):
    return jnp.concatenate(xs, axis=dimension)


@op("stack")
def _stack(*xs, axis=0):
    return jnp.stack(xs, axis=axis)


@op("unstack")
def _unstack(x, axis=0, num=None):
    n = num or x.shape[axis]
    return tuple(jnp.squeeze(s, axis) for s in jnp.split(x, n, axis=axis))


@op("split")
def _split(x, numSplit=2, dimension=0):
    return tuple(jnp.split(x, numSplit, axis=dimension))


@op("slice")
def _slice(x, begin=None, size=None):
    begin = tuple(begin)
    size = tuple(
        s if s >= 0 else x.shape[i] - begin[i] for i, s in enumerate(size)
    )
    return lax.dynamic_slice(x, begin, size)


@op("stridedSlice")
def _strided_slice(x, begin=None, end=None, strides=None):
    idx = tuple(
        slice(b, e, s) for b, e, s in zip(begin, end, strides or [1] * len(begin))
    )
    return x[idx]


@op("tile")
def _tile(x, reps=None):
    return jnp.tile(x, tuple(reps))


@op("pad")
def _pad(x, paddings=None, constant=0.0, mode="CONSTANT"):
    pads = tuple(tuple(p) for p in paddings)
    if mode.upper() == "CONSTANT":
        return jnp.pad(x, pads, constant_values=constant)
    return jnp.pad(x, pads, mode=mode.lower())


@op("reverse")
def _reverse(x, dimensions=None):
    return jnp.flip(x, axis=_axis(dimensions, x.ndim))


@op("gather")
def _gather(x, indices, axis=0):
    return jnp.take(x, indices.astype(jnp.int32), axis=axis)


@op("gatherNd")
def _gather_nd(x, indices):
    idx = tuple(jnp.moveaxis(indices.astype(jnp.int32), -1, 0))
    return x[idx]


@op("scatterUpdate")
def _scatter_update(ref, indices, updates):
    return ref.at[indices.astype(jnp.int32)].set(updates)


@op("scatterAdd")
def _scatter_add(ref, indices, updates):
    return ref.at[indices.astype(jnp.int32)].add(updates)


@op("oneHot")
def _one_hot(x, depth=None, on=1.0, off=0.0, axis=-1):
    return jax.nn.one_hot(x.astype(jnp.int32), depth, axis=axis) * (on - off) + off


@op("linspace")
def _linspace(start=0.0, stop=1.0, num=10):
    return jnp.linspace(start, stop, num)


@op("range")
def _range(start=0, limit=None, delta=1):
    return jnp.arange(start, limit, delta)


@op("shape_of")
def _shape_of(x):
    return jnp.asarray(x.shape, dtype=jnp.int32)


@op("cast")
def _cast(x, dtype=None):
    return x.astype(dtype)


@op("assign_op")
def _assign_op(a, b):
    return jnp.broadcast_to(b, a.shape).astype(a.dtype)


@op("invertPermutation")
def _invert_permutation(x):
    return jnp.argsort(x)


@op("sequenceMask")
def _sequence_mask(lengths, maxLen=None):
    return (jnp.arange(maxLen)[None, :] < lengths[:, None]).astype(jnp.float32)


@op("diag")
def _diag(x):
    return jnp.diag(x)


@op("eye_op")
def _eye(n=1, m=None):
    return jnp.eye(n, m)


@op("meshgrid")
def _meshgrid(*xs, indexing="xy"):
    return tuple(jnp.meshgrid(*xs, indexing=indexing))


# comparisons / selection
OPS["eq"] = lambda a, b: a == b
OPS["neq"] = lambda a, b: a != b
OPS["gt"] = lambda a, b: a > b
OPS["gte"] = lambda a, b: a >= b
OPS["lt"] = lambda a, b: a < b
OPS["lte"] = lambda a, b: a <= b
OPS["and_op"] = jnp.logical_and
OPS["or_op"] = jnp.logical_or
OPS["not_op"] = jnp.logical_not
OPS["xor_op"] = jnp.logical_xor


@op("where_op")
def _where(cond, x, y):
    return jnp.where(cond.astype(bool), x, y)


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------

@op("layerNorm")
def _layer_norm(x, gain, bias=None, channelwise_axis=-1, epsilon=1e-5):
    mean = jnp.mean(x, axis=channelwise_axis, keepdims=True)
    var = jnp.var(x, axis=channelwise_axis, keepdims=True)
    y = (x - mean) * lax.rsqrt(var + epsilon) * gain
    if bias is not None:
        y = y + bias
    return y


@op("batchNorm")
def _batch_norm(x, mean, variance, gamma=None, beta=None, epsilon=1e-5,
                axis=1):
    shape = [1] * x.ndim
    shape[axis] = x.shape[axis]
    rs = lambda a: a.reshape(shape)
    y = (x - rs(mean)) * lax.rsqrt(rs(variance) + epsilon)
    if gamma is not None:
        y = y * rs(gamma)
    if beta is not None:
        y = y + rs(beta)
    return y


@op("standardize")
def _standardize(x, dimensions=(-1,)):
    ax = _axis(dimensions, x.ndim)
    m = jnp.mean(x, axis=ax, keepdims=True)
    s = jnp.std(x, axis=ax, keepdims=True)
    return (x - m) / jnp.maximum(s, 1e-12)


@op("dropout", random=True, training_aware=True)
def _dropout(x, p=0.5, key=None, training=False):
    """p is the RETAIN probability, matching DL4J dropout semantics
    (org.deeplearning4j.nn.conf.dropout.Dropout: activations scaled by 1/p)."""
    if not training or p >= 1.0:
        return x
    mask = jax.random.bernoulli(key, p, x.shape)
    return jnp.where(mask, x / p, 0.0)


# ---------------------------------------------------------------------------
# random
# ---------------------------------------------------------------------------

@op("randomNormal", random=True)
def _random_normal(shape=None, mean=0.0, stddev=1.0, key=None):
    return mean + stddev * jax.random.normal(key, tuple(shape))


@op("randomUniform", random=True)
def _random_uniform(shape=None, min=0.0, max=1.0, key=None):
    return jax.random.uniform(key, tuple(shape), minval=min, maxval=max)


@op("randomBernoulli", random=True)
def _random_bernoulli(shape=None, p=0.5, key=None):
    return jax.random.bernoulli(key, p, tuple(shape)).astype(jnp.float32)


@op("randomGamma", random=True)
def _random_gamma(shape=None, alpha=1.0, beta=1.0, key=None):
    """Gamma(alpha, rate beta) (reference: random ops gamma declarable)."""
    return jax.random.gamma(key, alpha, tuple(shape)) / beta


@op("randomPoisson", random=True)
def _random_poisson(shape=None, lam=1.0, key=None):
    return jax.random.poisson(key, lam, tuple(shape)).astype(jnp.float32)


@op("randomExponential", random=True)
def _random_exponential(shape=None, lam=1.0, key=None):
    return jax.random.exponential(key, tuple(shape)) / lam


@op("truncatedNormal", random=True)
def _truncated_normal(shape=None, mean=0.0, stddev=1.0, key=None):
    """Normal truncated to +/-2 sigma (TF/DL4J truncated_normal
    semantics)."""
    return mean + stddev * jax.random.truncated_normal(
        key, -2.0, 2.0, tuple(shape))


# ---------------------------------------------------------------------------
# conv / pool (NCHW, weights [out, in, kH, kW] like libnd4j conv2d)
# ---------------------------------------------------------------------------

def _pair(v):
    if isinstance(v, (list, tuple)):
        return tuple(v)
    return (v, v)


def _conv_pad(padding, kernel, strides, dilation=(1, 1)):
    if isinstance(padding, str):
        return padding.upper()
    p = _pair(padding)
    return [(p[0], p[0]), (p[1], p[1])]


@op("conv2d")
def _conv2d(x, w, b=None, kernel=None, strides=(1, 1), padding=(0, 0),
            dilation=(1, 1), sameMode=False):
    """x: [N,C,H,W]; w: [outC, inC, kH, kW] (DL4J layout)."""
    strides = _pair(strides)
    dilation = _pair(dilation)
    pad = "SAME" if sameMode else _conv_pad(padding, kernel, strides, dilation)
    y = lax.conv_general_dilated(
        x, w, window_strides=strides, padding=pad,
        rhs_dilation=dilation,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
    )
    if b is not None:
        y = y + b.reshape(1, -1, 1, 1)
    return y


@op("depthwiseConv2d")
def _depthwise_conv2d(x, w, b=None, strides=(1, 1), padding=(0, 0),
                      dilation=(1, 1), sameMode=False):
    """w: [depthMult, inC, kH, kW] -> grouped conv with C groups."""
    strides = _pair(strides)
    dilation = _pair(dilation)
    c = x.shape[1]
    mult = w.shape[0]
    # reshape to [C*mult, 1, kH, kW] for feature_group_count=C
    w2 = jnp.transpose(w, (1, 0, 2, 3)).reshape(c * mult, 1, *w.shape[2:])
    pad = "SAME" if sameMode else _conv_pad(padding, None, strides, dilation)
    y = lax.conv_general_dilated(
        x, w2, window_strides=strides, padding=pad, rhs_dilation=dilation,
        dimension_numbers=("NCHW", "OIHW", "NCHW"), feature_group_count=c,
    )
    if b is not None:
        y = y + b.reshape(1, -1, 1, 1)
    return y


@op("conv1d")
def _conv1d(x, w, b=None, stride=1, padding=0, sameMode=False):
    """x: [N,C,W]; w: [outC, inC, k]."""
    pad = "SAME" if sameMode else [(padding, padding)]
    y = lax.conv_general_dilated(
        x, w, window_strides=(stride,), padding=pad,
        dimension_numbers=("NCH", "OIH", "NCH"),
    )
    if b is not None:
        y = y + b.reshape(1, -1, 1)
    return y


@op("deconv2d")
def _deconv2d(x, w, b=None, strides=(1, 1), padding=(0, 0), sameMode=False):
    """Transposed conv; w: [outC, inC, kH, kW] wrt the FORWARD direction of
    the deconv (i.e. produces outC channels). Implemented as the
    lhs-dilated conv with per-side padding k-1-p and a spatially flipped
    kernel, which yields DL4J's deconv output size s*(i-1) + k - 2p
    (SAME mode: i*s)."""
    strides = _pair(strides)
    p = _pair(padding)
    k = (w.shape[2], w.shape[3])
    if sameMode:
        # total pad k+s-2 per dim -> output i*s
        tot = (k[0] + strides[0] - 2, k[1] + strides[1] - 2)
        pad = [(tot[0] // 2, tot[0] - tot[0] // 2),
               (tot[1] // 2, tot[1] - tot[1] // 2)]
    else:
        pad = [(k[0] - 1 - p[0], k[0] - 1 - p[0]),
               (k[1] - 1 - p[1], k[1] - 1 - p[1])]
    y = lax.conv_general_dilated(
        x, jnp.flip(w, (2, 3)), window_strides=(1, 1), padding=pad,
        lhs_dilation=strides,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
    )
    if b is not None:
        y = y + b.reshape(1, -1, 1, 1)
    return y


def _pool(x, kernel, strides, padding, sameMode, init, fn, norm=False):
    kernel = _pair(kernel)
    strides = _pair(strides)
    p = _pair(padding)
    if sameMode:
        pad = "SAME"
    else:
        pad = ((0, 0), (0, 0), (p[0], p[0]), (p[1], p[1]))
    window = (1, 1) + kernel
    strides_full = (1, 1) + strides
    y = lax.reduce_window(x, init, fn, window, strides_full, pad)
    if norm:
        ones = jnp.ones_like(x)
        cnt = lax.reduce_window(ones, 0.0, lax.add, window, strides_full, pad)
        y = y / cnt
    return y


@op("maxPooling2d")
def _max_pool2d(x, kernel=(2, 2), strides=(2, 2), padding=(0, 0),
                sameMode=False):
    return _pool(x, kernel, strides, padding, sameMode, -jnp.inf, lax.max)


@op("avgPooling2d")
def _avg_pool2d(x, kernel=(2, 2), strides=(2, 2), padding=(0, 0),
                sameMode=False, includePadInAvg=False):
    if includePadInAvg:
        k = _pair(kernel)
        s = _pool(x, kernel, strides, padding, sameMode, 0.0, lax.add)
        return s / (k[0] * k[1])
    return _pool(x, kernel, strides, padding, sameMode, 0.0, lax.add, norm=True)


def _triple_(v):
    if isinstance(v, (list, tuple)):
        return tuple(int(a) for a in v)
    return (int(v),) * 3


@op("conv3d")
def _conv3d_op(x, w, b=None, strides=(1, 1, 1), padding=(0, 0, 0),
               dilation=(1, 1, 1), sameMode=False):
    """x: [N,C,D,H,W]; w: [outC, inC, kD, kH, kW] (op-level conv3d —
    reference: libnd4j conv3dnew declarable; the Convolution3D LAYER
    wraps the same lowering)."""
    strides = _triple_(strides)
    dilation = _triple_(dilation)
    if sameMode:
        pad = "SAME"
    else:
        p = _triple_(padding)
        pad = [(p[0], p[0]), (p[1], p[1]), (p[2], p[2])]
    y = lax.conv_general_dilated(
        x, w, window_strides=strides, padding=pad, rhs_dilation=dilation,
        dimension_numbers=("NCDHW", "OIDHW", "NCDHW"))
    if b is not None:
        y = y + b.reshape(1, -1, 1, 1, 1)
    return y


def _pool3d(x, kernel, strides, padding, sameMode, init, fn, norm=False):
    k = _triple_(kernel)
    s = _triple_(strides)
    p = _triple_(padding)
    pad = "SAME" if sameMode else (
        (0, 0), (0, 0), (p[0], p[0]), (p[1], p[1]), (p[2], p[2]))
    window = (1, 1) + k
    strides_full = (1, 1) + s
    y = lax.reduce_window(x, init, fn, window, strides_full, pad)
    if norm:
        cnt = lax.reduce_window(jnp.ones_like(x), 0.0, lax.add, window,
                                strides_full, pad)
        y = y / cnt
    return y


@op("maxPooling3d")
def _max_pool3d(x, kernel=(2, 2, 2), strides=(2, 2, 2), padding=(0, 0, 0),
                sameMode=False):
    return _pool3d(x, kernel, strides, padding, sameMode, -jnp.inf, lax.max)


@op("avgPooling3d")
def _avg_pool3d(x, kernel=(2, 2, 2), strides=(2, 2, 2), padding=(0, 0, 0),
                sameMode=False):
    return _pool3d(x, kernel, strides, padding, sameMode, 0.0, lax.add,
                   norm=True)


@op("globalAvgPooling")
def _global_avg_pool(x, dimensions=(2, 3)):
    return jnp.mean(x, axis=_axis(dimensions, x.ndim))


@op("upsampling2d")
def _upsampling2d(x, size=(2, 2)):
    s = _pair(size)
    return jnp.repeat(jnp.repeat(x, s[0], axis=2), s[1], axis=3)


@op("im2col")
def _im2col(x, kernel=(2, 2), strides=(1, 1), padding=(0, 0)):
    """Kept for parity with libnd4j helpers/im2col — on TPU conv doesn't go
    through im2col (XLA handles tiling), but the op is part of the surface."""
    k = _pair(kernel)
    s = _pair(strides)
    p = _pair(padding)
    xp = jnp.pad(x, ((0, 0), (0, 0), (p[0], p[0]), (p[1], p[1])))
    n, c, h, w = xp.shape
    oh = (h - k[0]) // s[0] + 1
    ow = (w - k[1]) // s[1] + 1
    idx_h = (jnp.arange(oh) * s[0])[:, None] + jnp.arange(k[0])[None, :]
    idx_w = (jnp.arange(ow) * s[1])[:, None] + jnp.arange(k[1])[None, :]
    cols = xp[:, :, idx_h[:, :, None, None], idx_w[None, None, :, :]]
    # [n, c, oh, kh, ow, kw] -> [n, c, kh, kw, oh, ow]
    return jnp.transpose(cols, (0, 1, 3, 5, 2, 4))


# ---------------------------------------------------------------------------
# recurrent (lstmLayer replaces libnd4j helpers/lstm + cudnn LSTM,
# SURVEY.md §2.1; scan keeps the weights resident and lets XLA pipeline steps)
# ---------------------------------------------------------------------------

@op("lstmCell")
def _lstm_cell(x, h_prev, c_prev, w, r, b=None, forgetBias=0.0):
    """One LSTM step. x:[N,I], h_prev/c_prev:[N,H], w:[I,4H], r:[H,4H],
    b:[4H]. Gate order i,f,g(cell),o — matches DL4J lstmLayer gate packing."""
    z = x @ w + h_prev @ r
    if b is not None:
        z = z + b
    hsz = h_prev.shape[-1]
    i, f, g, o = (z[..., k * hsz:(k + 1) * hsz] for k in range(4))
    i = jax.nn.sigmoid(i)
    f = jax.nn.sigmoid(f + forgetBias)
    g = jnp.tanh(g)
    o = jax.nn.sigmoid(o)
    c = f * c_prev + i * g
    h = o * jnp.tanh(c)
    return h, c


@op("lstmLayer")
def _lstm_layer(x, w, r, b=None, h0=None, c0=None, forgetBias=0.0,
                returnFullSequence=True, unroll=4):
    """x: [N, I, T] (DL4J NCW time-series layout). Returns ([N,H,T], hT, cT).

    TPU lowering (the cuDNN-LSTM trick, SURVEY.md §7 hard part 3): the
    input projection x@W for ALL timesteps is hoisted out of the
    recurrence as ONE [T*N, I] x [I, 4H] MXU matmul; only the [N,H] x
    [H,4H] recurrent matmul stays inside the lax.scan (unrolled to cut
    loop overhead), so the sequential chain carries half the FLOPs and
    the rest runs at large-matmul efficiency."""
    n, _, t = x.shape
    hsz = r.shape[0]
    if h0 is None:
        h0 = jnp.zeros((n, hsz), x.dtype)
    if c0 is None:
        c0 = jnp.zeros((n, hsz), x.dtype)

    xs = jnp.moveaxis(x, 2, 0)  # [T, N, I]
    xw = xs @ w                 # [T, N, 4H] — one batched MXU matmul
    if b is not None:
        xw = xw + b

    # Pallas recurrence kernel when shapes/dtype allow: h, c and R stay
    # VMEM-resident across all timesteps (kernels/lstm.py documents the
    # design and bounds). kernels.recurrence_route makes and COUNTS the
    # decision; under a GSPMD-sharded step the kernel runs per batch
    # shard (kernels.per_batch_shard)
    from deeplearning4j_tpu import kernels
    from deeplearning4j_tpu.kernels.lstm import lstm_seq, lstm_seq_available

    route = kernels.recurrence_route(
        "LSTM", r.dtype == jnp.float32
        and lstm_seq_available(kernels.shard_rows(n), hsz, x.dtype))
    if route != "scan":
        xw_k = xw.astype(jnp.float32)
        if forgetBias:
            xw_k = xw_k.at[:, :, hsz:2 * hsz].add(forgetBias)
        interpret = route == "interpret"
        seq = kernels.per_batch_shard(
            lambda xw_, r_, h_, c_: lstm_seq(xw_, r_, h_, c_, interpret),
            n, batch_dims=(1, None, 0, 0), out_batch_dims=(1, 0, 0))
        hs_k, hT, cT = seq(xw_k, r, h0.astype(jnp.float32),
                           c0.astype(jnp.float32))
        out = jnp.moveaxis(hs_k, 0, 2)
        if not returnFullSequence:
            return hT, hT, cT
        return out, hT, cT

    def step(carry, xw_t):
        h, c = carry
        z = xw_t + h @ r
        i, f, g, o = (z[..., k * hsz:(k + 1) * hsz] for k in range(4))
        i = jax.nn.sigmoid(i)
        f = jax.nn.sigmoid(f + forgetBias)
        g = jnp.tanh(g)
        o = jax.nn.sigmoid(o)
        c2 = f * c + i * g
        h2 = o * jnp.tanh(c2)
        return (h2, c2), h2

    (hT, cT), hs = lax.scan(step, (h0, c0), xw,
                            unroll=min(unroll, t))
    out = jnp.moveaxis(hs, 0, 2)  # [N, H, T]
    if not returnFullSequence:
        return hT, hT, cT
    return out, hT, cT


@op("gruCell")
def _gru_cell(x, h_prev, w, r, b=None):
    """x:[N,I], h_prev:[N,H], w:[I,3H], r:[H,3H], b:[6H] (ru then c, input
    and recurrent biases separate, like libnd4j gruCell)."""
    hsz = h_prev.shape[-1]
    wz = x @ w
    rz = h_prev @ r
    if b is not None:
        wz = wz + b[: 3 * hsz]
        rz = rz + b[3 * hsz:]
    ru_w, c_w = wz[..., : 2 * hsz], wz[..., 2 * hsz:]
    ru_r, c_r = rz[..., : 2 * hsz], rz[..., 2 * hsz:]
    ru = jax.nn.sigmoid(ru_w + ru_r)
    rgate, ugate = ru[..., :hsz], ru[..., hsz:]
    cand = jnp.tanh(c_w + rgate * c_r)
    return ugate * h_prev + (1 - ugate) * cand


@op("gruLayer")
def _gru_layer(x, w, r, b=None, h0=None, unroll=4, resetAfter=True,
               activation="tanh"):
    """Input projection hoisted out of the scan (same lowering as
    lstmLayer); the reset-gated candidate keeps only h@r sequential.
    On TPU the Pallas recurrence kernel (kernels/gru.py) takes over when
    shapes allow (kernels.recurrence_route counts the decision).

    Gate layout [reset | update | candidate]. resetAfter=True (cuDNN /
    Keras v2 convention): candidate = tanh(c_w + r * (h@Rc + rb_c)),
    bias b is [3H input || 3H recurrent]. resetAfter=False (classic
    Cho et al. / Keras reset_after=False): candidate =
    tanh(c_w + (r*h)@Rc), bias b is 3H input-side only."""
    n, _, t = x.shape
    hsz = r.shape[0]
    if h0 is None:
        h0 = jnp.zeros((n, hsz), x.dtype)
    xs = jnp.moveaxis(x, 2, 0)            # [T, N, I]
    xw = xs @ w                           # [T, N, 3H] — one MXU matmul
    if b is not None:
        xw = xw + b[: 3 * hsz]
    rb = b[3 * hsz:] if b is not None and b.shape[0] > 3 * hsz else None
    act = OPS[activation]

    if not resetAfter:
        def step_before(h, xw_t):
            ru_w, c_w = xw_t[..., : 2 * hsz], xw_t[..., 2 * hsz:]
            ru = jax.nn.sigmoid(ru_w + h @ r[:, : 2 * hsz])
            rgate, ugate = ru[..., :hsz], ru[..., hsz:]
            cand = act(c_w + (rgate * h) @ r[:, 2 * hsz:])
            h2 = ugate * h + (1.0 - ugate) * cand
            return h2, h2

        hT, hs = lax.scan(step_before, h0, xw, unroll=min(unroll, t))
        return jnp.moveaxis(hs, 0, 2), hT

    from deeplearning4j_tpu import kernels
    from deeplearning4j_tpu.kernels.gru import gru_seq, gru_seq_available

    route = kernels.recurrence_route(
        "GRU", activation == "tanh"  # the Pallas kernel fixes tanh
        and r.dtype == jnp.float32
        and gru_seq_available(kernels.shard_rows(n), hsz, x.dtype))
    if route != "scan":
        rb_k = (jnp.zeros((3 * hsz,), jnp.float32) if rb is None
                else rb.astype(jnp.float32))
        interpret = route == "interpret"
        seq = kernels.per_batch_shard(
            lambda xw_, r_, rb_, h_: gru_seq(xw_, r_, rb_, h_, interpret),
            n, batch_dims=(1, None, None, 0), out_batch_dims=(1, 0))
        hs_k, hT = seq(xw.astype(jnp.float32), r, rb_k,
                       h0.astype(jnp.float32))
        return jnp.moveaxis(hs_k, 0, 2), hT

    def step(h, xw_t):
        rz = h @ r
        if rb is not None:
            rz = rz + rb
        ru_w, c_w = xw_t[..., : 2 * hsz], xw_t[..., 2 * hsz:]
        ru_r, c_r = rz[..., : 2 * hsz], rz[..., 2 * hsz:]
        ru = jax.nn.sigmoid(ru_w + ru_r)
        rgate, ugate = ru[..., :hsz], ru[..., hsz:]
        cand = act(c_w + rgate * c_r)
        h2 = ugate * h + (1.0 - ugate) * cand
        return h2, h2

    hT, hs = lax.scan(step, h0, xw, unroll=min(unroll, t))
    return jnp.moveaxis(hs, 0, 2), hT


@op("simpleRnnLayer")
def _simple_rnn_layer(x, w, r, b=None, h0=None, activation="tanh",
                      unroll=4):
    n, _, t = x.shape
    hsz = r.shape[0]
    if h0 is None:
        h0 = jnp.zeros((n, hsz), x.dtype)
    act = OPS[activation]
    xs = jnp.moveaxis(x, 2, 0)
    xw = xs @ w                           # hoisted input projection
    if b is not None:
        xw = xw + b

    def step(h, xw_t):
        h2 = act(xw_t + h @ r)
        return h2, h2

    hT, hs = lax.scan(step, h0, xw, unroll=min(unroll, t))
    return jnp.moveaxis(hs, 0, 2), hT


# ---------------------------------------------------------------------------
# attention (the reference's multiHeadDotProductAttention declarable op;
# here the soft path — the Pallas flash kernel plugs in via ops/attention)
# ---------------------------------------------------------------------------

@op("dotProductAttention")
def _dot_product_attention(q, k, v, mask=None, scaled=True):
    """q:[..., T_q, D], k:[..., T_k, D], v:[..., T_k, Dv]."""
    scale = 1.0 / _math.sqrt(q.shape[-1]) if scaled else 1.0
    logits = (q * scale) @ jnp.swapaxes(k, -1, -2)
    if mask is not None:
        logits = jnp.where(mask.astype(bool), logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    return w @ v


@op("multiHeadDotProductAttention")
def _mhdpa(q, k, v, wq, wk, wv, wo, mask=None, numHeads=1, scaled=True):
    """Batched multi-head attention: q/k/v [N, T, E]; wq/wk/wv [E, H*Dh],
    wo [H*Dh, E]."""
    n, tq, e = q.shape
    h = numHeads

    def heads(x, wm):
        y = x @ wm
        return y.reshape(n, x.shape[1], h, -1).transpose(0, 2, 1, 3)

    qh, kh, vh = heads(q, wq), heads(k, wk), heads(v, wv)
    if mask is not None and mask.ndim == 2:
        mask = mask[:, None, None, :]
    o = _dot_product_attention(qh, kh, vh, mask, scaled)
    o = o.transpose(0, 2, 1, 3).reshape(n, tq, -1)
    return o @ wo


# ---------------------------------------------------------------------------
# losses (reference: SDLoss / org.nd4j.linalg.lossfunctions)
# ---------------------------------------------------------------------------

def _reduce_loss(per_ex, weights, reduction):
    if weights is not None:
        per_ex = per_ex * weights
    if reduction in ("MEAN_BY_NONZERO_WEIGHT_COUNT", "MEAN_BY_WEIGHT"):
        if weights is not None:
            denom = jnp.maximum(jnp.sum(weights != 0), 1)
            return jnp.sum(per_ex) / denom
        return jnp.mean(per_ex)
    if reduction == "SUM":
        return jnp.sum(per_ex)
    if reduction == "NONE":
        return per_ex
    return jnp.mean(per_ex)


@op("softmaxCrossEntropy")
def _softmax_ce(logits, labels, weights=None, labelSmoothing=0.0,
                reduction="MEAN_BY_NONZERO_WEIGHT_COUNT"):
    nc = logits.shape[-1]
    if labelSmoothing > 0:
        labels = labels * (1 - labelSmoothing) + labelSmoothing / nc
    lp = jax.nn.log_softmax(logits, axis=-1)
    per_ex = -jnp.sum(labels * lp, axis=-1)
    return _reduce_loss(per_ex, weights, reduction)


@op("sparseSoftmaxCrossEntropy")
def _sparse_softmax_ce(logits, labels, reduction="MEAN_BY_NONZERO_WEIGHT_COUNT"):
    lp = jax.nn.log_softmax(logits, axis=-1)
    per_ex = -jnp.take_along_axis(
        lp, labels.astype(jnp.int32)[..., None], axis=-1
    )[..., 0]
    return _reduce_loss(per_ex, None, reduction)


@op("sigmoidCrossEntropy")
def _sigmoid_ce(logits, labels, weights=None,
                reduction="MEAN_BY_NONZERO_WEIGHT_COUNT"):
    per = jnp.maximum(logits, 0) - logits * labels + jnp.log1p(
        jnp.exp(-jnp.abs(logits))
    )
    per_ex = jnp.mean(per, axis=tuple(range(1, per.ndim)))
    return _reduce_loss(per_ex, weights, reduction)


@op("meanSquaredError")
def _mse(predictions, labels, weights=None,
         reduction="MEAN_BY_NONZERO_WEIGHT_COUNT"):
    per = (predictions - labels) ** 2
    per_ex = jnp.mean(per, axis=tuple(range(1, per.ndim)))
    return _reduce_loss(per_ex, weights, reduction)


@op("absoluteDifference")
def _mae(predictions, labels, weights=None,
         reduction="MEAN_BY_NONZERO_WEIGHT_COUNT"):
    per = jnp.abs(predictions - labels)
    per_ex = jnp.mean(per, axis=tuple(range(1, per.ndim)))
    return _reduce_loss(per_ex, weights, reduction)


@op("huberLoss")
def _huber(predictions, labels, weights=None, delta=1.0,
           reduction="MEAN_BY_NONZERO_WEIGHT_COUNT"):
    err = jnp.abs(predictions - labels)
    per = jnp.where(err <= delta, 0.5 * err**2, delta * err - 0.5 * delta**2)
    per_ex = jnp.mean(per, axis=tuple(range(1, per.ndim)))
    return _reduce_loss(per_ex, weights, reduction)


@op("logLoss")
def _log_loss(predictions, labels, weights=None, epsilon=1e-7,
              reduction="MEAN_BY_NONZERO_WEIGHT_COUNT"):
    p = jnp.clip(predictions, epsilon, 1 - epsilon)
    per = -(labels * jnp.log(p) + (1 - labels) * jnp.log(1 - p))
    per_ex = jnp.mean(per, axis=tuple(range(1, per.ndim)))
    return _reduce_loss(per_ex, weights, reduction)


@op("hingeLoss")
def _hinge(predictions, labels, weights=None,
           reduction="MEAN_BY_NONZERO_WEIGHT_COUNT"):
    # labels in {0,1} -> {-1,1} like SDLoss.hingeLoss
    y = 2.0 * labels - 1.0
    per = jnp.maximum(0.0, 1.0 - y * predictions)
    per_ex = jnp.mean(per, axis=tuple(range(1, per.ndim)))
    return _reduce_loss(per_ex, weights, reduction)


@op("cosineDistance")
def _cosine_distance(predictions, labels, weights=None, dimension=-1,
                     reduction="MEAN_BY_NONZERO_WEIGHT_COUNT"):
    per_ex = 1.0 - jnp.sum(predictions * labels, axis=dimension)
    return _reduce_loss(per_ex, weights, reduction)


@op("klDivergence")
def _kld(predictions, labels, reduction="MEAN_BY_NONZERO_WEIGHT_COUNT"):
    per = labels * (jnp.log(jnp.maximum(labels, 1e-12)) -
                    jnp.log(jnp.maximum(predictions, 1e-12)))
    per_ex = jnp.sum(per, axis=tuple(range(1, per.ndim)))
    return _reduce_loss(per_ex, None, reduction)


# ---------------------------------------------------------------------------
# control flow
# ---------------------------------------------------------------------------
# Reference capability: libnd4j control-flow declarables + SameDiff's
# interpretation of TF Enter/Exit/Merge/Switch loops (SURVEY.md §2.1/§3.4;
# VERDICT.md round-1 missing item 5). TPU-first design: the loop/branch
# bodies are ordinary traced functions lowered to lax.while_loop /
# lax.cond / lax.scan — ONE compiled XLA op each, no per-iteration
# dispatch. Bodies are Python callables over jnp arrays, captured as op
# attrs; graphs holding them execute and (for cond/scan) differentiate,
# but cannot be serialized (same boundary the reference draws: its
# control-flow sub-graphs serialize as FlatBuffers function defs, ours
# would need the callable's source).

@op("whileLoop")
def _while_loop(*state, cond_fn=None, body_fn=None):
    """state -> final state after `while cond_fn(*state): state =
    body_fn(*state)`. Forward-only (XLA while has no reverse-mode)."""
    out = lax.while_loop(lambda s: cond_fn(*s),
                         lambda s: tuple(body_fn(*s)), tuple(state))
    return out if len(out) > 1 else out[0]


@op("ifCond")
def _if_cond(pred, *operands, true_fn=None, false_fn=None):
    out = lax.cond(jnp.asarray(pred).astype(bool).reshape(()),
                   lambda ops: _as_tuple(true_fn(*ops)),
                   lambda ops: _as_tuple(false_fn(*ops)), tuple(operands))
    return out if len(out) > 1 else out[0]


@op("scanOp")
def _scan_op(init, xs, body_fn=None):
    """lax.scan over leading axis of xs; body_fn(carry, x) -> (carry, y).
    Returns (final_carry, stacked_ys); reverse-mode differentiable."""
    return lax.scan(body_fn, init, xs)


@op("forLoop")
def _for_loop(*state, n=None, body_fn=None):
    """n fixed iterations: state = body_fn(i, *state) (fori_loop)."""
    out = lax.fori_loop(0, n, lambda i, s: tuple(body_fn(i, *s)),
                        tuple(state))
    return out if len(out) > 1 else out[0]


def _as_tuple(v):
    return v if isinstance(v, tuple) else (v,)


# ---------------------------------------------------------------------------
# TF-import support ops (registered statically so graphs holding them
# execute after save/load in a fresh process)
# ---------------------------------------------------------------------------

@op("tfEinsum")
def _tf_einsum(*xs, equation=None):
    return jnp.einsum(equation, *xs)


@op("tfZerosLike")
def _tf_zeros_like(x):
    return jnp.zeros_like(x)


@op("tfOnesLike")
def _tf_ones_like(x):
    return jnp.ones_like(x)


@op("tfStridedSlice")
def _tf_strided_slice(x, idx=None):
    import numpy as _np

    return x[tuple(
        (_np.newaxis if i is None else
         (slice(*i) if isinstance(i, (list, tuple)) else i))
        for i in idx)]


# ---------------------------------------------------------------------------
# linear algebra (reference: nd4j SDLinalg / libnd4j blas parity ops —
# cholesky, solve, matrix_inverse, svd, qr, lu, matrix_band_part, ...)
# ---------------------------------------------------------------------------

OPS["cholesky"] = jnp.linalg.cholesky
OPS["matrixInverse"] = jnp.linalg.inv
OPS["matrixDeterminant"] = jnp.linalg.det
OPS["logdet"] = lambda x: jnp.linalg.slogdet(x)[1]
OPS["trace"] = lambda x: jnp.trace(x, axis1=-2, axis2=-1)


@op("solve")
def _solve(a, b, adjoint=False):
    if adjoint:
        a = jnp.swapaxes(a, -1, -2).conj()
    return jnp.linalg.solve(a, b)


@op("triangularSolve")
def _triangular_solve(a, b, lower=True, adjoint=False):
    import jax.scipy.linalg as jsl

    return jsl.solve_triangular(a, b, lower=lower,
                                trans=2 if adjoint else 0)


@op("svd")
def _svd(x, fullUV=False, computeUV=True):
    # computeUV accepted for parity; U/V are always produced so the op's
    # graph arity stays fixed at 3 (XLA drops unused outputs anyway)
    u, s, vh = jnp.linalg.svd(x, full_matrices=fullUV)
    return s, u, jnp.swapaxes(vh, -1, -2)  # DL4J returns (s, u, v)


@op("qr")
def _qr(x, fullMatrices=False):
    return jnp.linalg.qr(x, mode="complete" if fullMatrices else "reduced")


@op("lu")
def _lu(x):
    import jax.scipy.linalg as jsl

    lu, piv = jsl.lu_factor(x)
    return lu, piv


@op("lstsq")
def _lstsq(a, b, fast=True):
    return jnp.linalg.lstsq(a, b)[0]


@op("matrixBandPart")
def _matrix_band_part(x, minLower=-1, maxUpper=-1):
    m, n = x.shape[-2], x.shape[-1]
    i = jnp.arange(m)[:, None]
    j = jnp.arange(n)[None, :]
    keep = jnp.ones((m, n), bool)
    if minLower >= 0:
        keep = keep & (i - j <= minLower)
    if maxUpper >= 0:
        keep = keep & (j - i <= maxUpper)
    return jnp.where(keep, x, jnp.zeros_like(x))


OPS["triu"] = lambda x, diag=0: jnp.triu(x, k=diag)
OPS["tril"] = lambda x, diag=0: jnp.tril(x, k=diag)
OPS["diagPart"] = lambda x: jnp.diagonal(x, axis1=-2, axis2=-1)


# ---------------------------------------------------------------------------
# segment reductions (reference: libnd4j parity_ops segment_* /
# unsorted_segment_*) — num_segments must be static under jit
# ---------------------------------------------------------------------------

def _num_segments(ids, numSegments):
    if numSegments is not None:
        return int(numSegments)
    try:
        return int(jnp.max(ids)) + 1
    except jax.errors.ConcretizationTypeError as e:
        raise ValueError(
            "segment ops need numSegments when the ids are traced "
            "(static output shape under jit); pass numSegments "
            "explicitly") from e


def _segment(reducer):
    def f(data, ids, numSegments=None):
        ids = jnp.asarray(ids, jnp.int32)
        return reducer(data, ids,
                       num_segments=_num_segments(ids, numSegments))
    return f


OPS["segmentSum"] = OPS["unsortedSegmentSum"] = _segment(jax.ops.segment_sum)
OPS["segmentMax"] = OPS["unsortedSegmentMax"] = _segment(jax.ops.segment_max)
OPS["segmentMin"] = OPS["unsortedSegmentMin"] = _segment(jax.ops.segment_min)
OPS["segmentProd"] = OPS["unsortedSegmentProd"] = _segment(
    jax.ops.segment_prod)


@op("segmentMean")
def _segment_mean(data, ids, numSegments=None):
    ids = jnp.asarray(ids, jnp.int32)
    n = _num_segments(ids, numSegments)
    s = jax.ops.segment_sum(data, ids, num_segments=n)
    c = jax.ops.segment_sum(jnp.ones_like(data), ids, num_segments=n)
    return s / jnp.maximum(c, 1)


OPS["unsortedSegmentMean"] = _segment_mean


# ---------------------------------------------------------------------------
# topK / misc (reference: parity ops top_k, in_top_k, confusion_matrix,
# bincount, zero_fraction)
# ---------------------------------------------------------------------------

@op("topK")
def _top_k(x, k=1, sorted=True):  # noqa: A002
    return lax.top_k(x, int(k))


@op("inTopK")
def _in_top_k(predictions, targets, k=1):
    _, idx = lax.top_k(predictions, int(k))
    return jnp.any(idx == targets[..., None], axis=-1)


@op("confusionMatrix")
def _confusion_matrix(labels, pred, numClasses):
    n = int(numClasses)
    idx = jnp.asarray(labels, jnp.int32) * n + jnp.asarray(pred, jnp.int32)
    return jnp.bincount(idx, length=n * n).reshape(n, n)


@op("bincount")
def _bincount(x, weights=None, minLength=0, maxLength=None):
    """DL4J bincount(values, weights, minLength, maxLength). With
    maxLength the output length is static (values >= it are dropped,
    TF maxlength semantics — required under jit); otherwise the length
    is max(values)+1 extended to minLength, which needs concrete
    values."""
    x = jnp.asarray(x, jnp.int32)
    if maxLength is not None:
        n = max(int(minLength), int(maxLength))
        return jnp.bincount(x, weights, length=n)
    try:
        m = int(jnp.max(x)) + 1
    except jax.errors.ConcretizationTypeError as e:
        raise ValueError(
            "bincount without maxLength needs concrete values; inside a "
            "jitted graph pass maxLength for a static output size") from e
    return jnp.bincount(x, weights, length=max(m, int(minLength)))


OPS["zeroFraction"] = lambda x: jnp.mean((x == 0).astype(jnp.float32))


# ---------------------------------------------------------------------------
# image / spatial ops (reference: libnd4j parity image ops — resize,
# extract_image_patches, space_to_batch, batch_to_space, s2d/d2s; the
# reference routes these through custom kernels, here jax.image / lax)
# ---------------------------------------------------------------------------

def _area_weight_matrix(n_in, n_out):
    """(n_out, n_in) row-stochastic overlap weights: output cell i spans
    input range [i*s, (i+1)*s), s = n_in/n_out; each input pixel
    contributes its fractional overlap (TF ResizeArea region averaging,
    valid for any ratio incl. upscale). Host-side numpy — shapes are
    static at trace time."""
    import numpy as np

    s = n_in / n_out
    mat = np.zeros((n_out, n_in), np.float32)
    for i in range(n_out):
        lo, hi = i * s, (i + 1) * s
        for j in range(int(np.floor(lo)), min(int(np.ceil(hi)), n_in)):
            mat[i, j] = min(hi, j + 1) - max(lo, j)
        mat[i] /= s
    return mat


@op("imageResize")
def _image_resize(x, height, width, method="bilinear", antialias=False):
    """x: [N,C,H,W] (DL4J layout); method: bilinear|nearest|cubic|
    lanczos3|lanczos5|area. antialias defaults OFF to match the TF/DL4J
    resize ops this mirrors (jax.image.resize's own default is
    antialias=True). `area` averages exact input regions; integer
    downscale factors take the reshape fast path, general ratios go
    through per-axis overlap-weight matmuls (TF ResizeArea semantics,
    MXU-shaped)."""
    height, width = int(height), int(width)
    n, c, h, w = x.shape
    m = str(method).lower()
    if m == "area":
        if h % height == 0 and w % width == 0:
            fh, fw = h // height, w // width
            return x.reshape(n, c, height, fh, width, fw).mean(
                axis=(3, 5))
        # contract in f32 regardless of input dtype (integer images would
        # truncate the fractional weights to zero; matches the integer
        # fast path, whose .mean() also yields float) at full precision —
        # resize is an exact-semantics op, the MXU bf16 default would
        # shift pixel values visibly
        xf = x.astype(jnp.float32)
        wh = jnp.asarray(_area_weight_matrix(h, height))
        ww = jnp.asarray(_area_weight_matrix(w, width))
        return jnp.einsum("nchw,Hh,Ww->ncHW", xf, wh, ww,
                          precision=lax.Precision.HIGHEST)
    meth = {"bilinear": "bilinear", "nearest": "nearest",
            "cubic": "cubic", "bicubic": "cubic",
            "lanczos3": "lanczos3", "lanczos5": "lanczos5"}[m]
    return jax.image.resize(x, (n, c, height, width), meth,
                            antialias=antialias)


@op("extractImagePatches")
def _extract_image_patches(x, kH, kW, sH=1, sW=1, sameMode=False):
    """TF/DL4J extract_image_patches orders the patch feature dim
    patch-position-major with depth fastest — (kh, kw, c) — while
    lax.conv_general_dilated_patches emits channel-major (c, kh, kw);
    permute to match the reference op's ordering."""
    pad = "SAME" if sameMode else "VALID"
    kH, kW = int(kH), int(kW)
    c = x.shape[1]
    p = lax.conv_general_dilated_patches(
        x, (kH, kW), (int(sH), int(sW)), pad,
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    n, _, oh, ow = p.shape
    p = p.reshape(n, c, kH, kW, oh, ow)
    return jnp.transpose(p, (0, 2, 3, 1, 4, 5)).reshape(
        n, kH * kW * c, oh, ow)


@op("spaceToDepth")
def _space_to_depth(x, blockSize=2):
    n, c, h, w = x.shape
    b = int(blockSize)
    x = x.reshape(n, c, h // b, b, w // b, b)
    return jnp.transpose(x, (0, 3, 5, 1, 2, 4)).reshape(
        n, c * b * b, h // b, w // b)


@op("depthToSpace")
def _depth_to_space(x, blockSize=2):
    n, c, h, w = x.shape
    b = int(blockSize)
    cout = c // (b * b)
    x = x.reshape(n, b, b, cout, h, w)
    return jnp.transpose(x, (0, 3, 4, 1, 5, 2)).reshape(
        n, cout, h * b, w * b)


@op("spaceToBatch")
def _space_to_batch(x, blockSize=2, padding=((0, 0), (0, 0))):
    n, c, h, w = x.shape
    b = int(blockSize)
    x = jnp.pad(x, ((0, 0), (0, 0)) + tuple(tuple(p) for p in padding))
    h2, w2 = x.shape[2], x.shape[3]
    x = x.reshape(n, c, h2 // b, b, w2 // b, b)
    return jnp.transpose(x, (3, 5, 0, 1, 2, 4)).reshape(
        n * b * b, c, h2 // b, w2 // b)


@op("batchToSpace")
def _batch_to_space(x, blockSize=2, crop=((0, 0), (0, 0))):
    nb, c, h, w = x.shape
    b = int(blockSize)
    n = nb // (b * b)
    x = x.reshape(b, b, n, c, h, w)
    x = jnp.transpose(x, (2, 3, 4, 0, 5, 1)).reshape(n, c, h * b, w * b)
    (ct, cb), (cl, cr) = crop
    return x[:, :, ct: x.shape[2] - cb, cl: x.shape[3] - cr]


# ---------------------------------------------------------------------------
# special functions (reference: libnd4j transforms — lgamma, digamma,
# igamma, betainc, erfc, zeta)
# ---------------------------------------------------------------------------

OPS["erfc"] = jax.scipy.special.erfc
OPS["lgamma"] = jax.scipy.special.gammaln
OPS["digamma"] = jax.scipy.special.digamma
OPS["igamma"] = jax.scipy.special.gammainc
OPS["igammac"] = jax.scipy.special.gammaincc
OPS["betainc"] = jax.scipy.special.betainc
OPS["atan2"] = jnp.arctan2
OPS["expm1"] = jnp.expm1
OPS["asinh"] = jnp.arcsinh
OPS["acosh"] = jnp.arccosh
OPS["atanh"] = jnp.arctanh


# ---------------------------------------------------------------------------
# CTC loss (reference: libnd4j ctc_loss declarable / SameDiff ctcLoss).
# TPU-first design: the forward (alpha) recursion in log space as ONE
# lax.scan over time — no per-timestep host dispatch, fully batched,
# differentiable by jax.grad (the reference ships a hand-written
# ctcLossGrad; reverse-mode through the scan supplies it here).
# ---------------------------------------------------------------------------

@op("ctcLoss")
def _ctc_loss(targetLabels, logitInput, targetLabelLengths=None,
              logitInputLengths=None, blankIndex=0):
    """targetLabels: [B, U] int labels (padded); logitInput: [B, T, C]
    UNNORMALIZED logits; lengths: [B] ints. Returns per-example negative
    log likelihood [B]."""
    labels = jnp.asarray(targetLabels, jnp.int32)
    logits = logitInput
    b, u = labels.shape
    t_max, c = logits.shape[1], logits.shape[2]
    if targetLabelLengths is None:
        targetLabelLengths = jnp.full((b,), u, jnp.int32)
    if logitInputLengths is None:
        logitInputLengths = jnp.full((b,), t_max, jnp.int32)
    lab_len = jnp.asarray(targetLabelLengths, jnp.int32)
    log_len = jnp.asarray(logitInputLengths, jnp.int32)
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)

    s = 2 * u + 1
    neg_inf = jnp.float32(-1e30)
    # extended sequence [blank, l1, blank, ..., lU, blank]
    ext = jnp.full((b, s), blankIndex, jnp.int32)
    ext = ext.at[:, 1::2].set(labels)
    is_lab = jnp.arange(s) % 2 == 1
    ext_m2 = jnp.concatenate(
        [jnp.full((b, 2), -1, jnp.int32), ext[:, :-2]], axis=1)
    allow_skip = is_lab[None, :] & (ext != ext_m2)

    def lp_ext(t_lp):
        return jnp.take_along_axis(t_lp, ext, axis=1)  # [B, S]

    alpha0 = jnp.full((b, s), neg_inf)
    first = lp_ext(lp[:, 0])
    alpha0 = alpha0.at[:, 0].set(first[:, 0])
    if s > 1:
        alpha0 = alpha0.at[:, 1].set(first[:, 1])

    def step(alpha, inputs):
        t_lp, t_idx = inputs
        a1 = jnp.concatenate(
            [jnp.full((b, 1), neg_inf), alpha[:, :-1]], axis=1)
        a2 = jnp.concatenate(
            [jnp.full((b, 2), neg_inf), alpha[:, :-2]], axis=1)
        a2 = jnp.where(allow_skip, a2, neg_inf)
        stacked = jnp.stack([alpha, a1, a2])
        new = jax.scipy.special.logsumexp(stacked, axis=0) + lp_ext(t_lp)
        # freeze past each example's input length
        live = (t_idx < log_len)[:, None]
        return jnp.where(live, new, alpha), None

    alpha, _ = lax.scan(
        step, alpha0,
        (jnp.moveaxis(lp[:, 1:], 1, 0), jnp.arange(1, t_max)))

    end = 2 * lab_len  # index of final blank state
    a_end = jnp.take_along_axis(alpha, end[:, None], axis=1)[:, 0]
    a_last = jnp.take_along_axis(
        alpha, jnp.maximum(end - 1, 0)[:, None], axis=1)[:, 0]
    a_last = jnp.where(lab_len > 0, a_last, neg_inf)
    return -jax.scipy.special.logsumexp(
        jnp.stack([a_end, a_last]), axis=0)


# ---------------------------------------------------------------------------
# non-max suppression as a REGISTERED op (reference: libnd4j
# non_max_suppression declarable; the host-side YoloUtils path remains
# for detection post-processing, this one is jittable in-graph)
# ---------------------------------------------------------------------------

@op("nonMaxSuppression")
def _non_max_suppression(boxes, scores, maxOutputSize=10,
                         iouThreshold=0.5, scoreThreshold=None):
    """boxes [N,4] (y1,x1,y2,x2), scores [N] -> selected indices
    [maxOutputSize] int32, padded with -1 (static shape for jit)."""
    n = boxes.shape[0]
    k = int(maxOutputSize)
    y1, x1, y2, x2 = (boxes[:, i] for i in range(4))
    area = jnp.maximum(y2 - y1, 0) * jnp.maximum(x2 - x1, 0)
    iy1 = jnp.maximum(y1[:, None], y1[None, :])
    ix1 = jnp.maximum(x1[:, None], x1[None, :])
    iy2 = jnp.minimum(y2[:, None], y2[None, :])
    ix2 = jnp.minimum(x2[:, None], x2[None, :])
    inter = (jnp.maximum(iy2 - iy1, 0) * jnp.maximum(ix2 - ix1, 0))
    union = area[:, None] + area[None, :] - inter
    iou = jnp.where(union > 0, inter / union, 0.0)

    live = jnp.ones((n,), bool)
    if scoreThreshold is not None:
        live = live & (scores >= scoreThreshold)

    def body(i, carry):
        live, out = carry
        masked = jnp.where(live, scores, -jnp.inf)
        idx = jnp.argmax(masked)
        ok = masked[idx] > -jnp.inf
        out = out.at[i].set(jnp.where(ok, idx.astype(jnp.int32), -1))
        # drop the pick and everything overlapping it — STRICTLY above
        # the threshold (TF/libnd4j semantics: iou > threshold
        # suppresses; boundary-equal survives)
        suppress = iou[idx] > iouThreshold
        live = live & ~suppress & ok
        live = live.at[idx].set(False)
        return live, out

    _, out = lax.fori_loop(0, k, body,
                           (live, jnp.full((k,), -1, jnp.int32)))
    return out


# ---------------------------------------------------------------------------
# round-3 declarable widening: shape/index utilities (reference: libnd4j
# transforms — roll, eye, repeat, flip, sort/argsort, scatter, fill)
# ---------------------------------------------------------------------------

@op("roll")
def _roll(x, shift=1, dimensions=None):
    return jnp.roll(x, shift, axis=_axis(dimensions, x.ndim))


@op("eye")
def _eye(rows=None, cols=None, dtype="float32"):
    return jnp.eye(int(rows), None if cols is None else int(cols),
                   dtype=jnp.dtype(dtype))


@op("repeat")
def _repeat(x, repeats=1, dimension=0):
    return jnp.repeat(x, int(repeats), axis=int(dimension))


OPS["flip"] = OPS["reverse"]   # TF/DL4J name alias for the same op


@op("sort")
def _sort(x, dimension=-1, descending=False):
    y = jnp.sort(x, axis=dimension)
    return jnp.flip(y, axis=dimension) if descending else y


@op("argsort")
def _argsort(x, dimension=-1, descending=False):
    i = jnp.argsort(x, axis=dimension)
    return jnp.flip(i, axis=dimension) if descending else i


@op("fill")
def _fill(shape=None, value=0.0, dtype="float32"):
    return jnp.full(tuple(int(s) for s in shape), value,
                    jnp.dtype(dtype))


@op("tensorScatterUpdate")
def _tensor_scatter_update(x, indices, updates):
    """TF tensor_scatter_nd_update semantics: indices [N, K] index the
    first K dims of x; updates [N, ...]."""
    idx = tuple(jnp.moveaxis(jnp.asarray(indices), -1, 0))
    return jnp.asarray(x).at[idx].set(updates)


@op("uniqueWithCounts")
def _unique_with_counts(x, size=None):
    """Static-shape unique (XLA needs fixed shapes): returns
    (values [size], counts [size]) padded with the first value /
    zero counts. `size` defaults to x.size."""
    flat = x.reshape(-1)
    n = flat.shape[0] if size is None else int(size)
    # jnp.unique(size=n) zero-pads counts and fills values itself
    return jnp.unique(flat, return_counts=True, size=n,
                      fill_value=flat[0])


# ---------------------------------------------------------------------------
# r4 registry widening (VERDICT r3 item 8): image adjustments/colorspace,
# scatter variants, separable conv / LRN / dilation, sequence utilities,
# loss variants, noise layers. Reference: libnd4j declarable families
# ops/declarable/generic/{parity_ops,transforms,nn,loss} (SURVEY.md §2.1).
# ---------------------------------------------------------------------------

@op("cross")
def _cross(a, b):
    return jnp.cross(a, b, axis=-1)


OPS["rint"] = jnp.rint
OPS["erfinv"] = lambda x: jax.scipy.special.erfinv(x)


@op("reverseSequence")
def _reverse_sequence(x, seq_lengths, seqAxis=1, batchAxis=0):
    """Reverse the first seq_lengths[b] elements along seqAxis per batch
    row (TF reverse_sequence / DL4J reverse_sequence)."""
    t = x.shape[seqAxis]
    idx = jnp.arange(t)
    sl = jnp.asarray(seq_lengths)

    def rev_row(row, n):
        # positions < n map to n-1-pos, others stay
        src = jnp.where(idx < n, n - 1 - idx, idx)
        return jnp.take(row, src, axis=seqAxis - 1 if seqAxis > batchAxis
                        else seqAxis)

    return jax.vmap(rev_row, in_axes=(batchAxis, 0),
                    out_axes=batchAxis)(x, sl)


@op("histogramFixedWidth")
def _histogram_fixed_width(x, range_lo, range_hi, nbins=100):
    lo, hi = float(range_lo), float(range_hi)
    nbins = int(nbins)
    scaled = (x.reshape(-1) - lo) / max(hi - lo, 1e-30) * nbins
    b = jnp.clip(scaled.astype(jnp.int32), 0, nbins - 1)
    return jnp.zeros(nbins, jnp.int32).at[b].add(1)


@op("weightedCrossEntropyWithLogits")
def _weighted_ce(targets, logits, posWeight):
    """TF nn.weighted_cross_entropy_with_logits: pos_weight scales the
    positive term; numerically stable log1p form."""
    log_w = 1.0 + (posWeight - 1.0) * targets
    return ((1.0 - targets) * logits + log_w *
            (jnp.log1p(jnp.exp(-jnp.abs(logits)))
             + jnp.maximum(-logits, 0.0)))


@op("meanPairwiseSquaredError")
def _mpse(labels, predictions, weights=1.0):
    """TF losses.mean_pairwise_squared_error per batch row."""
    d = (predictions - labels).reshape(labels.shape[0], -1)
    n = d.shape[1]
    sum_d = jnp.sum(d, axis=1)
    sum_d2 = jnp.sum(d * d, axis=1)
    per = 2.0 * (n * sum_d2 - sum_d * sum_d) / max(n * (n - 1), 1)
    return jnp.mean(per * weights)


@op("clipByGlobalNorm")
def _clip_by_global_norm(*tensors, clipNorm=1.0):
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(t)) for t in tensors))
    scale = jnp.minimum(1.0, clipNorm / jnp.maximum(gn, 1e-30))
    out = tuple(t * scale for t in tensors)
    return out if len(out) > 1 else out[0]


@op("matrixSetDiag")
def _matrix_set_diag(x, diag):
    x = jnp.asarray(x)
    diag = jnp.asarray(diag)
    n = min(x.shape[-2], x.shape[-1])
    i = jnp.arange(n)
    return x.at[..., i, i].set(diag[..., :n])


def _scatter_variant(mode):
    def f(ref, indices, updates):
        a = jnp.asarray(ref).at[jnp.asarray(indices)]
        return getattr(a, mode)(updates)
    return f


OPS["scatterMax"] = _scatter_variant("max")
OPS["scatterMin"] = _scatter_variant("min")
OPS["scatterMul"] = _scatter_variant("multiply")
OPS["scatterSub"] = lambda ref, idx, upd: \
    jnp.asarray(ref).at[jnp.asarray(idx)].add(-jnp.asarray(upd))


@op("scatterNd")
def _scatter_nd(indices, updates, shape):
    """TF scatter_nd: indices [N,K] into zeros(shape)."""
    idx = tuple(jnp.moveaxis(jnp.asarray(indices), -1, 0))
    return jnp.zeros(tuple(int(s) for s in shape),
                     jnp.asarray(updates).dtype).at[idx].add(updates)


@op("dynamicStitch")
def _dynamic_stitch(indices_list, data_list):
    """TF dynamic_stitch with statically-known index tensors stacked as
    tuples; later entries win on duplicates (TF contract)."""
    import numpy as np

    total = sum(int(np.prod(np.asarray(i).shape))
                for i in indices_list)
    first = jnp.asarray(data_list[0])
    inner = first.shape[len(np.asarray(indices_list[0]).shape):]
    out = jnp.zeros((total,) + inner, first.dtype)
    for ind, dat in zip(indices_list, data_list):
        ind = jnp.asarray(ind).reshape(-1)
        dat = jnp.asarray(dat).reshape((-1,) + inner)
        out = out.at[ind].set(dat)
    return out


@op("mirrorPad")
def _mirror_pad(x, paddings, mode="REFLECT"):
    import numpy as np

    mode = {"REFLECT": "reflect", "SYMMETRIC": "symmetric"}[
        str(mode).upper()]
    pads = [tuple(int(v) for v in p) for p in np.asarray(paddings)]
    return jnp.pad(x, pads, mode=mode)


@op("rot90")
def _rot90(x, k=1, axes=(0, 1)):
    return jnp.rot90(x, int(k), axes=tuple(int(a) for a in axes))


@op("sconv2d")
def _sconv2d(x, depthWeights, pointWeights, strides=(1, 1),
             sameMode=True):
    """Separable conv2d: depthwise [kH,kW,C,M] (TF HWIO-depthwise
    layout) then pointwise [1,1,C*M,F]; NCHW data like conv2d."""
    dwt = jnp.asarray(depthWeights)
    # [kH,kW,C,M] -> depthwiseConv2d's [M, C, kH, kW]
    dw = OPS["depthwiseConv2d"](x, jnp.transpose(dwt, (3, 2, 0, 1)),
                                strides=strides, sameMode=sameMode)
    pw = jnp.asarray(pointWeights)
    f = pw.shape[-1]
    pw_oihw = jnp.transpose(pw.reshape(pw.shape[-2], f)[None, None],
                            (3, 2, 0, 1))
    return OPS["conv2d"](dw, pw_oihw, sameMode=True)


@op("localResponseNormalization")
def _lrn(x, depth=5, bias=1.0, alpha=1.0, beta=0.5):
    """TF nn.local_response_normalization, NCHW input."""
    c = x.shape[1]
    r = int(depth)
    sq = jnp.square(x)
    acc = sum(
        jnp.pad(sq, ((0, 0), (d, 0), (0, 0), (0, 0)))[:, :c]
        if d >= 0 else
        jnp.pad(sq, ((0, 0), (0, -d), (0, 0), (0, 0)))[:, -c:]
        for d in range(-r, r + 1))
    return x / jnp.power(bias + alpha * acc, beta)


@op("dilation2d")
def _dilation2d(x, w, sH=1, sW=1, sameMode=True):
    """Grayscale morphological dilation (TF nn.dilation2d), NCHW x
    [N,C,H,W], w [C,kH,kW]. SAME padding uses -inf (TF semantics):
    padding must never win the max, so the spatial pad is applied
    explicitly before VALID patch extraction."""
    x = jnp.asarray(x)
    w = jnp.asarray(w)
    c, kh, kw = w.shape
    if sameMode:
        # TF SAME pad depends on the strided output size:
        # pad = max((ceil(H/s)-1)*s + k - H, 0) — NOT a flat k-1,
        # which over-pads when stride > 1 and shifts every window
        h, w_in = x.shape[2], x.shape[3]
        oh = -(-h // int(sH))
        ow_ = -(-w_in // int(sW))
        ph = max((oh - 1) * int(sH) + kh - h, 0)
        pw_ = max((ow_ - 1) * int(sW) + kw - w_in, 0)
        # large finite negative, not -inf (one-hot-conv patch
        # extraction computes 0*pad, and -inf would poison it with
        # NaN) and bf16-representable (the TPU conv truncates operands
        # to bf16, where float32-min overflows to -inf)
        x = jnp.pad(x, ((0, 0), (0, 0),
                        (ph // 2, ph - ph // 2),
                        (pw_ // 2, pw_ - pw_ // 2)),
                    constant_values=-1e30)
    patches = lax.conv_general_dilated_patches(
        x, (kh, kw), (int(sH), int(sW)), "VALID",
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        precision=lax.Precision.HIGHEST)
    n, _, oh, ow = patches.shape
    patches = patches.reshape(n, c, kh * kw, oh, ow)
    return jnp.max(patches + w.reshape(1, c, kh * kw, 1, 1), axis=2)


@op("adjustContrast")
def _adjust_contrast(x, factor):
    """Per-channel contrast about the spatial mean, NCHW (DL4J layout;
    the last two axes are H,W). NHWC images use adjustContrastV2, which
    the TF importer routes to."""
    x = jnp.asarray(x)
    mean = jnp.mean(x, axis=(-2, -1), keepdims=True) \
        if x.ndim == 4 else jnp.mean(x)
    return (x - mean) * factor + mean


def _rgb_to_hsv(x):
    """x [..., 3] in [0,1] -> HSV (TF image.rgb_to_hsv)."""
    r, g, b = x[..., 0], x[..., 1], x[..., 2]
    mx = jnp.max(x, axis=-1)
    mn = jnp.min(x, axis=-1)
    d = mx - mn
    safe = jnp.where(d > 0, d, 1.0)
    h = jnp.where(
        mx == r, (g - b) / safe % 6.0,
        jnp.where(mx == g, (b - r) / safe + 2.0, (r - g) / safe + 4.0))
    h = jnp.where(d > 0, h / 6.0, 0.0)
    s = jnp.where(mx > 0, d / jnp.where(mx > 0, mx, 1.0), 0.0)
    return jnp.stack([h, s, mx], axis=-1)


def _hsv_to_rgb(x):
    h, s, v = x[..., 0] * 6.0, x[..., 1], x[..., 2]
    i = jnp.floor(h)
    f = h - i
    p = v * (1 - s)
    q = v * (1 - s * f)
    t = v * (1 - s * (1 - f))
    i = i.astype(jnp.int32) % 6
    r = jnp.choose(i, [v, q, p, p, t, v], mode="clip")
    g = jnp.choose(i, [t, v, v, q, p, p], mode="clip")
    b = jnp.choose(i, [p, p, t, v, v, q], mode="clip")
    return jnp.stack([r, g, b], axis=-1)


OPS["rgbToHsv"] = _rgb_to_hsv
OPS["hsvToRgb"] = _hsv_to_rgb


@op("adjustHue")
def _adjust_hue(x, delta):
    hsv = _rgb_to_hsv(x)
    h = (hsv[..., 0] + delta) % 1.0
    return _hsv_to_rgb(jnp.stack([h, hsv[..., 1], hsv[..., 2]], -1))


@op("adjustSaturation")
def _adjust_saturation(x, factor):
    hsv = _rgb_to_hsv(x)
    s = jnp.clip(hsv[..., 1] * factor, 0.0, 1.0)
    return _hsv_to_rgb(jnp.stack([hsv[..., 0], s, hsv[..., 2]], -1))


@op("randomShuffle", random=True)
def _random_shuffle(x, key=None):
    return jax.random.permutation(key, x, axis=0)


@op("alphaDropout", random=True, training_aware=True)
def _alpha_dropout(x, p=0.05, key=None, training=False):
    """SELU-preserving dropout (Klambauer et al.); identity at
    inference."""
    if not training or key is None or p <= 0:
        return x
    alpha_p = -1.7580993408473766
    keep = jax.random.bernoulli(key, 1.0 - p, x.shape)
    # Klambauer et al. affine correction: a = ((1-p)(1 + p*a'^2))^-1/2
    # restores unit variance (the droped-out mixture has variance
    # (1-p)(1 + p*a'^2) around its mean)
    a = ((1.0 - p) * (1.0 + p * alpha_p ** 2)) ** -0.5
    b = -a * p * alpha_p
    return a * jnp.where(keep, x, alpha_p) + b


@op("gaussianDropout", random=True, training_aware=True)
def _gaussian_dropout(x, p=0.1, key=None, training=False):
    if not training or key is None or p <= 0:
        return x
    std = (p / (1.0 - p)) ** 0.5
    return x * (1.0 + std * jax.random.normal(key, x.shape, x.dtype))


@op("gaussianNoise", random=True, training_aware=True)
def _gaussian_noise(x, stddev=0.1, key=None, training=False):
    if not training or key is None:
        return x
    return x + stddev * jax.random.normal(key, x.shape, x.dtype)


@op("sparseSoftmaxCrossEntropyGrad")
def _sparse_softmax_ce_grad(z, y):
    """TF SparseSoftmaxCrossEntropyWithLogits: (loss [B],
    backprop [B, C])."""
    lp = jax.nn.log_softmax(z, axis=-1)
    loss = -jnp.take_along_axis(
        lp, jnp.asarray(y)[..., None].astype(jnp.int32), axis=-1)[..., 0]
    bp = jax.nn.softmax(z, axis=-1) - jax.nn.one_hot(
        y, z.shape[-1], dtype=z.dtype)
    return loss, bp


@op("adjustContrastV2")
def _adjust_contrast_nhwc(x, factor=1.0):
    """TF AdjustContrastv2: NHWC, per-channel spatial mean."""
    mean = jnp.mean(x, axis=(-3, -2), keepdims=True)
    return (x - mean) * factor + mean
