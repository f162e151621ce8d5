"""Layer configuration classes.

Reference capability: org.deeplearning4j.nn.conf.layers.* (the builder DSL,
SURVEY.md §2.5 "Config DSL") fused with the corresponding runtime impls in
org.deeplearning4j.nn.layers.* ("Layer impls"). The reference splits config
from runtime objects that dispatch per-op JNI calls (SURVEY.md §3.1); here a
layer config IS the runtime: it carries
    init_params(key, dtype)          -> trainable param dict
    init_state(dtype)                -> non-trainable state dict (e.g. BN)
    apply(params, state, x, training, rng) -> (y, new_state)
as pure functions, so a whole network lowers to one jittable step and XLA
does the fusion the reference needed cuDNN platform helpers for (the
LayerHelper seam of SURVEY.md §2.5 is therefore intentionally absent).

Conventions (matching DL4J):
  dense inputs  [N, F]; conv inputs [N, C, H, W]; recurrent inputs [N, C, T].
  dropOut(p) is the RETAIN probability (inverted dropout), as in DL4J.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.autodiff.ops import OPS
from deeplearning4j_tpu.nn.activations import resolve_activation
from deeplearning4j_tpu.nn.losses import resolve_loss
from deeplearning4j_tpu.nn.weights import init_weight
from deeplearning4j_tpu.nn.conf.inputs import (
    ConvolutionalFlatType, ConvolutionalType, FeedForwardType, InputType,
    RecurrentType,
)

LAYER_REGISTRY: dict = {}


def _pair(v):
    if isinstance(v, (list, tuple)):
        return tuple(int(x) for x in v)
    return (int(v), int(v))


def _register(cls):
    LAYER_REGISTRY[cls.__name__] = cls
    return cls


class _Builder:
    """Generic DL4J-style builder: any method call sets the same-named config
    field (e.g. .nIn(784).nOut(100).activation("relu")); build() constructs
    the layer class."""

    def __init__(self, cls, **preset):
        self._cls = cls
        self._kw = dict(preset)

    def __getattr__(self, item):
        if item.startswith("_"):
            raise AttributeError(item)

        def setter(*args):
            self._kw[item] = args[0] if len(args) == 1 else list(args)
            return self

        return setter

    def build(self):
        return self._cls(**self._kw)


class BaseLayer:
    """Common config fields + (de)serialization. Subclasses override
    infer() / init_params() / apply()."""

    # fields every layer inherits from the NeuralNetConfiguration defaults
    # when not set explicitly (reference: NeuralNetConfiguration.Builder
    # global defaults cloned into each layer conf)
    INHERITED = ("activation", "weightInit", "biasInit", "updater", "l1",
                 "l2", "dropOut", "gradientNormalization",
                 "gradientNormalizationThreshold")

    def __init__(self, name=None, activation=None, weightInit=None,
                 biasInit=None, updater=None, l1=None, l2=None, dropOut=None,
                 gradientNormalization=None,
                 gradientNormalizationThreshold=None):
        self.name = name
        self.activation = activation
        self.weightInit = weightInit
        self.biasInit = biasInit
        self.updater = updater
        self.l1 = l1
        self.l2 = l2
        self.dropOut = dropOut
        self.gradientNormalization = gradientNormalization
        self.gradientNormalizationThreshold = gradientNormalizationThreshold

    # -- builder -------------------------------------------------------------
    class _BuilderFactory:
        def __get__(self, obj, cls):
            return lambda **kw: _Builder(cls, **kw)

    Builder = _BuilderFactory()

    def apply_defaults(self, defaults: dict):
        import copy

        for f in self.INHERITED:
            if getattr(self, f, None) is None and f in defaults:
                # deep-copy so layers never share mutable config objects
                # (the reference clones the conf per layer)
                setattr(self, f, copy.deepcopy(defaults[f]))
        if self.activation is None:
            self.activation = "identity"
        if self.weightInit is None:
            self.weightInit = "xavier"
        if self.biasInit is None:
            self.biasInit = 0.0

    # -- shape / params ------------------------------------------------------
    def infer(self, input_type):
        """Set nIn-style fields from input_type; return the output type."""
        return input_type

    def init_params(self, key, dtype=jnp.float32) -> dict:
        return {}

    def init_state(self, dtype=jnp.float32) -> dict:
        return {}

    def apply(self, params, state, x, training, rng):
        return x, state

    def _dropout(self, x, training, rng):
        p = self.dropOut
        if not p or p >= 1.0 or not training or rng is None:
            return x
        keep = jax.random.bernoulli(rng, p, x.shape)
        return jnp.where(keep, x / p, jnp.zeros_like(x))

    def _act(self, x):
        # softmax normalizes the CLASS axis: dim 1 in the DL4J NCW
        # time-series layout [N, C, T] (axis -1 there is time)
        if x.ndim == 3 and self.activation in ("softmax", "logsoftmax"):
            fn = (jax.nn.softmax if self.activation == "softmax"
                  else jax.nn.log_softmax)
            return fn(x, axis=1)
        return resolve_activation(self.activation or "identity")(x)

    # -- serde ---------------------------------------------------------------
    def to_json(self):
        d = {"@class": type(self).__name__}
        for k, v in self.__dict__.items():
            if k.startswith("_") or v is None:
                continue
            if hasattr(v, "to_json"):
                v = {"__layer__": v.to_json()} if isinstance(
                    v, BaseLayer) else v.to_json()
            elif isinstance(v, tuple):
                v = list(v)
            d[k] = v
        return d

    @staticmethod
    def from_json(d):
        d = dict(d)
        cls = LAYER_REGISTRY[d.pop("@class")]
        for k, v in list(d.items()):
            if isinstance(v, dict) and "__layer__" in v:
                d[k] = BaseLayer.from_json(v["__layer__"])
            elif isinstance(v, dict) and "@class" in v:
                from deeplearning4j_tpu.optimize.updaters import (
                    updater_from_config)

                d[k] = updater_from_config(v)
        return cls(**d)

    def __repr__(self):
        fields = ", ".join(f"{k}={v!r}" for k, v in self.__dict__.items()
                           if v is not None and not k.startswith("_"))
        return f"{type(self).__name__}({fields})"


# ---------------------------------------------------------------------------
# feed-forward layers
# ---------------------------------------------------------------------------

@_register
class DenseLayer(BaseLayer):
    """Reference: conf.layers.DenseLayer + nn.layers.feedforward.dense.
    3-D input [N, C, T] is handled natively (per-timestep linear) instead of
    the reference's RnnToFeedForwardPreProcessor reshape round-trip."""

    def __init__(self, nIn=None, nOut=None, hasBias=True, **kw):
        super().__init__(**kw)
        self.nIn = nIn
        self.nOut = nOut
        self.hasBias = hasBias

    def infer(self, input_type):
        if isinstance(input_type, RecurrentType):
            self.nIn = self.nIn or input_type.size
            return InputType.recurrent(self.nOut, input_type.timeSeriesLength)
        self.nIn = self.nIn or input_type.arrayElementsPerExample()
        return InputType.feedForward(self.nOut)

    def init_params(self, key, dtype=jnp.float32):
        if self.nIn is None or self.nOut is None:
            raise ValueError(
                f"{type(self).__name__} has nIn={self.nIn}, nOut={self.nOut}:"
                f" set nIn explicitly or declare setInputType on the config")
        kw, kb = jax.random.split(key)
        p = {"W": init_weight(self.weightInit, kw, (self.nIn, self.nOut),
                              self.nIn, self.nOut, dtype)}
        if self.hasBias:
            p["b"] = jnp.full((self.nOut,), self.biasInit, dtype)
        return p

    def _linear(self, params, x):
        if x.ndim == 3:  # [N, C, T]: contract the channel axis per timestep
            y = jnp.einsum("nct,ch->nht", x, params["W"])
            if self.hasBias:
                y = y + params["b"][None, :, None]
            return y
        if x.ndim > 2:
            x = x.reshape(x.shape[0], -1)
        y = x @ params["W"]
        if self.hasBias:
            y = y + params["b"]
        return y

    def apply(self, params, state, x, training, rng):
        x = self._dropout(x, training, rng)
        return self._act(self._linear(params, x)), state


@_register
class EmbeddingLayer(BaseLayer):
    """Reference: conf.layers.EmbeddingLayer — int indices [N] or [N,1] (or
    one-hot [N, nIn]) -> [N, nOut]. Lookup is a gather, which XLA lowers to
    a dynamic-slice-friendly form on TPU."""

    def __init__(self, nIn=None, nOut=None, hasBias=False, **kw):
        super().__init__(**kw)
        self.nIn = nIn
        self.nOut = nOut
        self.hasBias = hasBias

    def infer(self, input_type):
        self.nIn = self.nIn or input_type.arrayElementsPerExample()
        return InputType.feedForward(self.nOut)

    def init_params(self, key, dtype=jnp.float32):
        p = {"W": init_weight(self.weightInit, key, (self.nIn, self.nOut),
                              self.nIn, self.nOut, dtype)}
        if self.hasBias:
            p["b"] = jnp.full((self.nOut,), self.biasInit, dtype)
        return p

    def apply(self, params, state, x, training, rng):
        if jnp.issubdtype(x.dtype, jnp.floating) and x.ndim == 2 \
                and x.shape[-1] == self.nIn:
            y = x @ params["W"]  # one-hot path
        else:
            idx = x.astype(jnp.int32)
            if idx.ndim == 2 and idx.shape[-1] == 1:
                idx = idx[:, 0]
            y = params["W"][idx]
        if self.hasBias:
            y = y + params["b"]
        return self._act(y), state


@_register
class EmbeddingSequenceLayer(EmbeddingLayer):
    """[N, T] int tokens -> [N, nOut, T] (recurrent layout)."""

    def infer(self, input_type):
        if self.nIn is None and isinstance(input_type, RecurrentType):
            self.nIn = input_type.size
        t = getattr(input_type, "timeSeriesLength", None)
        return InputType.recurrent(self.nOut, t)

    def apply(self, params, state, x, training, rng):
        idx = x.astype(jnp.int32)
        if idx.ndim == 3:  # [N, 1, T]
            idx = idx[:, 0, :]
        y = params["W"][idx]              # [N, T, nOut]
        if self.hasBias:
            y = y + params["b"]
        return self._act(jnp.moveaxis(y, 1, 2)), state  # [N, nOut, T]


# ---------------------------------------------------------------------------
# convolutional layers
# ---------------------------------------------------------------------------

class ConvolutionMode:
    TRUNCATE = "truncate"
    SAME = "same"


@_register
class ConvolutionLayer(BaseLayer):
    """Reference: conf.layers.ConvolutionLayer + nn.layers.convolution.
    One lax.conv_general_dilated call replaces im2col.cu + the cuDNN platform
    helper (SURVEY.md §2.1/§2.8 item 4-5); weights are OIHW like DL4J."""

    def __init__(self, nIn=None, nOut=None, kernelSize=(3, 3), stride=(1, 1),
                 padding=(0, 0), dilation=(1, 1), convolutionMode=None,
                 hasBias=True, **kw):
        super().__init__(**kw)
        self.nIn = nIn
        self.nOut = nOut
        self.kernelSize = _pair(kernelSize)
        self.stride = _pair(stride)
        self.padding = _pair(padding)
        self.dilation = _pair(dilation)
        self.convolutionMode = convolutionMode or ConvolutionMode.TRUNCATE
        self.hasBias = hasBias

    def _same(self):
        return self.convolutionMode == ConvolutionMode.SAME

    def infer(self, input_type):
        if not isinstance(input_type, ConvolutionalType):
            raise ValueError(
                f"ConvolutionLayer needs convolutional input, got {input_type}")
        self.nIn = self.nIn or input_type.channels
        kh, kw = self.kernelSize
        sh, sw = self.stride
        ph, pw = self.padding
        dh, dw = self.dilation
        ekh, ekw = (kh - 1) * dh + 1, (kw - 1) * dw + 1
        if self._same():
            oh = -(-input_type.height // sh)
            ow = -(-input_type.width // sw)
        else:
            oh = (input_type.height + 2 * ph - ekh) // sh + 1
            ow = (input_type.width + 2 * pw - ekw) // sw + 1
        return InputType.convolutional(oh, ow, self.nOut)

    def init_params(self, key, dtype=jnp.float32):
        kh, kw = self.kernelSize
        fan_in = self.nIn * kh * kw
        fan_out = self.nOut * kh * kw
        k1, k2 = jax.random.split(key)
        p = {"W": init_weight(self.weightInit, k1,
                              (self.nOut, self.nIn, kh, kw),
                              fan_in, fan_out, dtype)}
        if self.hasBias:
            p["b"] = jnp.full((self.nOut,), self.biasInit, dtype)
        return p

    def apply(self, params, state, x, training, rng):
        x = self._dropout(x, training, rng)
        y = OPS["conv2d"](x, params["W"], params.get("b"),
                          strides=self.stride, padding=self.padding,
                          dilation=self.dilation, sameMode=self._same())
        return self._act(y), state


@_register
class Convolution1DLayer(BaseLayer):
    """Input [N, C, T]."""

    def __init__(self, nIn=None, nOut=None, kernelSize=3, stride=1, padding=0,
                 convolutionMode=None, hasBias=True, **kw):
        super().__init__(**kw)
        self.nIn = nIn
        self.nOut = nOut
        self.kernelSize = int(kernelSize) if not isinstance(
            kernelSize, (list, tuple)) else int(kernelSize[0])
        self.stride = int(stride) if not isinstance(
            stride, (list, tuple)) else int(stride[0])
        self.padding = int(padding) if not isinstance(
            padding, (list, tuple)) else int(padding[0])
        self.convolutionMode = convolutionMode or ConvolutionMode.TRUNCATE
        self.hasBias = hasBias

    def infer(self, input_type):
        self.nIn = self.nIn or input_type.size
        t = getattr(input_type, "timeSeriesLength", None)
        if t is not None:
            if self.convolutionMode == ConvolutionMode.SAME:
                t = -(-t // self.stride)
            else:
                t = (t + 2 * self.padding - self.kernelSize) // self.stride + 1
        return InputType.recurrent(self.nOut, t)

    def init_params(self, key, dtype=jnp.float32):
        fan_in = self.nIn * self.kernelSize
        fan_out = self.nOut * self.kernelSize
        p = {"W": init_weight(self.weightInit, key,
                              (self.nOut, self.nIn, self.kernelSize),
                              fan_in, fan_out, dtype)}
        if self.hasBias:
            p["b"] = jnp.zeros((self.nOut,), dtype)
        return p

    def apply(self, params, state, x, training, rng):
        y = OPS["conv1d"](x, params["W"], params.get("b"), stride=self.stride,
                          padding=self.padding,
                          sameMode=self.convolutionMode == ConvolutionMode.SAME)
        return self._act(y), state


@_register
class SeparableConvolution2D(ConvolutionLayer):
    """Depthwise (depthMultiplier) + pointwise, as in the reference's
    SeparableConvolution2D."""

    def __init__(self, depthMultiplier=1, **kw):
        super().__init__(**kw)
        self.depthMultiplier = depthMultiplier

    def init_params(self, key, dtype=jnp.float32):
        kh, kw = self.kernelSize
        k1, k2 = jax.random.split(key)
        fan_d = self.nIn * kh * kw
        p = {
            "dW": init_weight(self.weightInit, k1,
                              (self.depthMultiplier, self.nIn, kh, kw),
                              fan_d, self.depthMultiplier * kh * kw, dtype),
            "pW": init_weight(self.weightInit, k2,
                              (self.nOut, self.nIn * self.depthMultiplier,
                               1, 1),
                              self.nIn * self.depthMultiplier, self.nOut,
                              dtype),
        }
        if self.hasBias:
            p["b"] = jnp.full((self.nOut,), self.biasInit, dtype)
        return p

    def apply(self, params, state, x, training, rng):
        x = self._dropout(x, training, rng)
        y = OPS["depthwiseConv2d"](x, params["dW"], None,
                                   strides=self.stride, padding=self.padding,
                                   dilation=self.dilation,
                                   sameMode=self._same())
        y = OPS["conv2d"](y, params["pW"], params.get("b"))
        return self._act(y), state


@_register
class DepthwiseConvolution2D(ConvolutionLayer):
    """Depthwise-only convolution (reference:
    conf.layers.DepthwiseConvolution2D): each input channel convolves
    with depthMultiplier filters of its own; nOut = nIn *
    depthMultiplier."""

    def __init__(self, depthMultiplier=1, **kw):
        super().__init__(**kw)
        self.depthMultiplier = int(depthMultiplier)

    def infer(self, input_type):
        if not isinstance(input_type, ConvolutionalType):
            raise ValueError(
                f"DepthwiseConvolution2D needs convolutional input, got "
                f"{input_type}")
        self.nIn = self.nIn or input_type.channels
        self.nOut = self.nIn * self.depthMultiplier
        # spatial math (incl. dilation) delegates to the base conv infer
        return super().infer(input_type)

    def init_params(self, key, dtype=jnp.float32):
        kh, kw = self.kernelSize
        fan_in = self.nIn * kh * kw
        # Keras DepthwiseConv2D bias flattening is (in, mult) — the same
        # c*depthMultiplier + m ordering the depthwiseConv2d op emits, so
        # imported biases install without a permute.
        p = {"W": init_weight(self.weightInit, key,
                              (self.depthMultiplier, self.nIn, kh, kw),
                              fan_in, self.depthMultiplier * kh * kw,
                              dtype)}
        if self.hasBias:
            p["b"] = jnp.full((self.nOut,), self.biasInit, dtype)
        return p

    def apply(self, params, state, x, training, rng):
        x = self._dropout(x, training, rng)
        y = OPS["depthwiseConv2d"](x, params["W"], params.get("b"),
                                   strides=self.stride,
                                   padding=self.padding,
                                   dilation=self.dilation,
                                   sameMode=self._same())
        return self._act(y), state


@_register
class Deconvolution2D(ConvolutionLayer):
    def infer(self, input_type):
        self.nIn = self.nIn or input_type.channels
        kh, kw = self.kernelSize
        sh, sw = self.stride
        ph, pw = self.padding
        if self._same():
            oh, ow = input_type.height * sh, input_type.width * sw
        else:
            oh = sh * (input_type.height - 1) + kh - 2 * ph
            ow = sw * (input_type.width - 1) + kw - 2 * pw
        return InputType.convolutional(oh, ow, self.nOut)

    def init_params(self, key, dtype=jnp.float32):
        kh, kw = self.kernelSize
        p = {"W": init_weight(self.weightInit, key,
                              (self.nOut, self.nIn, kh, kw),
                              self.nIn * kh * kw, self.nOut * kh * kw, dtype)}
        if self.hasBias:
            p["b"] = jnp.full((self.nOut,), self.biasInit, dtype)
        return p

    def apply(self, params, state, x, training, rng):
        y = OPS["deconv2d"](x, params["W"], params.get("b"),
                            strides=self.stride, padding=self.padding,
                            sameMode=self._same())
        return self._act(y), state


class PoolingType:
    MAX = "max"
    AVG = "avg"
    SUM = "sum"
    PNORM = "pnorm"


@_register
class SubsamplingLayer(BaseLayer):
    """Reference: conf.layers.SubsamplingLayer (max/avg pooling)."""

    def __init__(self, poolingType=PoolingType.MAX, kernelSize=(2, 2),
                 stride=(2, 2), padding=(0, 0), convolutionMode=None, **kw):
        super().__init__(**kw)
        self.poolingType = poolingType
        self.kernelSize = _pair(kernelSize)
        self.stride = _pair(stride)
        self.padding = _pair(padding)
        self.convolutionMode = convolutionMode or ConvolutionMode.TRUNCATE

    def infer(self, input_type):
        kh, kw = self.kernelSize
        sh, sw = self.stride
        ph, pw = self.padding
        if self.convolutionMode == ConvolutionMode.SAME:
            oh = -(-input_type.height // sh)
            ow = -(-input_type.width // sw)
        else:
            oh = (input_type.height + 2 * ph - kh) // sh + 1
            ow = (input_type.width + 2 * pw - kw) // sw + 1
        return InputType.convolutional(oh, ow, input_type.channels)

    def apply(self, params, state, x, training, rng):
        same = self.convolutionMode == ConvolutionMode.SAME
        if self.poolingType == PoolingType.MAX:
            y = OPS["maxPooling2d"](x, kernel=self.kernelSize,
                                    strides=self.stride,
                                    padding=self.padding, sameMode=same)
        else:
            y = OPS["avgPooling2d"](x, kernel=self.kernelSize,
                                    strides=self.stride,
                                    padding=self.padding, sameMode=same)
        return y, state


@_register
class Subsampling1DLayer(BaseLayer):
    def __init__(self, poolingType=PoolingType.MAX, kernelSize=2, stride=2,
                 padding=0, **kw):
        super().__init__(**kw)
        self.poolingType = poolingType
        self.kernelSize = int(kernelSize)
        self.stride = int(stride)
        self.padding = int(padding)

    def infer(self, input_type):
        t = getattr(input_type, "timeSeriesLength", None)
        if t is not None:
            t = (t + 2 * self.padding - self.kernelSize) // self.stride + 1
        return InputType.recurrent(input_type.size, t)

    def apply(self, params, state, x, training, rng):
        pad = ((0, 0), (0, 0), (self.padding, self.padding))
        window = (1, 1, self.kernelSize)
        strides = (1, 1, self.stride)
        if self.poolingType == PoolingType.MAX:
            y = lax.reduce_window(x, -jnp.inf, lax.max, window, strides, pad)
        else:
            s = lax.reduce_window(x, 0.0, lax.add, window, strides, pad)
            c = lax.reduce_window(jnp.ones_like(x), 0.0, lax.add, window,
                                  strides, pad)
            y = s / c
        return y, state


@_register
class BatchNormalization(BaseLayer):
    """Reference: conf.layers.BatchNormalization + nn.layers.normalization.
    Running stats live in the layer STATE dict and are updated in the
    compiled train step (no host round-trip); per-channel for conv input,
    per-feature for dense."""

    def __init__(self, nIn=None, nOut=None, decay=0.9, eps=1e-5, gamma=1.0,
                 beta=0.0, lockGammaBeta=False, **kw):
        super().__init__(**kw)
        self.nIn = nIn
        self.nOut = nOut
        self.decay = decay
        self.eps = eps
        self.gamma = gamma
        self.beta = beta
        self.lockGammaBeta = lockGammaBeta

    def infer(self, input_type):
        if isinstance(input_type, (ConvolutionalType, RecurrentType)):
            # per-channel stats for conv [N,C,H,W] and recurrent [N,C,T]
            self.nIn = self.nIn or getattr(input_type, "channels",
                                           getattr(input_type, "size", None))
        else:
            self.nIn = self.nIn or input_type.arrayElementsPerExample()
        self.nOut = self.nIn
        return input_type

    def init_params(self, key, dtype=jnp.float32):
        if self.lockGammaBeta:
            return {}
        return {"gamma": jnp.full((self.nIn,), self.gamma, dtype),
                "beta": jnp.full((self.nIn,), self.beta, dtype)}

    def init_state(self, dtype=jnp.float32):
        return {"mean": jnp.zeros((self.nIn,), dtype),
                "var": jnp.ones((self.nIn,), dtype)}

    def apply(self, params, state, x, training, rng):
        axes = tuple(i for i in range(x.ndim) if i != 1) if x.ndim > 2 \
            else (0,)
        shape = [1] * x.ndim
        shape[1 if x.ndim > 2 else -1] = -1
        if training:
            # Stats strategy by activation dtype:
            # - bf16/f16: ONE-PASS E[x^2]-mean^2 with f32 accumulators —
            #   reads x once instead of twice (+9% ResNet-50 bf16 train
            #   throughput on v5e in July 2026, not re-measured:
            #   tools/RESNET_MFU.md section 4);
            #   any mean>>std cancellation is below the activations' own
            #   quantization noise at these dtypes.
            # - f32: TWO-PASS centered stats — one-pass cancels
            #   catastrophically at mean>>std (guarded by
            #   tests/test_nn.py::TestBatchNormNumerics).
            # mean/var STAY f32 through the rsqrt — they are tiny
            # per-channel vectors, and quantizing them to bf16 before
            # adding eps would absorb eps entirely.
            low_prec = x.dtype in (jnp.bfloat16, jnp.float16)
            xf = x.astype(jnp.float32) if low_prec else x
            mean = jnp.mean(xf, axis=axes)
            if low_prec:
                var = jnp.maximum(
                    jnp.mean(jnp.square(xf), axis=axes)
                    - jnp.square(mean), 0.0)
            else:
                var = jnp.mean(
                    jnp.square(xf - mean.reshape(shape)), axis=axes)
            sdt = state["mean"].dtype
            new_state = {
                "mean": self.decay * state["mean"]
                + (1 - self.decay) * mean.astype(sdt),
                "var": self.decay * state["var"]
                + (1 - self.decay) * var.astype(sdt),
            }
        else:
            mean, var = state["mean"], state["var"]
            new_state = state
        xn = (x - mean.reshape(shape).astype(x.dtype)) * lax.rsqrt(
            var.reshape(shape) + self.eps).astype(x.dtype)
        if not self.lockGammaBeta:
            xn = xn * params["gamma"].reshape(shape) \
                + params["beta"].reshape(shape)
        return self._act(xn), new_state


@_register
class LocalResponseNormalization(BaseLayer):
    def __init__(self, k=2.0, n=5, alpha=1e-4, beta=0.75, **kw):
        super().__init__(**kw)
        self.k = k
        self.n = int(n)
        self.alpha = alpha
        self.beta = beta

    def apply(self, params, state, x, training, rng):
        sq = x * x
        half = self.n // 2
        # sum over a window of channels: pad C then reduce_window on axis 1
        window = (1, self.n, 1, 1)
        pad = ((0, 0), (half, half), (0, 0), (0, 0))
        s = lax.reduce_window(sq, 0.0, lax.add, window, (1, 1, 1, 1), pad)
        return x / (self.k + self.alpha * s) ** self.beta, state


@_register
class ZeroPaddingLayer(BaseLayer):
    def __init__(self, padding=(1, 1), **kw):
        super().__init__(**kw)
        p = padding
        if isinstance(p, int):
            p = (p, p, p, p)
        elif len(p) == 2:
            p = (p[0], p[0], p[1], p[1])
        self.padding = tuple(int(v) for v in p)  # top,bottom,left,right

    def infer(self, input_type):
        t, b, l, r = self.padding
        return InputType.convolutional(input_type.height + t + b,
                                       input_type.width + l + r,
                                       input_type.channels)

    def apply(self, params, state, x, training, rng):
        t, b, l, r = self.padding
        return jnp.pad(x, ((0, 0), (0, 0), (t, b), (l, r))), state


@_register
class Upsampling2D(BaseLayer):
    def __init__(self, size=(2, 2), **kw):
        super().__init__(**kw)
        self.size = _pair(size)

    def infer(self, input_type):
        return InputType.convolutional(input_type.height * self.size[0],
                                       input_type.width * self.size[1],
                                       input_type.channels)

    def apply(self, params, state, x, training, rng):
        return OPS["upsampling2d"](x, size=self.size), state


@_register
class SpaceToDepth(BaseLayer):
    """[N,C,H,W] -> [N, C*b*b, H/b, W/b] (reference:
    conf.layers.SpaceToDepthLayer — the YOLO2 'reorg' passthrough)."""

    def __init__(self, blockSize=2, **kw):
        super().__init__(**kw)
        self.blockSize = int(blockSize)

    def infer(self, input_type):
        bsz = self.blockSize
        if input_type.height % bsz or input_type.width % bsz:
            raise ValueError(
                f"SpaceToDepth(blockSize={bsz}) needs spatial dims "
                f"divisible by the block, got "
                f"{input_type.height}x{input_type.width}")
        return InputType.convolutional(input_type.height // bsz,
                                       input_type.width // bsz,
                                       input_type.channels * bsz * bsz)

    def apply(self, params, state, x, training, rng):
        n, c, h, w = x.shape
        bsz = self.blockSize
        x = x.reshape(n, c, h // bsz, bsz, w // bsz, bsz)
        x = jnp.transpose(x, (0, 3, 5, 1, 2, 4))
        return x.reshape(n, c * bsz * bsz, h // bsz, w // bsz), state


@_register
class DepthToSpace(BaseLayer):
    """[N, C*b*b, H, W] -> [N, C, H*b, W*b] (inverse of SpaceToDepth)."""

    def __init__(self, blockSize=2, **kw):
        super().__init__(**kw)
        self.blockSize = int(blockSize)

    def infer(self, input_type):
        bsz = self.blockSize
        if input_type.channels % (bsz * bsz):
            raise ValueError(
                f"DepthToSpace(blockSize={bsz}) needs channels divisible "
                f"by block^2, got {input_type.channels}")
        return InputType.convolutional(input_type.height * bsz,
                                       input_type.width * bsz,
                                       input_type.channels // (bsz * bsz))

    def apply(self, params, state, x, training, rng):
        n, c, h, w = x.shape
        bsz = self.blockSize
        cout = c // (bsz * bsz)
        x = x.reshape(n, bsz, bsz, cout, h, w)
        x = jnp.transpose(x, (0, 3, 4, 1, 5, 2))
        return x.reshape(n, cout, h * bsz, w * bsz), state


@_register
class GlobalPoolingLayer(BaseLayer):
    """[N,C,H,W] -> [N,C] or [N,C,T] -> [N,C]."""

    def __init__(self, poolingType=PoolingType.AVG, **kw):
        super().__init__(**kw)
        self.poolingType = poolingType

    def infer(self, input_type):
        if isinstance(input_type, ConvolutionalType):
            return InputType.feedForward(input_type.channels)
        if isinstance(input_type, RecurrentType):
            return InputType.feedForward(input_type.size)
        return input_type

    def apply(self, params, state, x, training, rng):
        axes = tuple(range(2, x.ndim))
        if self.poolingType == PoolingType.MAX:
            return jnp.max(x, axis=axes), state
        if self.poolingType == PoolingType.SUM:
            return jnp.sum(x, axis=axes), state
        return jnp.mean(x, axis=axes), state


@_register
class DropoutLayer(BaseLayer):
    def __init__(self, dropOut=0.5, **kw):
        kw["dropOut"] = dropOut
        super().__init__(**kw)

    def apply(self, params, state, x, training, rng):
        return self._dropout(x, training, rng), state


@_register
class ActivationLayer(BaseLayer):
    def apply(self, params, state, x, training, rng):
        return self._act(x), state


# ---------------------------------------------------------------------------
# recurrent layers
# ---------------------------------------------------------------------------

@_register
class LSTM(BaseLayer):
    """Reference: conf.layers.LSTM + nn.layers.recurrent.LSTM (and the cuDNN
    LSTM helper, SURVEY.md §2.5). The recurrence is a lax.scan — one fused
    XLA while loop with weights resident in VMEM across steps, replacing the
    per-timestep JNI dispatch + cuDNN path (SURVEY.md §7 hard part 3).
    Input/output layout [N, C, T]."""

    def __init__(self, nIn=None, nOut=None, forgetGateBiasInit=1.0, **kw):
        super().__init__(**kw)
        self.nIn = nIn
        self.nOut = nOut
        self.forgetGateBiasInit = forgetGateBiasInit
        if self.activation is None:
            self.activation = "tanh"

    def infer(self, input_type):
        self.nIn = self.nIn or input_type.size
        t = getattr(input_type, "timeSeriesLength", None)
        return InputType.recurrent(self.nOut, t)

    def init_params(self, key, dtype=jnp.float32):
        k1, k2 = jax.random.split(key)
        h = self.nOut
        return {
            "W": init_weight(self.weightInit, k1, (self.nIn, 4 * h),
                             self.nIn, h, dtype),
            "R": init_weight(self.weightInit, k2, (h, 4 * h), h, h, dtype),
            "b": jnp.zeros((4 * h,), dtype),
        }

    IS_RECURRENT = True

    def apply(self, params, state, x, training, rng):
        """When `state` carries {"h","c"} (streaming rnnTimeStep or a TBPTT
        segment, SURVEY.md §2.5 TBPTT row), the recurrence starts from it
        and the updated state is returned; otherwise zero-init stateless."""
        x = self._dropout(x, training, rng)
        h0 = state.get("h") if isinstance(state, dict) else None
        c0 = state.get("c") if isinstance(state, dict) else None
        out, hT, cT = OPS["lstmLayer"](
            x, params["W"], params["R"], params["b"], h0=h0, c0=c0,
            forgetBias=self.forgetGateBiasInit)
        if h0 is not None:
            return out, {"h": hT, "c": cT}
        return out, state

    def streaming_state(self, batch_size, dtype=jnp.float32):
        """Zero carried state for rnnTimeStep / TBPTT segments."""
        h = jnp.zeros((batch_size, self.nOut), dtype)
        return {"h": h, "c": jnp.zeros_like(h)}


@_register
class GravesLSTM(LSTM):
    """Kept for config parity; peephole connections are dropped (the
    reference deprecated GravesLSTM in favor of LSTM for the same reason
    cuDNN did not support them)."""


@_register
class GRU(BaseLayer):
    """Gated recurrent unit (reference: conf.layers.recurrent.GRU /
    libnd4j gruCell+gruLayer declarables, SURVEY.md §2.1). Backed by the
    gruLayer op (input projection hoisted to one MXU matmul; Pallas
    recurrence kernel on TPU when shapes allow). resetAfter=False (the
    default, matching the reference's gruCell/gruLayer classic Cho et
    al. reset-before form with a 3H input bias); True is the
    cuDNN/Keras-v2 convention (b holds [3H input || 3H recurrent]),
    which the Keras importer selects explicitly from reset_after."""

    def __init__(self, nIn=None, nOut=None, resetAfter=False, **kw):
        super().__init__(**kw)
        self.nIn = nIn
        self.nOut = nOut
        self.resetAfter = resetAfter
        if self.activation is None:
            self.activation = "tanh"

    def infer(self, input_type):
        self.nIn = self.nIn or input_type.size
        t = getattr(input_type, "timeSeriesLength", None)
        return InputType.recurrent(self.nOut, t)

    def init_params(self, key, dtype=jnp.float32):
        k1, k2 = jax.random.split(key)
        h = self.nOut
        nb = 6 * h if self.resetAfter else 3 * h
        return {
            "W": init_weight(self.weightInit, k1, (self.nIn, 3 * h),
                             self.nIn, h, dtype),
            "R": init_weight(self.weightInit, k2, (h, 3 * h), h, h, dtype),
            "b": jnp.zeros((nb,), dtype),
        }

    IS_RECURRENT = True

    def apply(self, params, state, x, training, rng):
        x = self._dropout(x, training, rng)
        h0 = state.get("h") if isinstance(state, dict) else None
        out, hT = OPS["gruLayer"](x, params["W"], params["R"],
                                  params["b"], h0=h0,
                                  resetAfter=self.resetAfter,
                                  activation=self.activation)
        if h0 is not None:
            return out, {"h": hT}
        return out, state

    def streaming_state(self, batch_size, dtype=jnp.float32):
        return {"h": jnp.zeros((batch_size, self.nOut), dtype)}


@_register
class SimpleRnn(BaseLayer):
    def __init__(self, nIn=None, nOut=None, **kw):
        super().__init__(**kw)
        self.nIn = nIn
        self.nOut = nOut
        if self.activation is None:
            self.activation = "tanh"

    def infer(self, input_type):
        self.nIn = self.nIn or input_type.size
        t = getattr(input_type, "timeSeriesLength", None)
        return InputType.recurrent(self.nOut, t)

    def init_params(self, key, dtype=jnp.float32):
        k1, k2 = jax.random.split(key)
        return {
            "W": init_weight(self.weightInit, k1, (self.nIn, self.nOut),
                             self.nIn, self.nOut, dtype),
            "R": init_weight(self.weightInit, k2, (self.nOut, self.nOut),
                             self.nOut, self.nOut, dtype),
            "b": jnp.zeros((self.nOut,), dtype),
        }

    IS_RECURRENT = True

    def apply(self, params, state, x, training, rng):
        h0 = state.get("h") if isinstance(state, dict) else None
        out, hT = OPS["simpleRnnLayer"](x, params["W"], params["R"],
                                        params["b"], h0=h0,
                                        activation=self.activation)
        if h0 is not None:
            return out, {"h": hT}
        return out, state

    def streaming_state(self, batch_size, dtype=jnp.float32):
        return {"h": jnp.zeros((batch_size, self.nOut), dtype)}


@_register
class Bidirectional(BaseLayer):
    """Wrapper running the sub-layer forward and on time-reversed input.
    Reference: conf.layers.recurrent.Bidirectional (modes CONCAT/ADD/
    AVERAGE/MUL)."""

    CONCAT, ADD, AVERAGE, MUL = "concat", "add", "average", "mul"

    def __init__(self, rnn=None, mode="concat", **kw):
        super().__init__(**kw)
        self.rnn = rnn
        self.mode = mode

    def apply_defaults(self, defaults):
        super().apply_defaults(defaults)
        self.rnn.apply_defaults(defaults)

    def infer(self, input_type):
        out = self.rnn.infer(input_type)
        size = out.size * 2 if self.mode == self.CONCAT else out.size
        return InputType.recurrent(size, getattr(out, "timeSeriesLength",
                                                 None))

    def init_params(self, key, dtype=jnp.float32):
        kf, kb = jax.random.split(key)
        return {"fwd": self.rnn.init_params(kf, dtype),
                "bwd": self.rnn.init_params(kb, dtype)}

    def apply(self, params, state, x, training, rng):
        yf, _ = self.rnn.apply(params["fwd"], {}, x, training, rng)
        yb, _ = self.rnn.apply(params["bwd"], {}, x[..., ::-1], training, rng)
        yb = yb[..., ::-1]
        if self.mode == self.CONCAT:
            return jnp.concatenate([yf, yb], axis=1), state
        if self.mode == self.ADD:
            return yf + yb, state
        if self.mode == self.MUL:
            return yf * yb, state
        return (yf + yb) / 2.0, state


@_register
class LastTimeStep(BaseLayer):
    """Wrapper: [N, C, T] -> [N, C] taking the final timestep."""

    def __init__(self, rnn=None, **kw):
        super().__init__(**kw)
        self.rnn = rnn

    def apply_defaults(self, defaults):
        super().apply_defaults(defaults)
        if self.rnn is not None:
            self.rnn.apply_defaults(defaults)

    def infer(self, input_type):
        out = self.rnn.infer(input_type)
        return InputType.feedForward(out.size)

    def init_params(self, key, dtype=jnp.float32):
        return self.rnn.init_params(key, dtype)

    def init_state(self, dtype=jnp.float32):
        return self.rnn.init_state(dtype)

    def apply(self, params, state, x, training, rng):
        y, state = self.rnn.apply(params, state, x, training, rng)
        return y[..., -1], state


# ---------------------------------------------------------------------------
# output layers
# ---------------------------------------------------------------------------

class BaseOutputLayer(DenseLayer):
    def __init__(self, lossFunction="mcxent", **kw):
        super().__init__(**kw)
        self.lossFunction = lossFunction
        # remember whether the user set the activation explicitly so a
        # global .activation(...) default can propagate (DL4J semantics:
        # softmax is the fallback only when NO global default exists)
        self._explicit_activation = self.activation is not None
        if self.activation is None:
            self.activation = "softmax"

    def apply_defaults(self, defaults):
        if (not getattr(self, "_explicit_activation", True)
                and defaults.get("activation") is not None):
            self.activation = defaults["activation"]
        super().apply_defaults(defaults)

    def pre_output(self, params, x):
        return self._linear(params, x)

    def compute_loss(self, params, x, labels, mask=None):
        pre = self.pre_output(params, x)
        return resolve_loss(self.lossFunction)(
            labels, pre, self.activation, mask)


@_register
class OutputLayer(BaseOutputLayer):
    """Reference: conf.layers.OutputLayer (dense + loss)."""


@_register
class RnnOutputLayer(BaseOutputLayer):
    """Per-timestep output over [N, C, T]."""

    def infer(self, input_type):
        self.nIn = self.nIn or input_type.size
        return InputType.recurrent(self.nOut,
                                   getattr(input_type, "timeSeriesLength",
                                           None))

    def apply(self, params, state, x, training, rng):
        return self._act(self._linear(params, x)), state


@_register
class LossLayer(BaseLayer):
    """No params: input is already the pre-output."""

    def __init__(self, lossFunction="mcxent", **kw):
        super().__init__(**kw)
        self.lossFunction = lossFunction
        if self.activation is None:
            self.activation = "softmax"

    def pre_output(self, params, x):
        return x

    def compute_loss(self, params, x, labels, mask=None):
        return resolve_loss(self.lossFunction)(
            labels, x, self.activation, mask)

    def apply(self, params, state, x, training, rng):
        return self._act(x), state


OUTPUT_LAYER_TYPES = (BaseOutputLayer, LossLayer)
