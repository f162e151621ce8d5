"""Model zoo: canned architectures.

Reference capability: deeplearning4j-zoo org.deeplearning4j.zoo.model.*
(SURVEY.md §2.7): ZooModel.init() returns a ready network. Pretrained
weight download is environment-gated (no egress here); initPretrained
raises with a clear message instead.

Configs follow the reference's published architectures (LeNet, SimpleCNN,
AlexNet, VGG16, Darknet19, ResNet50); all lower to single jitted XLA steps
like any other net."""

from __future__ import annotations

from deeplearning4j_tpu.nn import (
    ActivationLayer, BatchNormalization, ComputationGraph, ConvolutionLayer,
    ConvolutionMode, Deconvolution2D, DenseLayer, DropoutLayer,
    ElementWiseVertex, GlobalPoolingLayer, InputType,
    LocalResponseNormalization, LossLayer, LSTM, MergeVertex,
    MultiLayerNetwork,
    NeuralNetConfiguration, OutputLayer, PoolingType, RnnOutputLayer,
    SeparableConvolution2D, SubsamplingLayer, WeightInit)
from deeplearning4j_tpu.optimize.updaters import Adam, Nesterovs


def _expected_num_params(conf) -> int:
    """Parameter count of a configuration WITHOUT materializing weights
    (jax.eval_shape traces init_params abstractly)."""
    import math

    import jax

    from deeplearning4j_tpu.nn.conf.graph_conf import (
        ComputationGraphConfiguration)

    if isinstance(conf, ComputationGraphConfiguration):
        inits = [node.init_params for node, _ in conf.nodes.values()
                 if hasattr(node, "init_params")]
    else:
        inits = [lr.init_params for lr in conf.layers]
    key = jax.random.key(0)
    total = 0
    for init in inits:
        shapes = jax.eval_shape(lambda k, f=init: f(k, conf.dtype), key)
        total += sum(math.prod(s.shape)
                     for s in jax.tree_util.tree_leaves(shapes))
    return total


class ZooModel:
    def init(self):
        raise NotImplementedError

    def initPretrained(self, weightsFile=None):
        """Reference: ZooModel.initPretrained() downloads + checksums a
        weight file, then loads it. No egress here, so the weight file
        must already be local: a Dl4jCheckpoint zip, a ModelSerializer
        zip, or a save_params_npz .npz of named layer params."""
        if weightsFile is None:
            raise ValueError(
                "no network access in this environment: pass "
                "initPretrained(weightsFile=...) pointing at a local "
                "checkpoint zip or params .npz")
        path = str(weightsFile)
        if path.endswith(".npz"):
            from deeplearning4j_tpu.utils.checkpoint import load_params_npz

            return load_params_npz(self.init(), path)
        import zipfile

        with zipfile.ZipFile(path) as zf:
            names = set(zf.namelist())
        if "coefficients.bin" in names:
            from deeplearning4j_tpu.utils.checkpoint import Dl4jCheckpoint

            loaded = Dl4jCheckpoint.load(path)
        else:
            from deeplearning4j_tpu.utils.serializer import ModelSerializer

            loaded = ModelSerializer._restore(path, None, loadUpdater=False)
        # the zip rebuilds from its own configuration.json — reject a
        # checkpoint for a different architecture instead of silently
        # returning whatever network the file holds. The expected count
        # comes from eval_shape (abstract init: no weights materialized)
        # when the model exposes conf(); small models without one pay a
        # real init.
        if hasattr(self, "conf"):
            expected = _expected_num_params(self.conf())
        else:
            expected = self.init().numParams()
        if loaded.numParams() != expected:
            raise ValueError(
                f"checkpoint {path!r} holds a "
                f"{loaded.numParams()}-param model, but "
                f"{type(self).__name__} has {expected} params "
                "— wrong weights for this zoo model")
        return loaded

    def metaData(self):
        return {"name": type(self).__name__}


class LeNet(ZooModel):
    """Reference: zoo.model.LeNet (the LeNet-MNIST baseline,
    BASELINE.json configs[0])."""

    def __init__(self, numClasses=10, seed=123, inputShape=(1, 28, 28),
                 updater=None):
        self.numClasses = numClasses
        self.seed = seed
        self.inputShape = inputShape
        self.updater = updater or Adam(1e-3)

    def conf(self):
        c, h, w = self.inputShape
        return (NeuralNetConfiguration.Builder().seed(self.seed)
                .updater(self.updater).weightInit(WeightInit.XAVIER)
                .list()
                .layer(ConvolutionLayer.Builder().nOut(20).kernelSize([5, 5])
                       .stride([1, 1]).activation("relu").build())
                .layer(SubsamplingLayer.Builder(poolingType=PoolingType.MAX)
                       .kernelSize([2, 2]).stride([2, 2]).build())
                .layer(ConvolutionLayer.Builder().nOut(50).kernelSize([5, 5])
                       .stride([1, 1]).activation("relu").build())
                .layer(SubsamplingLayer.Builder(poolingType=PoolingType.MAX)
                       .kernelSize([2, 2]).stride([2, 2]).build())
                .layer(DenseLayer.Builder().nOut(500).activation("relu")
                       .build())
                .layer(OutputLayer.Builder().nOut(self.numClasses)
                       .activation("softmax").lossFunction("mcxent").build())
                .setInputType(InputType.convolutionalFlat(h, w, c))
                .build())

    def init(self) -> MultiLayerNetwork:
        return MultiLayerNetwork(self.conf()).init()


class SimpleCNN(ZooModel):
    """Reference: zoo.model.SimpleCNN."""

    def __init__(self, numClasses=10, seed=123, inputShape=(3, 48, 48)):
        self.numClasses = numClasses
        self.seed = seed
        self.inputShape = inputShape

    def init(self) -> MultiLayerNetwork:
        c, h, w = self.inputShape
        conf = (NeuralNetConfiguration.Builder().seed(self.seed)
                .updater(Adam(1e-3)).weightInit(WeightInit.RELU)
                .list()
                .layer(ConvolutionLayer.Builder().nOut(16)
                       .kernelSize([3, 3])
                       .convolutionMode(ConvolutionMode.SAME)
                       .activation("relu").build())
                .layer(BatchNormalization.Builder().build())
                .layer(ConvolutionLayer.Builder().nOut(16)
                       .kernelSize([3, 3])
                       .convolutionMode(ConvolutionMode.SAME)
                       .activation("relu").build())
                .layer(SubsamplingLayer.Builder().kernelSize([2, 2])
                       .stride([2, 2]).build())
                .layer(ConvolutionLayer.Builder().nOut(32)
                       .kernelSize([3, 3])
                       .convolutionMode(ConvolutionMode.SAME)
                       .activation("relu").build())
                .layer(BatchNormalization.Builder().build())
                .layer(SubsamplingLayer.Builder().kernelSize([2, 2])
                       .stride([2, 2]).build())
                .layer(GlobalPoolingLayer.Builder().build())
                .layer(DropoutLayer.Builder().dropOut(0.5).build())
                .layer(OutputLayer.Builder().nOut(self.numClasses)
                       .activation("softmax").lossFunction("mcxent").build())
                .setInputType(InputType.convolutional(h, w, c))
                .build())
        return MultiLayerNetwork(conf).init()


class AlexNet(ZooModel):
    """Reference: zoo.model.AlexNet (LRN + grouped-conv-free variant)."""

    def __init__(self, numClasses=1000, seed=123, inputShape=(3, 224, 224)):
        self.numClasses = numClasses
        self.seed = seed
        self.inputShape = inputShape

    def init(self) -> MultiLayerNetwork:
        c, h, w = self.inputShape
        conf = (NeuralNetConfiguration.Builder().seed(self.seed)
                .updater(Nesterovs(1e-2, 0.9)).weightInit(WeightInit.RELU)
                .list()
                .layer(ConvolutionLayer.Builder().nOut(96)
                       .kernelSize([11, 11]).stride([4, 4])
                       .activation("relu").build())
                .layer(LocalResponseNormalization.Builder().build())
                .layer(SubsamplingLayer.Builder().kernelSize([3, 3])
                       .stride([2, 2]).build())
                .layer(ConvolutionLayer.Builder().nOut(256)
                       .kernelSize([5, 5]).padding([2, 2])
                       .activation("relu").build())
                .layer(LocalResponseNormalization.Builder().build())
                .layer(SubsamplingLayer.Builder().kernelSize([3, 3])
                       .stride([2, 2]).build())
                .layer(ConvolutionLayer.Builder().nOut(384)
                       .kernelSize([3, 3]).padding([1, 1])
                       .activation("relu").build())
                .layer(ConvolutionLayer.Builder().nOut(384)
                       .kernelSize([3, 3]).padding([1, 1])
                       .activation("relu").build())
                .layer(ConvolutionLayer.Builder().nOut(256)
                       .kernelSize([3, 3]).padding([1, 1])
                       .activation("relu").build())
                .layer(SubsamplingLayer.Builder().kernelSize([3, 3])
                       .stride([2, 2]).build())
                .layer(DenseLayer.Builder().nOut(4096).activation("relu")
                       .dropOut(0.5).build())
                .layer(DenseLayer.Builder().nOut(4096).activation("relu")
                       .dropOut(0.5).build())
                .layer(OutputLayer.Builder().nOut(self.numClasses)
                       .activation("softmax").lossFunction("mcxent").build())
                .setInputType(InputType.convolutional(h, w, c))
                .build())
        return MultiLayerNetwork(conf).init()


class VGG16(ZooModel):
    """Reference: zoo.model.VGG16. BLOCKS = (channels, convs in a row) per
    pooled stage; VGG19 overrides it."""

    BLOCKS = ((64, 2), (128, 2), (256, 3), (512, 3), (512, 3))

    def __init__(self, numClasses=1000, seed=123, inputShape=(3, 224, 224)):
        self.numClasses = numClasses
        self.seed = seed
        self.inputShape = inputShape

    def init(self) -> MultiLayerNetwork:
        c, h, w = self.inputShape
        b = (NeuralNetConfiguration.Builder().seed(self.seed)
             .updater(Nesterovs(1e-2, 0.9)).weightInit(WeightInit.RELU)
             .list())

        def conv(n):
            return (ConvolutionLayer.Builder().nOut(n).kernelSize([3, 3])
                    .convolutionMode(ConvolutionMode.SAME)
                    .activation("relu").build())

        def pool():
            return (SubsamplingLayer.Builder().kernelSize([2, 2])
                    .stride([2, 2]).build())

        for n, reps in self.BLOCKS:
            for _ in range(reps):
                b = b.layer(conv(n))
            b = b.layer(pool())
        conf = (b
                .layer(DenseLayer.Builder().nOut(4096).activation("relu")
                       .dropOut(0.5).build())
                .layer(DenseLayer.Builder().nOut(4096).activation("relu")
                       .dropOut(0.5).build())
                .layer(OutputLayer.Builder().nOut(self.numClasses)
                       .activation("softmax").lossFunction("mcxent").build())
                .setInputType(InputType.convolutional(h, w, c))
                .build())
        return MultiLayerNetwork(conf).init()


class Darknet19(ZooModel):
    """Reference: zoo.model.Darknet19."""

    def __init__(self, numClasses=1000, seed=123, inputShape=(3, 224, 224)):
        self.numClasses = numClasses
        self.seed = seed
        self.inputShape = inputShape

    def init(self) -> MultiLayerNetwork:
        c, h, w = self.inputShape
        b = (NeuralNetConfiguration.Builder().seed(self.seed)
             .updater(Adam(1e-3)).weightInit(WeightInit.RELU).list())

        def conv(n, k):
            return (ConvolutionLayer.Builder().nOut(n).kernelSize([k, k])
                    .convolutionMode(ConvolutionMode.SAME)
                    .activation("leakyrelu").build())

        def bn():
            return BatchNormalization.Builder().build()

        def pool():
            return (SubsamplingLayer.Builder().kernelSize([2, 2])
                    .stride([2, 2]).build())

        plan = [(32, 3), "P", (64, 3), "P", (128, 3), (64, 1), (128, 3),
                "P", (256, 3), (128, 1), (256, 3), "P", (512, 3), (256, 1),
                (512, 3), (256, 1), (512, 3), "P", (1024, 3), (512, 1),
                (1024, 3), (512, 1), (1024, 3)]
        for item in plan:
            if item == "P":
                b = b.layer(pool())
            else:
                n, k = item
                b = b.layer(conv(n, k)).layer(bn())
        conf = (b.layer(ConvolutionLayer.Builder()
                        .nOut(self.numClasses).kernelSize([1, 1])
                        .convolutionMode(ConvolutionMode.SAME)
                        .activation("identity").build())
                .layer(GlobalPoolingLayer.Builder().build())
                .layer(LossLayer(lossFunction="mcxent",
                                 activation="softmax"))
                .setInputType(InputType.convolutional(h, w, c))
                .build())
        return MultiLayerNetwork(conf).init()


class ResNet50(ZooModel):
    """Reference: zoo.model.ResNet50 (the data-parallel throughput
    baseline, BASELINE.json configs[1]) — built as a ComputationGraph of
    bottleneck blocks with identity/projection shortcuts."""

    def __init__(self, numClasses=1000, seed=123, inputShape=(3, 224, 224),
                 updater=None, dataType="float32"):
        self.numClasses = numClasses
        self.seed = seed
        self.inputShape = inputShape
        self.updater = updater or Nesterovs(1e-2, 0.9)
        # "bfloat16" = TPU-idiomatic training dtype (the analog of the
        # reference's NeuralNetConfiguration.dataType(DataType.HALF));
        # measured on v5e it is ~1.5-2.6x the f32 throughput at b>=64
        self.dataType = dataType

    def conf(self):
        c, h, w = self.inputShape
        g = (NeuralNetConfiguration.Builder().seed(self.seed)
             .dataType(self.dataType)
             .updater(self.updater).weightInit(WeightInit.RELU)
             .graphBuilder()
             .addInputs("in"))
        g.setInputTypes(InputType.convolutional(h, w, c))

        def conv(name, n, k, s, inp, act="identity", pad_same=True):
            g.addLayer(name,
                       ConvolutionLayer.Builder().nOut(n)
                       .kernelSize([k, k]).stride([s, s])
                       .convolutionMode(ConvolutionMode.SAME if pad_same
                                        else ConvolutionMode.TRUNCATE)
                       .activation(act).build(), inp)
            return name

        def bn(name, inp, act="identity"):
            g.addLayer(name,
                       BatchNormalization.Builder().activation(act).build(),
                       inp)
            return name

        # stem
        x = conv("conv1", 64, 7, 2, "in")
        x = bn("bn1", x, "relu")
        g.addLayer("pool1",
                   SubsamplingLayer.Builder().kernelSize([3, 3])
                   .stride([2, 2]).convolutionMode(ConvolutionMode.SAME)
                   .build(), x)
        x = "pool1"

        def bottleneck(tag, inp, filters, stride, project):
            f1, f2, f3 = filters
            a = conv(f"{tag}_c1", f1, 1, stride, inp)
            a = bn(f"{tag}_b1", a, "relu")
            a = conv(f"{tag}_c2", f2, 3, 1, a)
            a = bn(f"{tag}_b2", a, "relu")
            a = conv(f"{tag}_c3", f3, 1, 1, a)
            a = bn(f"{tag}_b3", a)
            if project:
                s = conv(f"{tag}_proj", f3, 1, stride, inp)
                s = bn(f"{tag}_projbn", s)
            else:
                s = inp
            g.addVertex(f"{tag}_add", ElementWiseVertex("Add"), a, s)
            g.addLayer(f"{tag}_out",
                       ActivationLayer.Builder().activation("relu").build(),
                       f"{tag}_add")
            return f"{tag}_out"

        stages = [
            ("s2", 3, (64, 64, 256), 1),
            ("s3", 4, (128, 128, 512), 2),
            ("s4", 6, (256, 256, 1024), 2),
            ("s5", 3, (512, 512, 2048), 2),
        ]
        for stage, blocks, filters, stride in stages:
            for i in range(blocks):
                x = bottleneck(f"{stage}_{i}", x, filters,
                               stride if i == 0 else 1, i == 0)

        g.addLayer("avgpool", GlobalPoolingLayer.Builder().build(), x)
        g.addLayer("out",
                   OutputLayer.Builder().nOut(self.numClasses)
                   .activation("softmax").lossFunction("mcxent").build(),
                   "avgpool")
        g.setOutputs("out")
        return g.build()

    def init(self) -> ComputationGraph:
        return ComputationGraph(self.conf()).init()


class TextGenerationLSTM(ZooModel):
    """Reference: zoo.model.TextGenerationLSTM (GravesLSTM char-RNN
    baseline, BASELINE.json configs[2])."""

    def __init__(self, vocabSize=77, hidden=256, seqLength=100, seed=123,
                 updater=None):
        self.vocabSize = vocabSize
        self.hidden = hidden
        self.seqLength = seqLength
        self.seed = seed
        self.updater = updater or Adam(2e-3)

    def conf(self):
        return (NeuralNetConfiguration.Builder().seed(self.seed)
                .updater(self.updater).weightInit(WeightInit.XAVIER)
                .list()
                .layer(LSTM.Builder().nOut(self.hidden).activation("tanh")
                       .build())
                .layer(LSTM.Builder().nOut(self.hidden).activation("tanh")
                       .build())
                .layer(RnnOutputLayer.Builder().nOut(self.vocabSize)
                       .activation("softmax").lossFunction("mcxent").build())
                .setInputType(InputType.recurrent(self.vocabSize,
                                                  self.seqLength))
                .build())

    def init(self) -> MultiLayerNetwork:
        return MultiLayerNetwork(self.conf()).init()


class UNet(ZooModel):
    """Reference: zoo.model.UNet (encoder-decoder segmentation net with
    skip concatenations; Deconvolution2D upsampling). Width `base` scales
    the published 64-filter config down for small inputs."""

    def __init__(self, numClasses=1, seed=123, inputShape=(3, 128, 128),
                 base=64, updater=None, dataType="float32"):
        self.numClasses = numClasses
        self.seed = seed
        self.inputShape = inputShape
        self.base = base
        self.updater = updater or Adam(1e-3)
        self.dataType = dataType

    def conf(self):
        from deeplearning4j_tpu.nn import MergeVertex

        c, h, w = self.inputShape
        g = (NeuralNetConfiguration.Builder().seed(self.seed)
             .dataType(self.dataType)
             .updater(self.updater).weightInit(WeightInit.RELU)
             .graphBuilder().addInputs("in"))
        g.setInputTypes(InputType.convolutional(h, w, c))

        def conv(name, n, inp, act="relu", k=3):
            g.addLayer(name, ConvolutionLayer.Builder().nOut(n)
                       .kernelSize([k, k]).stride([1, 1])
                       .convolutionMode(ConvolutionMode.SAME)
                       .activation(act).build(), inp)
            return name

        def down(tag, n, inp):
            a = conv(f"{tag}_c1", n, inp)
            a = conv(f"{tag}_c2", n, a)
            g.addLayer(f"{tag}_pool", SubsamplingLayer.Builder()
                       .kernelSize([2, 2]).stride([2, 2]).build(), a)
            return a, f"{tag}_pool"

        def up(tag, n, inp, skip):
            g.addLayer(f"{tag}_up", Deconvolution2D.Builder().nOut(n)
                       .kernelSize([2, 2]).stride([2, 2])
                       .convolutionMode(ConvolutionMode.SAME)
                       .activation("relu").build(), inp)
            g.addVertex(f"{tag}_cat", MergeVertex(), f"{tag}_up", skip)
            a = conv(f"{tag}_c1", n, f"{tag}_cat")
            return conv(f"{tag}_c2", n, a)

        b = self.base
        s1, x = down("d1", b, "in")
        s2, x = down("d2", b * 2, x)
        s3, x = down("d3", b * 4, x)
        x = conv("mid_c1", b * 8, x)
        x = conv("mid_c2", b * 8, x)
        x = up("u3", b * 4, x, s3)
        x = up("u2", b * 2, x, s2)
        x = up("u1", b, x, s1)
        # 1x1 conv to class logits + per-pixel sigmoid loss (UNet's
        # published single-channel mask head)
        conv("logits", self.numClasses, x, act="identity", k=1)
        g.addLayer("out", LossLayer(lossFunction="xent",
                                    activation="sigmoid"), "logits")
        g.setOutputs("out")
        return g.build()

    def init(self) -> ComputationGraph:
        return ComputationGraph(self.conf()).init()


class SqueezeNet(ZooModel):
    """Reference: zoo.model.SqueezeNet (v1.1: fire modules — 1x1
    squeeze, parallel 1x1/3x3 expands concatenated)."""

    def __init__(self, numClasses=1000, seed=123, inputShape=(3, 227, 227),
                 updater=None, dataType="float32"):
        self.numClasses = numClasses
        self.seed = seed
        self.inputShape = inputShape
        self.updater = updater or Adam(1e-3)
        self.dataType = dataType

    def conf(self):
        from deeplearning4j_tpu.nn import MergeVertex

        c, h, w = self.inputShape
        g = (NeuralNetConfiguration.Builder().seed(self.seed)
             .dataType(self.dataType)
             .updater(self.updater).weightInit(WeightInit.RELU)
             .graphBuilder().addInputs("in"))
        g.setInputTypes(InputType.convolutional(h, w, c))

        def fire(tag, inp, squeeze, expand):
            g.addLayer(f"{tag}_sq", ConvolutionLayer.Builder().nOut(squeeze)
                       .kernelSize([1, 1]).stride([1, 1])
                       .activation("relu").build(), inp)
            g.addLayer(f"{tag}_e1", ConvolutionLayer.Builder().nOut(expand)
                       .kernelSize([1, 1]).stride([1, 1])
                       .activation("relu").build(), f"{tag}_sq")
            g.addLayer(f"{tag}_e3", ConvolutionLayer.Builder().nOut(expand)
                       .kernelSize([3, 3]).stride([1, 1])
                       .convolutionMode(ConvolutionMode.SAME)
                       .activation("relu").build(), f"{tag}_sq")
            g.addVertex(f"{tag}_cat", MergeVertex(), f"{tag}_e1",
                        f"{tag}_e3")
            return f"{tag}_cat"

        g.addLayer("conv1", ConvolutionLayer.Builder().nOut(64)
                   .kernelSize([3, 3]).stride([2, 2]).activation("relu")
                   .build(), "in")
        g.addLayer("pool1", SubsamplingLayer.Builder().kernelSize([3, 3])
                   .stride([2, 2]).build(), "conv1")
        x = fire("f2", "pool1", 16, 64)
        x = fire("f3", x, 16, 64)
        g.addLayer("pool3", SubsamplingLayer.Builder().kernelSize([3, 3])
                   .stride([2, 2]).build(), x)
        x = fire("f4", "pool3", 32, 128)
        x = fire("f5", x, 32, 128)
        g.addLayer("pool5", SubsamplingLayer.Builder().kernelSize([3, 3])
                   .stride([2, 2]).build(), x)
        x = fire("f6", "pool5", 48, 192)
        x = fire("f7", x, 48, 192)
        x = fire("f8", x, 64, 256)
        x = fire("f9", x, 64, 256)
        g.addLayer("drop", DropoutLayer.Builder().dropOut(0.5).build(), x)
        g.addLayer("conv10", ConvolutionLayer.Builder()
                   .nOut(self.numClasses).kernelSize([1, 1]).stride([1, 1])
                   .activation("relu").build(), "drop")
        g.addLayer("gap", GlobalPoolingLayer.Builder().build(), "conv10")
        g.addLayer("out", LossLayer(lossFunction="mcxent",
                                    activation="softmax"), "gap")
        g.setOutputs("out")
        return g.build()

    def init(self) -> ComputationGraph:
        return ComputationGraph(self.conf()).init()


class Xception(ZooModel):
    """Reference: zoo.model.Xception (depthwise-separable convolutions
    with residual shortcuts; `blocks` scales the published 8-block middle
    flow for small inputs)."""

    def __init__(self, numClasses=1000, seed=123, inputShape=(3, 299, 299),
                 blocks=8, updater=None, dataType="float32"):
        self.numClasses = numClasses
        self.seed = seed
        self.inputShape = inputShape
        self.blocks = blocks
        self.updater = updater or Adam(1e-3)
        self.dataType = dataType

    def conf(self):
        c, h, w = self.inputShape
        g = (NeuralNetConfiguration.Builder().seed(self.seed)
             .dataType(self.dataType)
             .updater(self.updater).weightInit(WeightInit.RELU)
             .graphBuilder().addInputs("in"))
        g.setInputTypes(InputType.convolutional(h, w, c))

        def sep(name, n, inp, act="relu"):
            g.addLayer(name, SeparableConvolution2D.Builder().nOut(n)
                       .kernelSize([3, 3]).stride([1, 1])
                       .convolutionMode(ConvolutionMode.SAME)
                       .activation(act).build(), inp)
            return name

        def bn(name, inp, act="identity"):
            g.addLayer(name, BatchNormalization.Builder().activation(act)
                       .build(), inp)
            return name

        # entry flow (compressed: conv stem + one strided sep block)
        g.addLayer("conv1", ConvolutionLayer.Builder().nOut(32)
                   .kernelSize([3, 3]).stride([2, 2]).activation("relu")
                   .build(), "in")
        x = bn("bn1", "conv1", "relu")
        g.addLayer("conv2", ConvolutionLayer.Builder().nOut(64)
                   .kernelSize([3, 3]).stride([1, 1]).activation("relu")
                   .build(), x)
        x = bn("bn2", "conv2", "relu")
        mid = 128
        a = sep("entry_s1", mid, x)
        a = bn("entry_b1", a, "relu")
        a = sep("entry_s2", mid, a)
        a = bn("entry_b2", a)
        g.addLayer("entry_pool", SubsamplingLayer.Builder()
                   .kernelSize([3, 3]).stride([2, 2])
                   .convolutionMode(ConvolutionMode.SAME).build(), a)
        g.addLayer("entry_proj", ConvolutionLayer.Builder().nOut(mid)
                   .kernelSize([1, 1]).stride([2, 2]).build(), x)
        g.addVertex("entry_add", ElementWiseVertex("Add"), "entry_pool",
                    "entry_proj")
        x = "entry_add"

        # middle flow: residual triple-separable blocks
        for i in range(self.blocks):
            tag = f"mid{i}"
            a = sep(f"{tag}_s1", mid, x)
            a = bn(f"{tag}_b1", a, "relu")
            a = sep(f"{tag}_s2", mid, a)
            a = bn(f"{tag}_b2", a, "relu")
            a = sep(f"{tag}_s3", mid, a)
            a = bn(f"{tag}_b3", a)
            g.addVertex(f"{tag}_add", ElementWiseVertex("Add"), a, x)
            x = f"{tag}_add"

        # exit flow
        a = sep("exit_s1", mid * 2, x)
        a = bn("exit_b1", a, "relu")
        g.addLayer("gap", GlobalPoolingLayer.Builder().build(), a)
        g.addLayer("out", OutputLayer.Builder().nOut(self.numClasses)
                   .activation("softmax").lossFunction("mcxent").build(),
                   "gap")
        g.setOutputs("out")
        return g.build()

    def init(self) -> ComputationGraph:
        return ComputationGraph(self.conf()).init()


class TinyYOLO(ZooModel):
    """Reference: zoo.model.TinyYOLO (tiny-YOLOv2 on VOC: 5 anchor priors,
    20 classes, 416x416 input -> 13x13 grid)."""

    PRIORS = [[1.08, 1.19], [3.42, 4.41], [6.63, 11.38], [9.42, 5.11],
              [16.62, 10.52]]

    def __init__(self, numClasses=20, seed=123, inputShape=(3, 416, 416),
                 boundingBoxPriors=None, updater=None):
        self.numClasses = numClasses
        self.seed = seed
        self.inputShape = inputShape
        self.priors = (boundingBoxPriors if boundingBoxPriors is not None
                       else self.PRIORS)
        self.updater = updater or Adam(1e-3)

    def conf(self):
        from deeplearning4j_tpu.nn.conf.objdetect import Yolo2OutputLayer

        c, h, w = self.inputShape
        b = (NeuralNetConfiguration.Builder().seed(self.seed)
             .updater(self.updater).weightInit(WeightInit.RELU).list())

        def conv(n, k=3):
            return (ConvolutionLayer.Builder().nOut(n).kernelSize([k, k])
                    .convolutionMode(ConvolutionMode.SAME)
                    .activation("identity").hasBias(False).build())

        def bn():
            return (BatchNormalization.Builder().activation("leakyrelu")
                    .build())

        for n in (16, 32, 64, 128, 256):
            b = (b.layer(conv(n)).layer(bn())
                 .layer(SubsamplingLayer.Builder().kernelSize([2, 2])
                        .stride([2, 2]).build()))
        # stride-1 SAME pool keeps the 13x13 grid (tiny-YOLOv2 layer 6)
        b = (b.layer(conv(512)).layer(bn())
             .layer(SubsamplingLayer.Builder().kernelSize([2, 2])
                    .stride([1, 1])
                    .convolutionMode(ConvolutionMode.SAME).build()))
        for n in (1024, 1024):
            b = b.layer(conv(n)).layer(bn())
        n_out = len(self.priors) * (5 + self.numClasses)
        return (b.layer(ConvolutionLayer.Builder().nOut(n_out)
                        .kernelSize([1, 1])
                        .convolutionMode(ConvolutionMode.SAME)
                        .activation("identity").build())
                .layer(Yolo2OutputLayer(boundingBoxPriors=self.priors))
                .setInputType(InputType.convolutional(h, w, c))
                .build())

    def init(self) -> MultiLayerNetwork:
        return MultiLayerNetwork(self.conf()).init()


class YOLO2(ZooModel):
    """Reference: zoo.model.YOLO2 — Darknet-19 backbone + the SpaceToDepth
    'reorg' passthrough merging the 26x26 mid-level features into the
    13x13 head (built as a ComputationGraph, like the reference)."""

    PRIORS = [[0.57273, 0.677385], [1.87446, 2.06253], [3.33843, 5.47434],
              [7.88282, 3.52778], [9.77052, 9.16828]]

    def __init__(self, numClasses=80, seed=123, inputShape=(3, 416, 416),
                 boundingBoxPriors=None, updater=None):
        self.numClasses = numClasses
        self.seed = seed
        self.inputShape = inputShape
        self.priors = (boundingBoxPriors if boundingBoxPriors is not None
                       else self.PRIORS)
        self.updater = updater or Adam(1e-3)

    def conf(self):
        from deeplearning4j_tpu.nn import MergeVertex
        from deeplearning4j_tpu.nn.conf.layers import SpaceToDepth
        from deeplearning4j_tpu.nn.conf.objdetect import Yolo2OutputLayer

        c, h, w = self.inputShape
        g = (NeuralNetConfiguration.Builder().seed(self.seed)
             .updater(self.updater).weightInit(WeightInit.RELU)
             .graphBuilder()
             .addInputs("in"))
        g.setInputTypes(InputType.convolutional(h, w, c))

        idx = [0]

        def conv(n, k, x):
            name = f"c{idx[0]}"
            idx[0] += 1
            g.addLayer(name, ConvolutionLayer.Builder().nOut(n)
                       .kernelSize([k, k])
                       .convolutionMode(ConvolutionMode.SAME)
                       .activation("identity").hasBias(False).build(), x)
            g.addLayer(name + "b", BatchNormalization.Builder()
                       .activation("leakyrelu").build(), name)
            return name + "b"

        def pool(x):
            name = f"p{idx[0]}"
            idx[0] += 1
            g.addLayer(name, SubsamplingLayer.Builder().kernelSize([2, 2])
                       .stride([2, 2]).build(), x)
            return name

        # darknet-19 trunk
        x = conv(32, 3, "in")
        x = pool(x)
        x = conv(64, 3, x)
        x = pool(x)
        for n1, n2 in ((128, 64), (256, 128)):
            x = conv(n1, 3, x)
            x = conv(n2, 1, x)
            x = conv(n1, 3, x)
            x = pool(x)
        x = conv(512, 3, x)
        x = conv(256, 1, x)
        x = conv(512, 3, x)
        x = conv(256, 1, x)
        x = conv(512, 3, x)
        passthrough = x                     # 26x26x512 mid-level features
        x = pool(x)
        x = conv(1024, 3, x)
        x = conv(512, 1, x)
        x = conv(1024, 3, x)
        x = conv(512, 1, x)
        x = conv(1024, 3, x)
        x = conv(1024, 3, x)
        x = conv(1024, 3, x)

        # reorg passthrough: 1x1 conv to 64ch, then 26x26x64 -> 13x13x256,
        # concat with the 13x13x1024 head (YOLOv2 layout)
        p = conv(64, 1, passthrough)
        g.addLayer("reorg", SpaceToDepth.Builder().blockSize(2).build(), p)
        g.addVertex("cat", MergeVertex(), "reorg", x)
        x = conv(1024, 3, "cat")
        n_out = len(self.priors) * (5 + self.numClasses)
        g.addLayer("head", ConvolutionLayer.Builder().nOut(n_out)
                   .kernelSize([1, 1]).convolutionMode(ConvolutionMode.SAME)
                   .activation("identity").build(), x)
        g.addLayer("out", Yolo2OutputLayer(boundingBoxPriors=self.priors),
                   "head")
        g.setOutputs("out")
        return g.build()

    def init(self) -> ComputationGraph:
        return ComputationGraph(self.conf()).init()


class NASNet(ZooModel):
    """Reference: zoo.model.NASNet (NASNet-A Mobile: numBlocks normal
    cells per stage, reduction cells between stages,
    penultimateFilters = 24 * base filter count). Cell topology follows
    NASNet-A: each cell squeezes its two inputs (h, h_prev) to the
    stage's filter count with 1x1 conv+BN, runs the published 5-branch
    separable-conv/pool block mix, and concatenates the branch outputs;
    reduction cells stride 2 with a strided 1x1 projection as the
    h_prev spatial adjust (capability-parity stand-in for the factorized
    reduction)."""

    def __init__(self, numClasses=1000, seed=123, inputShape=(3, 224, 224),
                 numBlocks=4, penultimateFilters=1056, stemFilters=32,
                 updater=None, dataType="float32"):
        if penultimateFilters % 24:
            raise ValueError(
                f"penultimateFilters must be divisible by 24 (NASNet-A "
                f"concat width), got {penultimateFilters}")
        self.numClasses = numClasses
        self.seed = seed
        self.inputShape = inputShape
        self.numBlocks = numBlocks
        self.penultimateFilters = penultimateFilters
        self.stemFilters = stemFilters
        self.updater = updater or Adam(1e-3)
        self.dataType = dataType

    def conf(self):
        c, h, w = self.inputShape
        g = (NeuralNetConfiguration.Builder().seed(self.seed)
             .dataType(self.dataType)
             .updater(self.updater).weightInit(WeightInit.RELU)
             .graphBuilder().addInputs("in"))
        g.setInputTypes(InputType.convolutional(h, w, c))
        f0 = self.penultimateFilters // 24

        def conv1x1(name, n, inp, stride=1):
            g.addLayer(f"{name}_c", ConvolutionLayer.Builder().nOut(n)
                       .kernelSize([1, 1]).stride([stride, stride])
                       .convolutionMode(ConvolutionMode.SAME)
                       .activation("relu").build(), inp)
            g.addLayer(name, BatchNormalization.Builder().build(),
                       f"{name}_c")
            return name

        def sep_block(name, n, k, stride, inp):
            """relu -> sepconv(k, stride) -> bn -> relu -> sepconv(k) -> bn
            (the NASNet separable stack)."""
            g.addLayer(f"{name}_s1", SeparableConvolution2D.Builder()
                       .nOut(n).kernelSize([k, k]).stride([stride, stride])
                       .convolutionMode(ConvolutionMode.SAME)
                       .activation("relu").build(), inp)
            g.addLayer(f"{name}_b1", BatchNormalization.Builder()
                       .activation("relu").build(), f"{name}_s1")
            g.addLayer(f"{name}_s2", SeparableConvolution2D.Builder()
                       .nOut(n).kernelSize([k, k]).stride([1, 1])
                       .convolutionMode(ConvolutionMode.SAME)
                       .activation("identity").build(), f"{name}_b1")
            g.addLayer(name, BatchNormalization.Builder().build(),
                       f"{name}_s2")
            return name

        def pool(name, kind, stride, inp):
            g.addLayer(name, SubsamplingLayer.Builder()
                       .poolingType(kind)
                       .kernelSize([3, 3]).stride([stride, stride])
                       .convolutionMode(ConvolutionMode.SAME).build(), inp)
            return name

        def add(name, a, b):
            g.addVertex(name, ElementWiseVertex("Add"), a, b)
            return name

        # spatial size (square) per tensor name: the h_prev input of a
        # cell that follows a reduction is at 2x the cell resolution, so
        # its 1x1 adjust must stride by size[p] // target
        sz = {}

        def normal_cell(tag, p, x, n):
            hq = conv1x1(f"{tag}_hq", n, x)
            # ceil-divide: odd sizes (e.g. 15 -> 8 under SAME/s2) need
            # stride 2 even though floor(15/8) = 1
            pq = conv1x1(f"{tag}_pq", n, p, stride=-(-sz[p] // sz[x]))
            sz[f"{tag}_out"] = sz[x]
            b1 = add(f"{tag}_b1", sep_block(f"{tag}_b1l", n, 3, 1, hq),
                     sep_block(f"{tag}_b1r", n, 5, 1, pq))
            b2 = add(f"{tag}_b2", sep_block(f"{tag}_b2l", n, 5, 1, pq),
                     sep_block(f"{tag}_b2r", n, 3, 1, pq))
            b3 = add(f"{tag}_b3", pool(f"{tag}_b3l", PoolingType.AVG, 1,
                                       hq), pq)
            b4 = add(f"{tag}_b4", pool(f"{tag}_b4l", PoolingType.AVG, 1,
                                       pq),
                     pool(f"{tag}_b4r", PoolingType.AVG, 1, pq))
            b5 = add(f"{tag}_b5", sep_block(f"{tag}_b5l", n, 3, 1, hq),
                     hq)
            g.addVertex(f"{tag}_out", MergeVertex(), pq, b1, b2, b3, b4,
                        b5)
            return f"{tag}_out"

        def reduction_cell(tag, p, x, n):
            target = -(-sz[x] // 2)
            hq = conv1x1(f"{tag}_hq", n, x)
            pq = conv1x1(f"{tag}_pq", n, p, stride=-(-sz[p] // target))
            sz[f"{tag}_out"] = target
            # pq is already stride-adjusted to the target size, so every
            # pq-side branch runs stride 1; hq-side branches stride 2
            b1 = add(f"{tag}_b1", sep_block(f"{tag}_b1l", n, 5, 2, hq),
                     sep_block(f"{tag}_b1r", n, 7, 1, pq))
            b2 = add(f"{tag}_b2", pool(f"{tag}_b2l", PoolingType.MAX, 2,
                                       hq),
                     sep_block(f"{tag}_b2r", n, 7, 1, pq))
            b3 = add(f"{tag}_b3", pool(f"{tag}_b3l", PoolingType.AVG, 2,
                                       hq),
                     sep_block(f"{tag}_b3r", n, 5, 1, pq))
            b4 = add(f"{tag}_b4", pool(f"{tag}_b4l", PoolingType.MAX, 2,
                                       hq),
                     sep_block(f"{tag}_b4r", n, 3, 1, b1))
            b5 = add(f"{tag}_b5", pool(f"{tag}_b5l", PoolingType.AVG, 1,
                                       b1), b2)
            g.addVertex(f"{tag}_out", MergeVertex(), b2, b3, b4, b5)
            return f"{tag}_out"

        # stem
        g.addLayer("stem_conv", ConvolutionLayer.Builder()
                   .nOut(self.stemFilters).kernelSize([3, 3])
                   .stride([2, 2]).convolutionMode(ConvolutionMode.SAME)
                   .build(), "in")
        g.addLayer("stem_bn", BatchNormalization.Builder().build(),
                   "stem_conv")
        sz["stem_bn"] = -(-h // 2)
        p, x = "stem_bn", reduction_cell("stem_r1", "stem_bn", "stem_bn",
                                         f0 // 2 or 1)
        p, x = x, reduction_cell("stem_r2", p, x, f0 // 2 or 1)

        filters = f0
        for stage in range(3):
            for i in range(self.numBlocks):
                p, x = x, normal_cell(f"s{stage}n{i}", p, x, filters)
            if stage < 2:
                p, x = x, reduction_cell(f"s{stage}r", p, x, filters * 2)
                filters *= 2

        g.addLayer("relu_out", ActivationLayer.Builder()
                   .activation("relu").build(), x)
        g.addLayer("gap", GlobalPoolingLayer.Builder().build(),
                   "relu_out")
        g.addLayer("out", OutputLayer.Builder().nOut(self.numClasses)
                   .activation("softmax").lossFunction("mcxent").build(),
                   "gap")
        g.setOutputs("out")
        return g.build()

    def init(self) -> ComputationGraph:
        return ComputationGraph(self.conf()).init()


class VGG19(VGG16):
    """Reference: zoo.model.VGG19 — VGG16 with a 4th conv in the last
    three blocks (same builder, different BLOCKS)."""

    BLOCKS = ((64, 2), (128, 2), (256, 4), (512, 4), (512, 4))


class FaceNetNN4Small2(ZooModel):
    """Reference: zoo.model.FaceNetNN4Small2 — the face-embedding model
    trained with CenterLossOutputLayer. Inception-style graph: stem convs,
    mixed 1x1/3x3/5x5/pool towers merged on the channel axis, embedding
    dense layer, center-loss softmax head."""

    def __init__(self, numClasses=10, seed=123, inputShape=(3, 96, 96),
                 embeddingSize=128, lambdaCoeff=2e-4, updater=None):
        self.numClasses = numClasses
        self.seed = seed
        self.inputShape = inputShape
        self.embeddingSize = embeddingSize
        self.lambdaCoeff = lambdaCoeff
        self.updater = updater or Adam(1e-3)

    def conf(self):
        from deeplearning4j_tpu.nn import (
            CenterLossOutputLayer, L2NormalizeVertex, MergeVertex)

        c, h, w = self.inputShape
        g = (NeuralNetConfiguration.Builder().seed(self.seed)
             .updater(self.updater).weightInit(WeightInit.RELU)
             .graphBuilder().addInputs("in"))
        g.setInputTypes(InputType.convolutional(h, w, c))

        def conv(name, src, n, k, s=1):
            g.addLayer(name, ConvolutionLayer.Builder().nOut(n)
                       .kernelSize([k, k]).stride([s, s])
                       .convolutionMode(ConvolutionMode.SAME)
                       .activation("identity").hasBias(False).build(), src)
            g.addLayer(name + "_bn", BatchNormalization.Builder()
                       .activation("relu").build(), name)
            return name + "_bn"

        # stem
        x = conv("stem1", "in", 64, 7, 2)
        g.addLayer("stem_pool", SubsamplingLayer.Builder()
                   .kernelSize([3, 3]).stride([2, 2])
                   .convolutionMode(ConvolutionMode.SAME).build(), x)
        x = conv("stem2", "stem_pool", 64, 1)
        x = conv("stem3", x, 192, 3)
        g.addLayer("stem_pool2", SubsamplingLayer.Builder()
                   .kernelSize([3, 3]).stride([2, 2])
                   .convolutionMode(ConvolutionMode.SAME).build(), x)
        x = "stem_pool2"

        # inception blocks: (1x1, 3x3 reduce->3x3, 5x5 reduce->5x5, pool->1x1)
        def inception(tag, src, n1, r3, n3, r5, n5, np_):
            t1 = conv(f"{tag}_1x1", src, n1, 1)
            t3 = conv(f"{tag}_3r", src, r3, 1)
            t3 = conv(f"{tag}_3x3", t3, n3, 3)
            t5 = conv(f"{tag}_5r", src, r5, 1)
            t5 = conv(f"{tag}_5x5", t5, n5, 5)
            g.addLayer(f"{tag}_pool", SubsamplingLayer.Builder()
                       .kernelSize([3, 3]).stride([1, 1])
                       .convolutionMode(ConvolutionMode.SAME).build(), src)
            tp = conv(f"{tag}_poolproj", f"{tag}_pool", np_, 1)
            g.addVertex(f"{tag}_cat", MergeVertex(), t1, t3, t5, tp)
            return f"{tag}_cat"

        x = inception("inc1", x, 64, 96, 128, 16, 32, 32)
        x = inception("inc2", x, 64, 96, 128, 32, 64, 64)
        g.addLayer("red_pool", SubsamplingLayer.Builder()
                   .kernelSize([3, 3]).stride([2, 2])
                   .convolutionMode(ConvolutionMode.SAME).build(), x)
        x = inception("inc3", "red_pool", 128, 96, 192, 32, 64, 64)

        # embedding + center-loss head
        g.addLayer("gap", GlobalPoolingLayer.Builder().build(), x)
        g.addLayer("embedding", DenseLayer.Builder()
                   .nOut(self.embeddingSize).activation("identity").build(),
                   "gap")
        g.addVertex("l2norm", L2NormalizeVertex(), "embedding")
        g.addLayer("out", CenterLossOutputLayer.Builder()
                   .nOut(self.numClasses).lambdaCoeff(self.lambdaCoeff)
                   .activation("softmax").lossFunction("mcxent").build(),
                   "l2norm")
        g.setOutputs("out")
        return g.build()

    def init(self) -> ComputationGraph:
        return ComputationGraph(self.conf()).init()


class InceptionResNetV1(ZooModel):
    """Reference: zoo.model.InceptionResNetV1 (the FaceNet-class
    inception-resnet: stem + residual inception blocks with a scale on
    the residual branch, embedding + center-loss head like
    FaceNetNN4Small2)."""

    def __init__(self, numClasses=10, seed=123, inputShape=(3, 96, 96),
                 embeddingSize=128, blocksA=2, blocksB=2, lambdaCoeff=2e-4,
                 updater=None):
        self.numClasses = numClasses
        self.seed = seed
        self.inputShape = inputShape
        self.embeddingSize = embeddingSize
        self.blocksA = blocksA
        self.blocksB = blocksB
        self.lambdaCoeff = lambdaCoeff
        self.updater = updater or Adam(1e-3)

    def conf(self):
        from deeplearning4j_tpu.nn import (
            CenterLossOutputLayer, L2NormalizeVertex, MergeVertex,
            ScaleVertex)

        c, h, w = self.inputShape
        g = (NeuralNetConfiguration.Builder().seed(self.seed)
             .updater(self.updater).weightInit(WeightInit.RELU)
             .graphBuilder().addInputs("in"))
        g.setInputTypes(InputType.convolutional(h, w, c))

        def conv(name, src, n, k, s=1, act="relu"):
            g.addLayer(name, ConvolutionLayer.Builder().nOut(n)
                       .kernelSize([k, k]).stride([s, s])
                       .convolutionMode(ConvolutionMode.SAME)
                       .activation("identity").hasBias(False).build(), src)
            g.addLayer(name + "_bn", BatchNormalization.Builder()
                       .activation(act).build(), name)
            return name + "_bn"

        # stem: conv s2, conv, conv, pool -> width 64
        x = conv("stem1", "in", 32, 3, 2)
        x = conv("stem2", x, 32, 3)
        x = conv("stem3", x, 64, 3)
        g.addLayer("stem_pool", SubsamplingLayer.Builder()
                   .kernelSize([3, 3]).stride([2, 2])
                   .convolutionMode(ConvolutionMode.SAME).build(), x)
        x = conv("stem4", "stem_pool", 128, 1)

        def block(tag, src, width, mid, scale=0.17):
            """Inception-resnet block: two towers -> 1x1 up-proj,
            residual-added with a scale (the V1 stabilization)."""
            t1 = conv(f"{tag}_1x1", src, mid, 1)
            t2 = conv(f"{tag}_3a", src, mid, 1)
            t2 = conv(f"{tag}_3b", t2, mid, 3)
            g.addVertex(f"{tag}_cat", MergeVertex(), t1, t2)
            up = conv(f"{tag}_up", f"{tag}_cat", width, 1, act="identity")
            g.addVertex(f"{tag}_scale", ScaleVertex(scale), up)
            g.addVertex(f"{tag}_add", ElementWiseVertex("Add"), src,
                        f"{tag}_scale")
            g.addLayer(f"{tag}_act", ActivationLayer.Builder()
                       .activation("relu").build(), f"{tag}_add")
            return f"{tag}_act"

        for i in range(self.blocksA):
            x = block(f"ira{i}", x, 128, 32)
        # reduction: stride-2 pool + channel up-projection
        g.addLayer("redA_pool", SubsamplingLayer.Builder()
                   .kernelSize([3, 3]).stride([2, 2])
                   .convolutionMode(ConvolutionMode.SAME).build(), x)
        x = conv("redA_proj", "redA_pool", 256, 1)
        for i in range(self.blocksB):
            x = block(f"irb{i}", x, 256, 64, scale=0.1)

        g.addLayer("gap", GlobalPoolingLayer.Builder().build(), x)
        g.addLayer("embedding", DenseLayer.Builder()
                   .nOut(self.embeddingSize).activation("identity").build(),
                   "gap")
        g.addVertex("l2norm", L2NormalizeVertex(), "embedding")
        g.addLayer("out", CenterLossOutputLayer.Builder()
                   .nOut(self.numClasses).lambdaCoeff(self.lambdaCoeff)
                   .activation("softmax").lossFunction("mcxent").build(),
                   "l2norm")
        g.setOutputs("out")
        return g.build()

    def init(self) -> ComputationGraph:
        return ComputationGraph(self.conf()).init()
