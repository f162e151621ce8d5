"""Real-TPU test tier, gated behind DL4J_TPU_TESTS=1.

The suite pins the CPU platform (conftest), so nothing in tier-1 touches
a chip — this tier mirrors the reference's CUDA-gated tests (SURVEY.md §4
implication 4). The tests run real-chip work in SUBPROCESSES because the
pytest parent has already initialized the CPU backend; the children use
the chip one at a time (one process per chip), each with
JAX_PLATFORMS=tpu set explicitly: left to inherit, an unset variable
would let jax fall back to the CPU with a warning and every test that
does not call a compiled Pallas kernel would pass without a chip.

Run on the chip machine:
    DL4J_TPU_TESTS=1 python -m pytest tests/test_tpu_gated.py -v
"""

import os
import subprocess
import sys

import pytest

gated = pytest.mark.skipif(
    os.environ.get("DL4J_TPU_TESTS") != "1",
    reason="real-TPU tier: set DL4J_TPU_TESTS=1 on a machine with a chip")

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _chip_env(**extra):
    """The children's environment: the chip or nothing, and none of the
    CPU-mesh XLA_FLAGS the conftest may have set."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "tpu"
    env.update(extra)
    return env


def _run(script, timeout=420):
    res = subprocess.run([sys.executable, "-c", script], cwd=_REPO,
                         env=_chip_env(), capture_output=True, text=True,
                         timeout=timeout)
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr}"
    return res.stdout


@gated
class TestRealChip:
    def test_device_is_tpu(self):
        out = _run("import jax; d = jax.devices()[0]; "
                   "print(d.platform, d.device_kind)")
        assert "tpu" in out.lower()

    def test_bert_step_trains_on_chip(self):
        out = _run("""
import numpy as np, jax
from deeplearning4j_tpu.models.bert import (BertConfig, BertTrainer,
                                            synthetic_mlm_batch)
from deeplearning4j_tpu.parallel.mesh import MeshConfig
cfg = BertConfig(vocab_size=500, hidden=64, num_layers=2, num_heads=2,
                 ffn=128, max_len=64)
mesh = MeshConfig(data=1, devices=jax.devices()[:1]).build()
tr = BertTrainer(cfg, mesh, lr=1e-3)
tok, lab = synthetic_mlm_batch(cfg, 4, 64, seed=0)
l0 = float(tr.train_step(tok, lab))
for _ in range(4):
    l1 = float(tr.train_step(tok, lab))
assert np.isfinite(l0) and l1 < l0, (l0, l1)
print('OK', l0, l1)
""")
        assert "OK" in out

    def test_flash_attention_matches_dense_on_chip(self):
        out = _run("""
import numpy as np, jax, jax.numpy as jnp
from deeplearning4j_tpu.models.bert import (BertConfig, _attention)
cfg_d = BertConfig(attention_impl='dense')
cfg_f = BertConfig(attention_impl='flash')
k = jax.random.key(0)
q, kk, v = (jax.random.normal(jax.random.fold_in(k, i),
            (2, 4, 256, 64), jnp.bfloat16) for i in range(3))
d = np.asarray(_attention(q, kk, v, None, cfg_d).astype(jnp.float32))
f = np.asarray(_attention(q, kk, v, None, cfg_f).astype(jnp.float32))
np.testing.assert_allclose(d, f, rtol=5e-2, atol=5e-2)
print('OK')
""")
        assert "OK" in out

    def test_long_sequence_auto_selects_flash(self):
        """T=2048 on TPU: 'auto' must route to the Pallas flash kernel
        (asserted by making the dense path raise) and match a dense
        softmax reference on a query slice. T=1100 (non-128-divisible)
        must stay dense rather than crash the kernel."""
        out = _run("""
import math
import numpy as np, jax, jax.numpy as jnp
import deeplearning4j_tpu.models.bert as bert
cfg = bert.BertConfig(attention_impl='auto')
k = jax.random.key(0)
q, kk, v = (jax.random.normal(jax.random.fold_in(k, i),
            (1, 4, 2048, 64), jnp.bfloat16) for i in range(3))
_dense = bert._dense_attention
def _boom(*a):
    raise AssertionError('auto resolved to dense at T=2048')
bert._dense_attention = _boom
try:
    out = bert._attention(q, kk, v, None, cfg)
finally:
    bert._dense_attention = _dense
assert out.shape == (1, 4, 2048, 64)
s = jnp.einsum('bhqd,bhkd->bhqk', q[:, :, :256].astype(jnp.float32),
               kk.astype(jnp.float32)) / math.sqrt(64)
w = jax.nn.softmax(s, axis=-1)
ref = jnp.einsum('bhqk,bhkd->bhqd', w, v.astype(jnp.float32))
np.testing.assert_allclose(np.asarray(out[:, :, :256], np.float32),
                           np.asarray(ref), rtol=5e-2, atol=5e-2)
# non-128-divisible long T falls back to dense without crashing
q2, k2, v2 = (a[:, :, :1100] for a in (q, kk, v))
out2 = bert._attention(q2, k2, v2, None, cfg)
assert out2.shape == (1, 4, 1100, 64)
print('OK')
""")
        assert "OK" in out


@gated
class TestRealChipRound2:
    """Round-2 session features on the real chip."""

    def test_yolo_detects_on_chip(self):
        _run("""
import numpy as np
from deeplearning4j_tpu.models import TinyYOLO
from deeplearning4j_tpu.nn import YoloUtils
net = TinyYOLO(numClasses=3, inputShape=(3, 128, 128),
               boundingBoxPriors=[[1.0, 1.0], [3.0, 3.0]]).init()
rng = np.random.RandomState(0)
xs, ys = [], []
for k in range(8):
    img = rng.rand(3, 128, 128).astype(np.float32) * 0.1
    ci, cj = k % 4, (k * 2 + 1) % 4
    img[:, ci * 32 + 8:ci * 32 + 24, cj * 32 + 8:cj * 32 + 24] = 1.0
    lab = np.zeros((7, 4, 4), np.float32)
    cx, cy = cj + 0.5, ci + 0.5
    lab[0, ci, cj] = cx - 0.5; lab[1, ci, cj] = cy - 0.5
    lab[2, ci, cj] = cx + 0.5; lab[3, ci, cj] = cy + 0.5
    lab[4, ci, cj] = 1.0
    xs.append(img); ys.append(lab)
x, y = np.stack(xs), np.stack(ys)
net.fit([(x, y)] * 200)
objs = YoloUtils.getPredictedObjects(net.output(x).numpy(),
                                     threshold=0.3)
assert len(objs) >= 4, len(objs)
print("OK")
""", timeout=540)

    def test_vae_pretrain_on_chip(self):
        _run("""
import numpy as np
from deeplearning4j_tpu.nn import (MultiLayerNetwork,
    NeuralNetConfiguration, OutputLayer, VariationalAutoencoder)
from deeplearning4j_tpu.optimize.updaters import Adam
rng = np.random.RandomState(0)
x = (rng.rand(128, 16) > 0.5).astype(np.float32)
b = (NeuralNetConfiguration.Builder().seed(7).updater(Adam(1e-2)).list()
     .layer(VariationalAutoencoder.Builder().nIn(16).nOut(4)
            .encoderLayerSizes([24]).decoderLayerSizes([24]).build())
     .layer(OutputLayer.Builder().nOut(2).build()))
net = MultiLayerNetwork(b.build()).init()
import jax
key = jax.random.key(0)
e0 = float(net.layers[0].pretrain_loss(net._params[0], x, key))
net.pretrain([(x, None)] * 50)
e1 = float(net.layers[0].pretrain_loss(net._params[0], x, key))
assert e1 < e0, (e0, e1)
print("OK")
""")

    def test_attention_classifier_on_chip(self):
        _run("""
import numpy as np
from deeplearning4j_tpu.nn import (GlobalPoolingLayer, MultiLayerNetwork,
    NeuralNetConfiguration, OutputLayer, SelfAttentionLayer, InputType)
from deeplearning4j_tpu.optimize.updaters import Adam
rng = np.random.RandomState(0)
x = rng.randn(32, 4, 10).astype(np.float32)
y = np.eye(2, dtype=np.float32)[(x.sum((1, 2)) > 0).astype(int)]
b = (NeuralNetConfiguration.Builder().seed(2).updater(Adam(1e-2)).list()
     .layer(SelfAttentionLayer.Builder(nOut=8, nHeads=2,
                                       activation="tanh").build())
     .layer(GlobalPoolingLayer.Builder().build())
     .layer(OutputLayer.Builder().nOut(2).build())
     .setInputType(InputType.recurrent(4, 10)))
net = MultiLayerNetwork(b.build()).init()
s0 = net.score((x, y))
net.fit([(x, y)] * 40)
assert net.score((x, y)) < s0
print("OK")
""")


@gated
class TestPallasLstmOnChip:
    def test_compiled_kernel_matches_scan(self):
        out = _run("""
import numpy as np, jax, jax.numpy as jnp, os
from deeplearning4j_tpu.kernels.lstm import lstm_seq
rng = np.random.default_rng(0)
t, n, h = 12, 8, 128
xw = jnp.asarray(rng.normal(size=(t, n, 4*h))*0.3, jnp.float32)
r = jnp.asarray(rng.normal(size=(h, 4*h))*0.1, jnp.float32)
h0 = jnp.asarray(rng.normal(size=(n, h))*0.2, jnp.float32)
c0 = jnp.zeros((n, h), jnp.float32)
hs_c, hT_c, cT_c = jax.jit(lambda *a: lstm_seq(*a, False))(xw, r, h0, c0)
hs_i, _, _ = lstm_seq(xw, r, h0, c0, True)
np.testing.assert_allclose(np.asarray(hs_c), np.asarray(hs_i),
                           rtol=3e-5, atol=2e-5)
def loss(impl):
    def f(xw, r):
        hs, hT, cT = lstm_seq(xw, r, h0, c0, impl)
        return jnp.sum(hs * hs) + jnp.sum(hT) - jnp.sum(cT)
    return f
gc = jax.jit(jax.grad(loss(False), argnums=(0, 1)))(xw, r)
gi = jax.grad(loss(True), argnums=(0, 1))(xw, r)
for a, b in zip(gc, gi):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=5e-4, atol=5e-5)
print("PALLAS_LSTM_PARITY_OK")
""")
        assert "PALLAS_LSTM_PARITY_OK" in out

    def test_lstm_layer_routes_to_kernel_and_trains(self):
        out = _run("""
import numpy as np
from deeplearning4j_tpu.nn import (InputType, LSTM, MultiLayerNetwork,
                                   NeuralNetConfiguration, RnnOutputLayer)
from deeplearning4j_tpu.optimize.updaters import Adam
# H=128 batch=8: satisfies the kernel's shape gate on TPU
conf = (NeuralNetConfiguration.Builder().seed(0).updater(Adam(5e-3))
        .list()
        .layer(LSTM.Builder().nOut(128).activation("tanh").build())
        .layer(RnnOutputLayer.Builder().nOut(5).activation("softmax")
               .build())
        .setInputType(InputType.recurrent(5, 16)).build())
net = MultiLayerNetwork(conf); net.init()
rng = np.random.default_rng(0)
ids = rng.integers(0, 5, (8, 17))
X = np.eye(5, dtype=np.float32)[ids[:, :-1]].transpose(0, 2, 1)
y = np.eye(5, dtype=np.float32)[ids[:, 1:]].transpose(0, 2, 1)
s0 = net.score((X, y))
net.fit([(X, y)] * 25)
s1 = net.score((X, y))
assert s1 < s0, (s0, s1)
print("PALLAS_LSTM_TRAIN_OK", s0, "->", s1)
""")
        assert "PALLAS_LSTM_TRAIN_OK" in out


@gated
class TestPallasLstmRoutedBranchParity:
    def test_lstm_layer_kernel_vs_scan_with_forget_bias(self):
        """The _lstm_layer ROUTING branch (forgetBias fold,
        returnFullSequence=False) must match the scan branch numerically
        — run both in subprocesses toggled by DL4J_DISABLE_PALLAS_LSTM."""
        script = """
import numpy as np, jax, jax.numpy as jnp
from deeplearning4j_tpu.autodiff.ops import OPS
rng = np.random.default_rng(7)
n, i_sz, h, t = 8, 16, 128, 10
x = jnp.asarray(rng.normal(size=(n, i_sz, t)) * 0.5, jnp.float32)
w = jnp.asarray(rng.normal(size=(i_sz, 4 * h)) * 0.1, jnp.float32)
r = jnp.asarray(rng.normal(size=(h, 4 * h)) * 0.1, jnp.float32)
b = jnp.asarray(rng.normal(size=(4 * h,)) * 0.05, jnp.float32)
out, hT, cT = OPS["lstmLayer"](x, w, r, b, forgetBias=1.0)
hT2, _, cT2 = OPS["lstmLayer"](x, w, r, b, forgetBias=1.0,
                               returnFullSequence=False)
g = jax.grad(lambda w, r: jnp.sum(jnp.square(
    OPS["lstmLayer"](x, w, r, b, forgetBias=1.0)[0])),
    argnums=(0, 1))(w, r)
np.save("/tmp/_lstm_branch_{tag}.npy",
        {"out": np.asarray(out), "hT": np.asarray(hT),
         "cT": np.asarray(cT), "hT2": np.asarray(hT2),
         "cT2": np.asarray(cT2), "gw": np.asarray(g[0]),
         "gr": np.asarray(g[1])}, allow_pickle=True)
print("BRANCH_OK")
"""
        import numpy as np

        for tag, env_extra in (("kernel", {}),
                               ("scan", {"DL4J_DISABLE_PALLAS_LSTM": "1"})):
            res = subprocess.run(
                [sys.executable, "-c", script.replace("{tag}", tag)],
                cwd=_REPO, env=_chip_env(**env_extra),
                capture_output=True, text=True, timeout=420)
            assert res.returncode == 0, res.stderr
        a = np.load("/tmp/_lstm_branch_kernel.npy",
                    allow_pickle=True).item()
        b = np.load("/tmp/_lstm_branch_scan.npy",
                    allow_pickle=True).item()
        for k in a:
            np.testing.assert_allclose(a[k], b[k], rtol=5e-4, atol=5e-5,
                                       err_msg=k)


@gated
class TestPallasGruOnChip:
    def test_compiled_matches_interpret_and_layer_trains(self):
        out = _run("""
import numpy as np, jax, jax.numpy as jnp
from deeplearning4j_tpu.kernels.gru import gru_seq
rng = np.random.default_rng(0)
t, n, h = 10, 8, 128
xw = jnp.asarray(rng.normal(size=(t, n, 3*h))*0.3, jnp.float32)
r = jnp.asarray(rng.normal(size=(h, 3*h))*0.1, jnp.float32)
rb = jnp.asarray(rng.normal(size=(3*h,))*0.05, jnp.float32)
h0 = jnp.zeros((n, h), jnp.float32)
hs_c, hT_c = jax.jit(lambda *a: gru_seq(*a, False))(xw, r, rb, h0)
hs_i, hT_i = gru_seq(xw, r, rb, h0, True)
np.testing.assert_allclose(np.asarray(hs_c), np.asarray(hs_i),
                           rtol=3e-5, atol=2e-5)
def loss(impl):
    def f(xw, r, rb):
        hs, hT = gru_seq(xw, r, rb, h0, impl)
        return jnp.sum(hs * hs) + jnp.sum(hT)
    return f
gc = jax.jit(jax.grad(loss(False), argnums=(0, 1, 2)))(xw, r, rb)
gi = jax.grad(loss(True), argnums=(0, 1, 2))(xw, r, rb)
for a, b in zip(gc, gi):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=5e-4, atol=5e-5)

# the gruLayer OP routes through the kernel on TPU (H=128, N=8)
from deeplearning4j_tpu.autodiff.ops import OPS
x = jnp.asarray(rng.normal(size=(8, 6, 12)) * 0.5, jnp.float32)
w = jnp.asarray(rng.normal(size=(6, 3 * 128)) * 0.1, jnp.float32)
r2 = jnp.asarray(rng.normal(size=(128, 3 * 128)) * 0.1, jnp.float32)
b2 = jnp.asarray(rng.normal(size=(6 * 128,)) * 0.05, jnp.float32)
out_k, hT_k = OPS["gruLayer"](x, w, r2, b2)
import os
os.environ["DL4J_DISABLE_PALLAS_GRU"] = "1"
out_s, hT_s = OPS["gruLayer"](x, w, r2, b2)
np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_s),
                           rtol=5e-4, atol=5e-5)
np.testing.assert_allclose(np.asarray(hT_k), np.asarray(hT_s),
                           rtol=5e-4, atol=5e-5)
print("PALLAS_GRU_OK")
""")
        assert "PALLAS_GRU_OK" in out
