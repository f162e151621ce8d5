"""Parity tests for the in-repo Pallas kernels (interpret mode on the
CPU test platform; the compiled path is covered by the TPU-gated tier).

Reference analog: libnd4j platform-helper conformance — the custom
kernel must match the generic lowering bit-for-tolerance (SURVEY.md §4
op-validation row)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from deeplearning4j_tpu.kernels.lstm import lstm_seq


def _scan_reference(xw, r, h0, c0):
    hsz = r.shape[0]

    def step(carry, xw_t):
        h, c = carry
        z = xw_t + h @ r
        i = jax.nn.sigmoid(z[:, :hsz])
        f = jax.nn.sigmoid(z[:, hsz:2 * hsz])
        g = jnp.tanh(z[:, 2 * hsz:3 * hsz])
        o = jax.nn.sigmoid(z[:, 3 * hsz:])
        c2 = f * c + i * g
        h2 = o * jnp.tanh(c2)
        return (h2, c2), h2

    (hT, cT), hs = jax.lax.scan(step, (h0, c0), xw)
    return hs, hT, cT


def _data(t=5, n=8, h=128, seed=0):
    rng = np.random.default_rng(seed)
    xw = jnp.asarray(rng.normal(size=(t, n, 4 * h)) * 0.3, jnp.float32)
    r = jnp.asarray(rng.normal(size=(h, 4 * h)) * 0.1, jnp.float32)
    h0 = jnp.asarray(rng.normal(size=(n, h)) * 0.2, jnp.float32)
    c0 = jnp.asarray(rng.normal(size=(n, h)) * 0.2, jnp.float32)
    return xw, r, h0, c0


class TestLstmPallasParity:
    def test_forward_matches_scan(self):
        xw, r, h0, c0 = _data()
        hs_k, hT_k, cT_k = lstm_seq(xw, r, h0, c0, True)
        hs_s, hT_s, cT_s = _scan_reference(xw, r, h0, c0)
        np.testing.assert_allclose(np.asarray(hs_k), np.asarray(hs_s),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(hT_k), np.asarray(hT_s),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(cT_k), np.asarray(cT_s),
                                   rtol=1e-5, atol=1e-6)

    def test_gradients_match_scan(self):
        xw, r, h0, c0 = _data(t=4, n=8, h=128, seed=3)

        def loss_k(xw, r, h0, c0):
            hs, hT, cT = lstm_seq(xw, r, h0, c0, True)
            return (jnp.sum(hs * jnp.cos(hs)) + jnp.sum(hT * hT)
                    + jnp.sum(jnp.abs(cT)))

        def loss_s(xw, r, h0, c0):
            hs, hT, cT = _scan_reference(xw, r, h0, c0)
            return (jnp.sum(hs * jnp.cos(hs)) + jnp.sum(hT * hT)
                    + jnp.sum(jnp.abs(cT)))

        gk = jax.grad(loss_k, argnums=(0, 1, 2, 3))(xw, r, h0, c0)
        gs = jax.grad(loss_s, argnums=(0, 1, 2, 3))(xw, r, h0, c0)
        for a, b, name in zip(gk, gs, ("dxw", "dR", "dh0", "dc0")):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-5,
                err_msg=name)

    def test_single_timestep(self):
        xw, r, h0, c0 = _data(t=1, n=8, h=128, seed=5)
        hs_k, hT_k, cT_k = lstm_seq(xw, r, h0, c0, True)
        hs_s, hT_s, cT_s = _scan_reference(xw, r, h0, c0)
        np.testing.assert_allclose(np.asarray(hs_k), np.asarray(hs_s),
                                   rtol=1e-5, atol=1e-6)


def _gru_scan_reference(xw, r, rb, h0):
    hsz = r.shape[0]

    def step(h, xw_t):
        rz = h @ r + rb
        ru = jax.nn.sigmoid(xw_t[:, :2 * hsz] + rz[:, :2 * hsz])
        cand = jnp.tanh(xw_t[:, 2 * hsz:] + ru[:, :hsz] * rz[:, 2 * hsz:])
        u = ru[:, hsz:]
        h2 = u * h + (1.0 - u) * cand
        return h2, h2

    hT, hs = jax.lax.scan(step, h0, xw)
    return hs, hT


def _gru_data(t=5, n=8, h=128, seed=0):
    rng = np.random.default_rng(seed)
    xw = jnp.asarray(rng.normal(size=(t, n, 3 * h)) * 0.3, jnp.float32)
    r = jnp.asarray(rng.normal(size=(h, 3 * h)) * 0.1, jnp.float32)
    rb = jnp.asarray(rng.normal(size=(3 * h,)) * 0.05, jnp.float32)
    h0 = jnp.asarray(rng.normal(size=(n, h)) * 0.2, jnp.float32)
    return xw, r, rb, h0


class TestGruPallasParity:
    def test_forward_matches_scan(self):
        from deeplearning4j_tpu.kernels.gru import gru_seq

        xw, r, rb, h0 = _gru_data()
        hs_k, hT_k = gru_seq(xw, r, rb, h0, True)
        hs_s, hT_s = _gru_scan_reference(xw, r, rb, h0)
        np.testing.assert_allclose(np.asarray(hs_k), np.asarray(hs_s),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(hT_k), np.asarray(hT_s),
                                   rtol=1e-5, atol=1e-6)

    def test_gradients_match_scan(self):
        from deeplearning4j_tpu.kernels.gru import gru_seq

        xw, r, rb, h0 = _gru_data(t=4, seed=3)

        def loss_k(xw, r, rb, h0):
            hs, hT = gru_seq(xw, r, rb, h0, True)
            return jnp.sum(hs * jnp.sin(hs)) + jnp.sum(hT * hT)

        def loss_s(xw, r, rb, h0):
            hs, hT = _gru_scan_reference(xw, r, rb, h0)
            return jnp.sum(hs * jnp.sin(hs)) + jnp.sum(hT * hT)

        gk = jax.grad(loss_k, argnums=(0, 1, 2, 3))(xw, r, rb, h0)
        gs = jax.grad(loss_s, argnums=(0, 1, 2, 3))(xw, r, rb, h0)
        for a, b, name in zip(gk, gs, ("dxw", "dR", "drb", "dh0")):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-5,
                err_msg=name)


class TestRecurrenceRouting:
    """The kernel-or-scan decision is made in one place, counted, and
    under a batch-sharded step the kernel runs per shard (a bare Mosaic
    call inside a multi-device GSPMD jit is a lowering error on TPU)."""

    def test_route_is_counted_and_gated(self, monkeypatch):
        from deeplearning4j_tpu import kernels, telemetry

        reg = telemetry.get_registry()

        def count(op, route):
            fam = reg.counter("dl4j_recurrence_route_total",
                              kernels.ROUTE_HELP, ("op", "route"))
            return fam.labels(op=op, route=route).value

        before = count("LSTM", "scan")
        assert kernels.recurrence_route("LSTM", True) == "scan"  # cpu
        assert count("LSTM", "scan") == before + 1
        monkeypatch.setenv("DL4J_PALLAS_INTERPRET", "1")
        assert kernels.recurrence_route("LSTM", True) == "interpret"
        assert kernels.recurrence_route("LSTM", False) == "scan"
        monkeypatch.setenv("DL4J_DISABLE_PALLAS_LSTM", "1")
        assert kernels.recurrence_route("LSTM", True) == "scan"
        assert kernels.recurrence_route("GRU", True) == "interpret"
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert kernels.recurrence_route("GRU", True) == "pallas"

    def test_sharded_fit_matches_single_device(self, monkeypatch):
        """ShardedTrainer over 4 devices with the kernel routed per
        batch shard == the same fit on one device (scan route): the
        shard_map transpose must sum dR over the data axis."""
        from deeplearning4j_tpu import kernels
        from deeplearning4j_tpu.nn import (InputType, LSTM,
                                           MultiLayerNetwork,
                                           NeuralNetConfiguration,
                                           RnnOutputLayer)
        from deeplearning4j_tpu.optimize.updaters import Sgd
        from deeplearning4j_tpu.parallel.mesh import MeshConfig
        from deeplearning4j_tpu.parallel.trainer import ShardedTrainer

        def build():
            conf = (NeuralNetConfiguration.Builder().seed(3)
                    .updater(Sgd(0.1)).list()
                    .layer(LSTM.Builder().nOut(128).activation("tanh")
                           .build())
                    .layer(RnnOutputLayer.Builder().nOut(5)
                           .activation("softmax").build())
                    .setInputType(InputType.recurrent(5, 6)).build())
            return MultiLayerNetwork(conf).init()

        rng = np.random.default_rng(0)
        ids = rng.integers(0, 5, (32, 7))
        x = np.eye(5, dtype=np.float32)[ids[:, :-1]].transpose(0, 2, 1)
        y = np.eye(5, dtype=np.float32)[ids[:, 1:]].transpose(0, 2, 1)

        ref = build()
        ref.fit([(x, y)] * 2)
        monkeypatch.setenv("DL4J_PALLAS_INTERPRET", "1")
        net = build()
        mesh = MeshConfig(data=4, devices=jax.devices()[:4]).build()
        seen = []
        real = kernels.per_batch_shard
        monkeypatch.setattr(
            kernels, "per_batch_shard",
            lambda fn, n, *a, **k: (seen.append(kernels.shard_rows(n)),
                                    real(fn, n, *a, **k))[1])
        ShardedTrainer(net, mesh).fit([(x, y)] * 2)
        assert seen and set(seen) == {8}, seen   # 32 rows / 4 devices
        for a, b in zip(jax.tree_util.tree_leaves(ref._params),
                        jax.tree_util.tree_leaves(net._params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)
