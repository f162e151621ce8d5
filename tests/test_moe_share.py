"""ISSUE 34 tests of `parallel/moe.py:moe_share_apply`'s expert forms: the
ungated relu² expert in a latent space between `latent_in` and `latent_out`
(whose four shares, with what every chip computes alike counted once, add up
to the uncut reference's layer, `benchmark/reference/nemotron_h_plain.py`),
and the gated silu form, which gives what it gave."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.drivers import serve_closed_hybrid as driver  # noqa: E402
from benchmark.reference import nemotron_h_plain as plain  # noqa: E402
from deeplearning4j_tpu.parallel import moe  # noqa: E402

# the benchmark's toy hybrid configuration: hidden 64, 16 experts of 32 in a
# latent space of 24, 4 chosen, weighed by 40
TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmark",
                   "data", "toy-hybrid-serve", "configs",
                   "toy-hybrid-lm.json")

# float32 on both sides, summed in other orders
TOL = 2e-5
# 24 rows that choose 4 experts each: the worst case (one batched product
# over the held experts), or a shorter buffer that still takes every held
# choice of these rows (sorted, grouped products)
BOTH_FORMS = pytest.mark.parametrize("rows", [
    pytest.param(96, id="dense"), pytest.param(80, id="grouped")])


def latent_layer(seed=3, rows=24):
    """(the reference's expert layer holding every expert, its sizes, a batch
    of normed rows)."""
    with open(TOY) as f:
        cfg = json.load(f)
    cfg["model"]["experts_held"] = [0, 16]
    sizes = driver.reference_sizes(cfg)
    lp = plain.draw_params(seed, sizes)["layers"][1]
    u = jax.random.normal(jax.random.key(seed), (rows, 64), jnp.float32)
    return lp, sizes, u


def share_of(lp, first, count):
    """The program's parameters of the experts first..first+count-1."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    return {"router": f32(lp["gate"]),
            "bias": f32(lp["e_score_correction_bias"]),
            "latent_in": f32(lp["fc1_latent_proj"]),
            "latent_out": f32(lp["fc2_latent_proj"]),
            **{v: f32(lp["experts"][k][first:first + count])
               for k, v in driver.MLP_NAMES.items()}}


@BOTH_FORMS
def test_the_four_shares_of_a_latent_layer_add_up_to_the_whole(rows):
    assert moe.moe_share_dense(24, 4, rows) == (rows == 96)
    lp, sizes, u = latent_layer()
    whole = plain.sparse(lp, u, sizes, "f32")
    # what every chip computes alike, counted once: the shared expert (the
    # two latent projections are inside each share, and linear)
    total = plain.plain_mlp(lp["shared_experts"], u, "f32")
    chosen = 0
    for first in range(0, 16, 4):
        y, choices, dropped = moe.moe_share_apply(
            share_of(lp, first, 4), u, top_k=4, experts_held=(first, 4),
            routed_scale=sizes["routed_scale"], rows=rows,
            activation="relu2")
        assert y.shape == u.shape and int(dropped) == 0
        total, chosen = total + y, chosen + int(choices.sum())
    assert chosen == 24 * 4
    assert np.abs(np.asarray(total - whole)).max() < TOL
    # and the layer is not the shared expert alone, nor a gated one
    assert np.abs(np.asarray(whole - plain.sparse(
        lp, u, sizes, "f32", shared=False))).max() > 100 * TOL
    silu, _, _ = moe.moe_share_apply(
        share_of(lp, 0, 16), u, top_k=4, experts_held=(0, 16),
        routed_scale=sizes["routed_scale"], rows=rows)
    relu2, _, _ = moe.moe_share_apply(
        share_of(lp, 0, 16), u, top_k=4, experts_held=(0, 16),
        routed_scale=sizes["routed_scale"], rows=rows, activation="relu2")
    assert np.abs(np.asarray(silu - relu2)).max() > 100 * TOL


@BOTH_FORMS
def test_the_gated_silu_form_gives_what_it_gave(rows):
    """Against the layer written out by hand: sigmoid scores, the 4 largest,
    2.5 x s / sum of the chosen, `down(silu(gate x) * up x)` an expert; and
    naming the default activation changes no bit."""
    params = moe.moe_share_init(jax.random.key(7), 64, 32, 16, 8, std=0.1)
    assert set(params) == {"router", "gate", "up", "down"}
    x = jax.random.normal(jax.random.key(8), (24, 64), jnp.float32)
    kw = dict(top_k=4, experts_held=(4, 8), routed_scale=2.5, rows=rows)
    y, choices, dropped = moe.moe_share_apply(params, x, **kw)
    hp = jax.lax.Precision.HIGHEST
    s = jax.nn.sigmoid(jnp.matmul(x, params["router"], precision=hp))
    top_s, top_i = jax.lax.top_k(s, 4)
    w = jnp.zeros_like(s).at[jnp.arange(24)[:, None], top_i].set(
        2.5 * top_s / top_s.sum(-1, keepdims=True))[:, 4:12]
    mid = jax.nn.silu(jnp.einsum("sd,edf->esf", x, params["gate"],
                                 precision=hp)) \
        * jnp.einsum("sd,edf->esf", x, params["up"], precision=hp)
    by_hand = jnp.einsum("esd,se->sd", jnp.einsum(
        "esf,efd->esd", mid, params["down"], precision=hp), w)
    assert np.abs(np.asarray(y - by_hand)).max() < TOL
    assert choices.tolist() == (np.asarray(w) > 0).sum(0).tolist()
    named = moe.moe_share_apply(params, x, **kw, activation="silu")
    for a, b in zip((y, choices, dropped), named):
        assert (np.asarray(a) == np.asarray(b)).all()


def test_the_expert_forms_are_read_from_the_parameters():
    gated = moe.moe_share_init(jax.random.key(0), 64, 32, 16, 4)
    plain_ = moe.moe_share_init(jax.random.key(0), 64, 32, 16, 4,
                                gated=False, latent=24)
    # the leaves both have are drawn alike where their shapes agree
    assert (np.asarray(gated["router"]) == np.asarray(plain_["router"])).all()
    assert set(plain_) == {"router", "up", "down", "latent_in", "latent_out"}
    assert plain_["up"].shape == (4, 24, 32)
    assert plain_["down"].shape == (4, 32, 24)
    assert plain_["latent_in"].shape == (64, 24)
    with pytest.raises(KeyError):
        moe.moe_share_apply(gated, jnp.zeros((8, 64)), top_k=2,
                            experts_held=(0, 4), activation="gelu")
