"""The arithmetic of the latent-attention serving cell whose residual path
holds several streams (hyper-connections): parameters, operations and bytes of
one token step from shapes, by the **least work** the mathematics needs.
`lib/arith_mla.py`'s terms at this model's sizes (its layer kinds may hold
any number of leading dense layers), plus the residual path. Nothing here
touches a device or the program.

`published` is the configuration file's `published` group (the widths and
`hc_mult`), `model` its `model` group (the cut)."""

from __future__ import annotations

from benchmark.lib import arith_mla

# the maps' leaves are float32 whatever the weights' dtype
MAPS_ITEMSIZE = 4


def map_columns(published):
    """Columns of a sublayer's `phi`: H_pre, H_post and H_res row by row."""
    n = published["hc_mult"]
    return 2 * n + n * n


def sublayer_map_params(published):
    """One sublayer's residual maps: `phi`, the three `alpha`, the biases."""
    n, cols = published["hc_mult"], map_columns(published)
    return n * published["hidden_size"] * cols + 3 + cols


def sublayers(model):
    """A layer has two: around its attention and around its MLP."""
    return 2 * len(model["layer_kinds"])


def layer_params(published, model, kind):
    return arith_mla.layer_params(published, model, kind) \
        + 2 * sublayer_map_params(published)


def total_params(published, model):
    return arith_mla.total_params(published, model) \
        + sublayers(model) * sublayer_map_params(published)


def mix_flops_per_position(published):
    """One sublayer's residual path for one position: the maps' products
    `2 nC (2n + n^2)`, the read-out `sum_i H_pre[i] X_i` `2 nC`, and the mix
    `sum_j H_res[i, j] X_j + H_post[i] y` `2 n^2 C + 2 nC`. The Sinkhorn
    iterations (some `4 n^2` operations each on `n^2` numbers) are left
    out: a thousandth of the rest."""
    n, c = published["hc_mult"], published["hidden_size"]
    return 2 * n * c * map_columns(published) + 2 * n * c \
        + 2 * n * n * c + 2 * n * c


def flops_per_position(published, model, context, held_choices):
    """`arith_mla.flops_per_position` and the residual path of every
    sublayer."""
    return arith_mla.flops_per_position(published, model, context,
                                        held_choices) \
        + sublayers(model) * mix_flops_per_position(published)


def step_bytes(published, model, live_slots, context, touched_experts,
               w_itemsize, kv_itemsize):
    """`arith_mla.step_bytes` (the weights once without the embedding's
    unread rows and the held experts no choice fell on, the live latent rows
    once a layer, one row written a slot and layer) and every sublayer's
    float32 maps once. The streams are activations and not counted here:
    `mix_bytes` has them."""
    return arith_mla.step_bytes(published, model, live_slots, context,
                                touched_experts, w_itemsize, kv_itemsize) \
        + MAPS_ITEMSIZE * sublayers(model) * sublayer_map_params(published)


def mix_bytes(published, model, live_slots, itemsize):
    """Least HBM traffic of a launch's residual path: a sublayer, its maps'
    leaves once and the live slots' streams read once and written once."""
    n, c = published["hc_mult"], published["hidden_size"]
    return sublayers(model) * (
        MAPS_ITEMSIZE * sublayer_map_params(published)
        + 2 * live_slots * n * c * itemsize)
