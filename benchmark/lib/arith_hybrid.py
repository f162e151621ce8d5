"""The hybrid state-space serving cell's arithmetic: parameters, operations
and bytes of one token step from shapes, by the **least work** the
mathematics needs, so that a share of a peak computed from them cannot pass
100%. Nothing here touches a device or the program.

`published` is the configuration file's `published` group (the widths),
`model` its `model` group (the cut: which layers run, as `layer_kinds` of
`mamba`, `attention` and `sparse`, the experts and the rows of the vocabulary
held here)."""

from __future__ import annotations

STATE_ITEMSIZE = 4      # the Mamba-2 state is float32 whatever the weights


def _mamba_widths(p):
    """(inner, convolution channels, in-projection columns, state numbers a
    sequence) of one Mamba-2 layer."""
    inner = p["mamba_num_heads"] * p["mamba_head_dim"]
    width = inner + 2 * p["n_groups"] * p["ssm_state_size"]
    return (inner, width, inner + width + p["mamba_num_heads"],
            inner * p["ssm_state_size"])


def mamba_params(published):
    """One Mamba-2 layer: the two projections, the convolution's taps and
    bias, dt_bias, A_log and D a head, the gated norm's gain, the layer's
    norm."""
    p, d = published, published["hidden_size"]
    inner, width, cols, _ = _mamba_widths(p)
    return (d * cols + (p["conv_kernel"] + 1) * width
            + 3 * p["mamba_num_heads"] + inner + inner * d + d)


def attention_params(published):
    """One attention layer: q, k, v and output matrices, the layer's norm."""
    p, d = published, published["hidden_size"]
    q = p["num_attention_heads"] * p["head_dim"]
    kv = p["num_key_value_heads"] * p["head_dim"]
    return 2 * d * q + 2 * d * kv + d


def expert_params(published):
    """One routed expert: an ungated MLP in the latent space."""
    return 2 * published["moe_latent_size"] * published["moe_intermediate_size"]


def shared_params(published):
    return 2 * published["hidden_size"] * published["n_shared_experts"] \
        * published["moe_shared_expert_intermediate_size"]


def sparse_params(published, model):
    """One expert layer that runs here: the router with its bias, the two
    latent projections, the shared expert, the held experts, the norm."""
    p, d = published, published["hidden_size"]
    n_all = model["n_routed_experts_published"]
    return (d * n_all + n_all + 2 * d * p["moe_latent_size"]
            + shared_params(p) + model["experts_held"][1] * expert_params(p)
            + d)


def layer_params(published, model, kind):
    return {"mamba": mamba_params, "attention": attention_params}.get(
        kind, lambda p: sparse_params(p, model))(published)


def total_params(published, model):
    d = published["hidden_size"]
    return (sum(layer_params(published, model, k)
                for k in model["layer_kinds"])
            + 2 * model["vocab_size"] * d + d)


def count(model, kind):
    return sum(1 for k in model["layer_kinds"] if k == kind)


def kv_row_bytes(published, itemsize):
    """What a position leaves in the pages, an attention layer: K and V."""
    return 2 * published["num_key_value_heads"] * published["head_dim"] \
        * itemsize


def update_flops_per_position(published):
    """One Mamba-2 layer's state update and read-out for one sequence: a
    number of the state takes a multiplication by the decay, a
    multiply-and-add of dt x B, and a multiply-and-add into y: 5."""
    return 5 * _mamba_widths(published)[3]


def flops_per_position(published, model, context, held_choices):
    """Model FLOPs to advance one sequence by one position with `context`
    rows visible to an attention layer (its own included): every matrix
    once, the convolution, the state's update, the live rows' scores and
    values, the router, the latent projections and the shared expert of an
    expert layer, the head over the held rows of the vocabulary, and the
    experts' two products for `held_choices`, the choices a position that
    fell on held experts, all expert layers together, as the program counted
    them."""
    p, d = published, published["hidden_size"]
    inner, width, cols, _ = _mamba_widths(p)
    q = p["num_attention_heads"] * p["head_dim"]
    kv = p["num_key_value_heads"] * p["head_dim"]
    per = {
        "mamba": 2 * (d * cols + inner * d) + 2 * p["conv_kernel"] * width
        + update_flops_per_position(p),
        "attention": 2 * (2 * d * q + 2 * d * kv) + 4 * q * context,
        "sparse": 2 * d * model["n_routed_experts_published"]
        + 2 * 2 * d * p["moe_latent_size"] + 2 * shared_params(p)}
    return (sum(per[k] for k in model["layer_kinds"])
            + 2 * d * model["vocab_size"]
            + 2 * expert_params(p) * held_choices)


def slot_state_bytes(published, model, slots, tail_itemsize):
    """What `slots` slots hold by slot: the float32 states and the
    convolution tails of every Mamba-2 layer."""
    p = published
    _, width, _, state = _mamba_widths(p)
    return count(model, "mamba") * slots * (
        state * STATE_ITEMSIZE
        + (p["conv_kernel"] - 1) * width * tail_itemsize)


def update_bytes(published, model, live_slots):
    """Least traffic of the state updates of one token step: every live
    slot's state read once and written once, a Mamba-2 layer."""
    return 2 * count(model, "mamba") * live_slots \
        * _mamba_widths(published)[3] * STATE_ITEMSIZE


def step_bytes(published, model, live_slots, context, touched_experts,
               w_itemsize, kv_itemsize):
    """Least HBM traffic of one token step over `live_slots` slots at a mean
    of `context` rows: the weights once, but of the embedding only the rows
    the slots' tokens name and of the held experts only the
    `touched_experts` (all expert layers together) a choice fell on; the
    live slots' states and convolution tails read and written once a
    Mamba-2 layer; each live row of the pages once an attention layer and one
    row written a slot."""
    p, d = published, published["hidden_size"]
    weights = total_params(p, model) \
        - (model["vocab_size"] - live_slots) * d \
        - (count(model, "sparse") * model["experts_held"][1]
           - touched_experts) * expert_params(p)
    rows = count(model, "attention") * live_slots * (context + 1)
    return (w_itemsize * weights
            + 2 * slot_state_bytes(p, model, live_slots, kv_itemsize)
            + rows * kv_row_bytes(p, kv_itemsize))


def update_least_seconds(published, model, live_slots, peak):
    """Least time of a step's state updates: the larger of their FLOPs over
    the peak and the states' bytes over the HBM bandwidth (the bytes: 5
    FLOPs to 8 bytes)."""
    return max(
        count(model, "mamba") * live_slots
        * update_flops_per_position(published) / peak["bf16_flops_per_s"],
        update_bytes(published, model, live_slots) / peak["hbm_bytes_per_s"])


def expert_least_seconds(published, held_choices, touched_experts, peak,
                         w_itemsize):
    """Least time of a step's expert products: the larger of the held
    choices' FLOPs over the peak and the touched experts' weights over the
    HBM bandwidth."""
    return max(
        2 * expert_params(published) * held_choices
        / peak["bf16_flops_per_s"],
        w_itemsize * expert_params(published) * touched_experts
        / peak["hbm_bytes_per_s"])
