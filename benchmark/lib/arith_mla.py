"""The latent-attention serving cell's arithmetic: parameters, operations and
bytes of one token step from shapes, by the **least work** the mathematics
needs, so that a share of a peak computed from them cannot pass 100%. Nothing
here touches a device or the program.

`published` is the configuration file's `published` group (the widths),
`model` its `model` group (the cut: which layers run, the experts and the
rows of the vocabulary held here)."""

from __future__ import annotations


def attention_params(published):
    """One layer's latent attention: the two query matrices, the latent and
    shared-key matrix, the up-projection, the output matrix, the two inner
    norms and the layer's two outer norms."""
    p = published
    d, h = p["hidden_size"], p["num_attention_heads"]
    qk = p["qk_nope_head_dim"] + p["qk_rope_head_dim"]
    return (d * p["q_lora_rank"] + p["q_lora_rank"] * h * qk
            + d * (p["kv_lora_rank"] + p["qk_rope_head_dim"])
            + p["kv_lora_rank"] * h * (p["qk_nope_head_dim"]
                                       + p["v_head_dim"])
            + h * p["v_head_dim"] * d
            + p["q_lora_rank"] + p["kv_lora_rank"] + 2 * d)


def expert_params(published):
    """One routed expert's gated MLP."""
    return 3 * published["hidden_size"] * published["moe_intermediate_size"]


def layer_params(published, model, kind):
    """One layer that runs here; a sparse layer with its held experts, its
    router with the bias and the shared expert."""
    p, d = published, published["hidden_size"]
    if kind == "dense":
        return attention_params(p) + 3 * d * p["intermediate_size"]
    n_all = model["n_routed_experts_published"]
    return (attention_params(p) + d * n_all + n_all
            + (p["n_shared_experts"] + model["experts_held"][1])
            * expert_params(p))


def total_params(published, model):
    d = published["hidden_size"]
    return (sum(layer_params(published, model, k)
                for k in model["layer_kinds"])
            + 2 * model["vocab_size"] * d + d)


def latent_row_bytes(published, itemsize):
    """What a position leaves in the pool, a layer."""
    return (published["kv_lora_rank"]
            + published["qk_rope_head_dim"]) * itemsize


def attend_flops_per_row(published):
    """One cached row against one slot's query, all heads: the score over the
    latent and the rotary key, and the weighted latent."""
    p = published
    return 2 * p["num_attention_heads"] * (
        2 * p["kv_lora_rank"] + p["qk_rope_head_dim"])


def flops_per_position(published, model, context, held_choices):
    """Model FLOPs to advance one sequence by one position with `context`
    rows visible a layer (its own included), absorbed: every matrix once,
    the query's and the output's halves of the up-projection a head, the
    live rows, the router and the shared expert of a sparse layer, the head
    over the held rows of the vocabulary, and the experts' three products for
    `held_choices`, the choices a position that fell on held experts, all
    sparse layers together, as the program counted them."""
    p, d, h = published, published["hidden_size"], \
        published["num_attention_heads"]
    qk = p["qk_nope_head_dim"] + p["qk_rope_head_dim"]
    attn = 2 * (d * p["q_lora_rank"] + p["q_lora_rank"] * h * qk
                + d * (p["kv_lora_rank"] + p["qk_rope_head_dim"])
                + h * p["qk_nope_head_dim"] * p["kv_lora_rank"]
                + h * p["kv_lora_rank"] * p["v_head_dim"]
                + h * p["v_head_dim"] * d) \
        + attend_flops_per_row(p) * context
    total = 0
    for kind in model["layer_kinds"]:
        total += attn
        if kind == "dense":
            total += 2 * 3 * d * p["intermediate_size"]
        else:
            total += 2 * d * model["n_routed_experts_published"] \
                + 2 * p["n_shared_experts"] * expert_params(p)
    return total + 2 * d * model["vocab_size"] \
        + 2 * expert_params(p) * held_choices


def step_bytes(published, model, live_slots, context, touched_experts,
               w_itemsize, kv_itemsize):
    """Least HBM traffic of one token step over `live_slots` slots at a mean
    of `context` rows: the weights once, but of the embedding only the rows
    the slots' tokens name and of the held experts only the
    `touched_experts` (all sparse layers together) a choice fell on; each
    live row of the pool once a layer; one row written a slot and layer."""
    p, d = published, published["hidden_size"]
    sparse = sum(1 for k in model["layer_kinds"] if k == "sparse")
    weights = total_params(p, model) \
        - (model["vocab_size"] - live_slots) * d \
        - (sparse * model["experts_held"][1] - touched_experts) \
        * expert_params(p)
    rows = len(model["layer_kinds"]) * live_slots * (context + 1)
    return w_itemsize * weights + rows * latent_row_bytes(p, kv_itemsize)


def expert_least_seconds(published, held_choices, touched_experts, peak,
                         w_itemsize):
    """Least time of a step's grouped products: the larger of the held
    choices' FLOPs over the peak and the touched experts' weights over the
    HBM bandwidth."""
    return max(
        2 * expert_params(published) * held_choices
        / peak["bf16_flops_per_s"],
        w_itemsize * expert_params(published) * touched_experts
        / peak["hbm_bytes_per_s"])
