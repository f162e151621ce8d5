"""The causal-LM cells' arithmetic: operations from shapes, by the **least
work** the mathematics needs, so that a share of a peak computed from them
cannot pass 100%. Nothing here touches a device or the program.

`model` is the configuration file's `model` section beside its `published`
keys: widths from `published`, the cut (layers, experts held, vocabulary
held) from `model`."""

from __future__ import annotations


def layer_specs(published, model):
    """[(attention kind, query heads, mlp kind)] of the layers that run."""
    n = model["num_layers"]
    return list(zip(published["layer_types"][:n],
                    published["num_attention_heads_per_layer"][:n],
                    published["mlp_layer_types"][:n]))


def attended_pairs(seq, window=None):
    """(query, key) pairs one head has to score in one row: j <= i, and on a
    sliding layer i - j < window."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def attention_flops_per_step(published, model, rows, seq):
    """Score and value products of every layer, forward and backward (the
    backward pass has four such products where the forward has two: 3x):
    2 products x 2 x head_dim a pair and head, over the pairs the mask
    leaves. A kernel that masks after the product does more; that is not
    in the count."""
    hd, total = published["head_dim"], 0
    for kind, heads, _ in layer_specs(published, model):
        window = (published["sliding_window"]
                  if kind == "sliding_attention" else None)
        total += rows * heads * attended_pairs(seq, window) * 4 * hd
    return 3 * total


def expert_flops_per_choice(published):
    """One (token, expert) choice through the expert's gated MLP, forward:
    three products of 2 x hidden x expert width."""
    return 3 * 2 * published["hidden_size"] * published["moe_intermediate_size"]


def expert_matmul_flops_per_step(published, held_choices):
    """The grouped products of a step, forward and backward (3x), for the
    counted choices that fell on held experts, summed over the layers."""
    return 3 * held_choices * expert_flops_per_choice(published)


def train_flops_per_step(published, model, rows, seq, held_choices=None):
    """Model FLOPs of one fwd+bwd step (bwd = 2x fwd, recomputation not
    counted) by the least work: causal pairs only, in-window pairs only on
    sliding layers, and the experts' products for `held_choices`, the
    choices a step that the router sent to held experts, all sparse layers
    together, as the program counted them. Without a count it is the
    expectation, `top_k * held / all` held experts a token and sparse
    layer (1 for 32 of 256 at top-8): what a prediction is made from."""
    d, hd = published["hidden_size"], published["head_dim"]
    kv = published["num_key_value_heads"]
    tokens, per_token = rows * seq, 0
    sparse = sum(1 for *_, mlp in layer_specs(published, model)
                 if mlp == "sparse")
    if held_choices is None:
        held_choices = tokens * sparse * published["num_experts_per_tok"] \
            * model["num_experts"] / model["num_experts_published"]
    for _, heads, mlp in layer_specs(published, model):
        per_token += 2 * d * (heads * hd + 2 * kv * hd + heads) \
            + 2 * heads * hd * d
        if mlp == "dense":
            per_token += 3 * 2 * d * published["intermediate_size"]
        else:
            per_token += 2 * d * model["num_experts_published"]
            per_token += 3 * 2 * d * published[
                "shared_expert_intermediate_size"]
    per_token += 2 * d * model["vocab_size"]
    return 3 * tokens * per_token \
        + expert_matmul_flops_per_step(published, held_choices) \
        + attention_flops_per_step(published, model, rows, seq)
