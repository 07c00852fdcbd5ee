"""Operations and bytes of ONE decode step of a looped stack, from the
published sizes: the yardstick of `decode_stream_roofline.math`, kept beside
roofline.py (whose `least_seconds` prices what this returns).

A looped model (`total_ut_steps` = T passes over the same `num_hidden_layers`
= L layers) streams its stack T times a step: the weights are read again in
every pass, because a pass's input is the pass before's output. Counted is
what the step cannot avoid; what a program moves besides (a re-laid weight, a
gathered table, scores written out) is its loss against this bound."""


def layer_parameters(sizes: dict) -> int:
    """Matrix parameters of one layer: q, k, v and o, and the gated feed-forward's three."""
    hidden, head = int(sizes["hidden_size"]), int(sizes["head_dim"])
    heads, kv_heads = int(sizes["num_attention_heads"]), int(sizes["num_key_value_heads"])
    return hidden * head * (2 * heads + 2 * kv_heads) + 3 * hidden * int(sizes["intermediate_size"])


def decode_step(resident: float, rows: int, sizes: dict, weight_bytes: int = 2, kv_bytes: int = 2,
                dtype_bytes: int = 2):
    """One step for `rows` rows with `resident` key positions in all (summed
    over the rows; each holds a K and a V in every one of the T x L planes).

    Bytes: T x (the L layers' matrices and their four norms, the final norm) +
    the gate + the head's matrix + each row's embedding row; every resident
    position's K and V read once in each (pass, layer) plane and each row's new
    K and V written there; the rows' state read and written a layer and the
    rows' logits written. Operations: 2 a matrix parameter a row a pass, the
    head's, and 2 x 2 x head_dim a query head a resident position a (pass,
    layer) for q k^T and p v."""
    passes, layers = int(sizes["total_ut_steps"]), int(sizes["num_hidden_layers"])
    hidden, vocab, head = int(sizes["hidden_size"]), int(sizes["vocab_size"]), int(sizes["head_dim"])
    heads, kv_heads = int(sizes["num_attention_heads"]), int(sizes["num_key_value_heads"])
    planes = passes * layers
    stack = layers * (layer_parameters(sizes) + 4 * hidden) + hidden  # what one pass streams
    weights = (passes * stack + hidden + 1 + hidden * vocab + rows * hidden) * weight_bytes
    kv = (resident + rows) * planes * 2 * kv_heads * head * kv_bytes
    activations = (planes * 2 * rows * hidden + rows * vocab) * dtype_bytes
    flops = 2.0 * rows * (passes * layers * layer_parameters(sizes) + hidden * vocab) \
        + 2.0 * (2 * head) * heads * resident * planes
    return flops, weights + kv + activations
