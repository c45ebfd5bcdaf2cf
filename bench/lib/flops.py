"""Model FLOPs of a served job, from its shapes and the published sizes.

Counts what the model needs and nothing the implementation adds: every
linear layer (2 FLOPs per multiply-add), causal attention (the score and
value products over the positions each query may see) and the output
head at each position whose next token is served.  The embedding lookup
and the elementwise work are not counted.
"""

from __future__ import annotations


def _sizes(model: dict):
    d = model["hidden_size"]
    heads = model["num_attention_heads"]
    hd = model.get("head_dim", d // heads)
    kv = model["num_key_value_heads"]
    per_layer = d * heads * hd + 2 * d * kv * hd + heads * hd * d \
        + 3 * d * model["intermediate_size"]
    return model["num_hidden_layers"], per_layer, heads * hd, \
        d * model["vocab_size"]


def job_flops(model: dict, batch: int, prompt: int, gen: int) -> float:
    """FLOPs of prefilling ``prompt`` tokens and serving ``gen`` tokens
    greedily, for each of ``batch`` rows (the first served token comes
    from the prefill, each later one from a decode step)."""
    layers, linear, qk, head = _sizes(model)
    # prefill: every position through every layer; query i sees i + 1 keys
    seen = prompt * (prompt + 1) // 2
    flops = 2 * layers * linear * prompt + 4 * layers * qk * seen + 2 * head
    # decode: the step at position p sees p + 1 keys
    for p in range(prompt, prompt + gen - 1):
        flops += 2 * layers * linear + 4 * layers * qk * (p + 1) + 2 * head
    return float(batch * flops)
