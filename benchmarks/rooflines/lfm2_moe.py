"""The matrix products and attention calls of one training step of the LFM2
mixture-of-experts layers a configuration holds."""
from __future__ import annotations

from . import grouped


def expert_layers(config: dict) -> int:
    return config["num_layers"] - config["num_dense_layers"]


def expected_rows(config: dict, tokens: int) -> float:
    """Token-expert pairs a held expert layer computes when the router spreads
    its choices evenly: ``tokens x experts a token x held / router width``."""
    return tokens * config["num_experts_per_tok"] * config["num_experts"] / config["router_width"]


def train_step_work(config: dict, batch: int, seq_len: int) -> dict:
    """One step at ``batch`` rows of ``seq_len`` tokens. The experts' products
    are counted at the expected rows (:func:`expected_rows`): what a step
    really routes to the held experts moves with the weights and the batch,
    and ``expert_matmul_roofline`` counts that from the program's own gauge."""
    c, heads, kv = config["hidden_size"], config["num_attention_heads"], config["num_key_value_heads"]
    d, tokens = c // heads, batch * seq_len
    first = config.get("first_layer", 0)
    kinds = config["layer_types"][first:first + config["num_layers"]]
    matmuls, attentions = [], []
    for i, kind in enumerate(kinds):
        if kind == "conv":
            matmuls += [(tokens, c, 3 * c), (tokens, c, c)]
        else:
            matmuls += [(tokens, c, c), (tokens, c, kv * d), (tokens, c, kv * d), (tokens, c, c)]
            attentions.append(dict(b=batch, h=heads, i=seq_len, j=seq_len, dk=d, dv=d, causal=True))
        if i < config["num_dense_layers"]:
            m = config["intermediate_size"]
            matmuls += [(tokens, c, m), (tokens, c, m), (tokens, m, c)]
        else:
            matmuls.append((tokens, c, config["router_width"]))
            matmuls += grouped.expert_products(config, expected_rows(config, tokens))
    matmuls.append((tokens, c, config["vocab_size"]))
    return {"matmuls": matmuls, "attentions": attentions}
