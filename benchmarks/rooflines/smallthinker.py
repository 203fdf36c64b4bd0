"""The matrix products and attention calls of one training step of the
SmallThinker layers a configuration holds (``reference/smallthinker.py``):
the four projections of grouped-query attention, the router, the held
experts' grouped products and the head.

``rooflines/work.py`` knows a causal call and a full one. A window call's
query-key pairs are the band's: query ``t`` sees ``min(t + 1, W)`` keys, ``W
(W + 1) / 2 + (S - W) W`` pairs a head at ``S`` positions. It is given as the
two calls whose pairs add up to exactly that: a causal ``i = j = W`` (the
first ``W`` queries, a triangle) and a full ``i = S - W``, ``j = W`` (every
later query, ``W`` keys each). The operations are then exact; the bytes are
those of two smaller calls (``W`` more key and value rows than the one call
moves), and these shapes are bound by operations, not bytes."""
from __future__ import annotations

from . import grouped


def expert_layers(config: dict) -> int:
    return config["num_layers"]


def expected_rows(config: dict, tokens: int) -> float:
    """Token-expert pairs a held expert layer computes when the router spreads
    its choices evenly: ``tokens x experts a token x held / router width``."""
    return (tokens * config["moe_num_active_primary_experts"] * config["moe_num_primary_experts"]
            / config["router_width"])


def _held(config: dict) -> range:
    first = config.get("first_layer", 0)
    return range(first, first + config["num_layers"])


def _call(config: dict, batch: int, i: int, j: int, causal: bool) -> dict:
    d = config["head_dim"]
    return dict(b=batch, h=config["num_attention_heads"], i=i, j=j, dk=d, dv=d, causal=causal)


def window_calls(config: dict, batch: int, seq_len: int) -> list:
    """The window layers' attention calls of a step, each as the two calls
    whose pairs are its band's (one where the window holds the whole row)."""
    w = min(config["sliding_window_size"], seq_len)
    calls = []
    for l in _held(config):
        if config["sliding_window_layout"][l]:
            calls.append(_call(config, batch, w, w, True))
            if seq_len > w:
                calls.append(_call(config, batch, seq_len - w, w, False))
    return calls


def global_calls(config: dict, batch: int, seq_len: int) -> list:
    return [_call(config, batch, seq_len, seq_len, True)
            for l in _held(config) if not config["sliding_window_layout"][l]]


def train_step_work(config: dict, batch: int, seq_len: int) -> dict:
    """One step at ``batch`` rows of ``seq_len`` tokens. The experts' products
    are counted at the expected rows (:func:`expected_rows`), as in
    ``rooflines/lfm2_moe.py``."""
    c, d, tokens = config["hidden_size"], config["head_dim"], batch * seq_len
    q, kv = config["num_attention_heads"] * d, config["num_key_value_heads"] * d
    matmuls = []
    for _ in _held(config):
        matmuls += [(tokens, c, q), (tokens, c, kv), (tokens, c, kv), (tokens, q, c),
                    (tokens, c, config["router_width"])]
        matmuls += grouped.expert_products(config, expected_rows(config, tokens))
    matmuls.append((tokens, c, config["vocab_size"]))
    attentions = global_calls(config, batch, seq_len) + window_calls(config, batch, seq_len)
    return {"matmuls": matmuls, "attentions": attentions}
