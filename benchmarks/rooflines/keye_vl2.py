"""The matrix products and attention calls of one training step of the
Keye-VL-2.0 language-model layers a configuration holds
(``reference/keye_vl2.py``): the four projections of grouped-query attention,
the indexer's three projections and its causal scores, the router, the held
experts' grouped products and the head.

The attention's required work is that of the selected pairs alone: query
``t`` attends to ``min(t + 1, K)`` keys, ``K (K + 1) / 2 + (S - K) K`` pairs a
head at ``S`` positions, exactly a ``K``-window's. It is given as the two
calls whose pairs add up to that, as ``rooflines/smallthinker.py::window_calls``
gives a band: a causal ``i = j = K`` (the first ``K`` queries) and a full
``i = S - K``, ``j = K`` (every later query, ``K`` keys each). The indexer
scores every causal pair at ``H`` heads of ``e``: ``2 H e`` operations a pair
forward, and its backward the two products of the same size (the scores'
gradient into ``q_I`` and into ``k_I``), three times the forward as a matrix
product's; it is given as the matrix product of that many operations
(:func:`indexer_calls` has it as a call for a reader)."""
from __future__ import annotations

from . import grouped


def expert_layers(config: dict) -> int:
    return config["num_layers"]


def expected_rows(config: dict, tokens: int) -> float:
    """Token-expert pairs a held expert layer computes when the router spreads
    its choices evenly: ``tokens x experts a token x held / router width``."""
    return tokens * config["num_experts_per_tok"] * config["num_experts"] / config["router_width"]


def _call(config: dict, batch: int, i: int, j: int, causal: bool) -> dict:
    d = config["head_dim"]
    return dict(b=batch, h=config["num_attention_heads"], i=i, j=j, dk=d, dv=d, causal=causal)


def sparse_calls(config: dict, batch: int, seq_len: int) -> list:
    """The sparse layers' attention calls of a step, each as the two calls
    whose pairs are its selection's (one where a row holds no more than
    ``K`` positions)."""
    k = min(config["sa_config"]["topk"], seq_len)
    calls = []
    for _ in range(config["num_layers"]):
        calls.append(_call(config, batch, k, k, True))
        if seq_len > k:
            calls.append(_call(config, batch, seq_len - k, k, False))
    return calls


def indexer_calls(config: dict, batch: int, seq_len: int) -> list:
    """The indexer's scores of a step as attention calls: every causal pair at
    ``H`` heads of ``e`` channels, scores alone (no values)."""
    sa = config["sa_config"]
    return [dict(b=batch, h=sa["indexer_num_heads"], i=seq_len, j=seq_len, dk=sa["indexer_head_dim"],
                 dv=0, causal=True) for _ in range(config["num_layers"])]


def train_step_work(config: dict, batch: int, seq_len: int) -> dict:
    """One step at ``batch`` rows of ``seq_len`` tokens. The experts' products
    are counted at the expected rows (:func:`expected_rows`), as in
    ``rooflines/lfm2_moe.py``; the indexer's scores as the matrix product
    ``(b H S, e, (S + 1) / 2)``, whose operations are the causal pairs'."""
    c, d, tokens = config["hidden_size"], config["head_dim"], batch * seq_len
    q, kv = config["num_attention_heads"] * d, config["num_key_value_heads"] * d
    sa = config["sa_config"]
    hi, e = sa["indexer_num_heads"], sa["indexer_head_dim"]
    matmuls = []
    for _ in range(config["num_layers"]):
        matmuls += [(tokens, c, q), (tokens, c, kv), (tokens, c, kv), (tokens, q, c),
                    (tokens, c, hi * e), (tokens, c, e), (tokens, c, hi),
                    (tokens, c, config["router_width"])]
        matmuls.append((batch * hi * seq_len, e, (seq_len + 1) / 2))
        matmuls += grouped.expert_products(config, expected_rows(config, tokens))
    matmuls.append((tokens, c, config["vocab_size"]))
    return {"matmuls": matmuls, "attentions": sparse_calls(config, batch, seq_len)}
