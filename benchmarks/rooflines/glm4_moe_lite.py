"""The matrix products and attention calls of one training step of the
GLM-4.7-Flash layers a configuration holds (``reference/glm4_moe_lite.py``):
latent attention's five products a layer, the dense SwiGLU, the router, the
held routed experts' grouped products, the shared expert, both heads and the
prediction module's projection and layer."""
from __future__ import annotations

from . import grouped


def _dense_layers(config: dict) -> int:
    first = config.get("first_layer", 0)
    return max(0, min(config["num_layers"], config["first_k_dense_replace"] - first))


def expert_layers(config: dict) -> int:
    """Expert layers a step runs: the held ones and the prediction module's."""
    return config["num_layers"] - _dense_layers(config) + config["num_nextn_predict_layers"]


def expected_rows(config: dict, tokens: int) -> float:
    """Token-expert pairs a held expert layer computes when the router spreads
    its choices evenly: ``tokens x experts a token x held / router width``."""
    return tokens * config["num_experts_per_tok"] * config["n_routed_experts"] / config["router_width"]


def _latent_attention(config: dict, batch: int, seq_len: int) -> tuple:
    c, h, tokens = config["hidden_size"], config["num_attention_heads"], batch * seq_len
    rq, rkv = config["q_lora_rank"], config["kv_lora_rank"]
    dn, dr, dv = config["qk_nope_head_dim"], config["qk_rope_head_dim"], config["v_head_dim"]
    matmuls = [(tokens, c, rq), (tokens, rq, h * (dn + dr)), (tokens, c, rkv + dr),
               (tokens, rkv, h * (dn + dv)), (tokens, h * dv, c)]
    return matmuls, dict(b=batch, h=h, i=seq_len, j=seq_len, dk=dn + dr, dv=dv, causal=True)


def _expert_ffn(config: dict, tokens: int) -> list:
    c, shared = config["hidden_size"], config["n_shared_experts"] * config["moe_intermediate_size"]
    return ([(tokens, c, config["router_width"])]
            + grouped.expert_products(config, expected_rows(config, tokens))
            + [(tokens, c, shared), (tokens, c, shared), (tokens, shared, c)])


def train_step_work(config: dict, batch: int, seq_len: int) -> dict:
    """One step at ``batch`` rows of ``seq_len`` tokens. The routed experts'
    products are counted at the expected rows (:func:`expected_rows`), as in
    ``rooflines/lfm2_moe.py``; the shared expert takes every token."""
    c, tokens = config["hidden_size"], batch * seq_len
    matmuls, attentions = [], []
    for i in range(config["num_layers"]):
        products, call = _latent_attention(config, batch, seq_len)
        matmuls += products
        attentions.append(call)
        if i < _dense_layers(config):
            m = config["intermediate_size"]
            matmuls += [(tokens, c, m), (tokens, c, m), (tokens, m, c)]
        else:
            matmuls += _expert_ffn(config, tokens)
    matmuls.append((tokens, c, config["vocab_size"]))
    if config["num_nextn_predict_layers"]:
        products, call = _latent_attention(config, batch, seq_len)
        matmuls += [(tokens, 2 * c, c)] + products + _expert_ffn(config, tokens)
        matmuls.append((tokens, c, config["vocab_size"]))
        attentions.append(call)
    return {"matmuls": matmuls, "attentions": attentions}
