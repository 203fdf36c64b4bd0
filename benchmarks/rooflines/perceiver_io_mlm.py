"""The matrix products and attention calls of one Perceiver IO MLM step."""
from __future__ import annotations


def train_step_work(config: dict, batch: int, seq_len: int) -> dict:
    d, dl, k = config["d_model"], config["d_latents"], config["num_latents"]
    qk, vc, vocab = config["qk_channels"], config["v_channels"], config["vocab_size"]
    hc, hs = config["num_cross_attention_heads"], config["num_self_attention_heads"]
    wc, ws = config["cross_attention_widening_factor"], config["self_attention_widening_factor"]
    n_in, n_lat = batch * seq_len, batch * k
    n_out = batch * config["max_position_embeddings"]  # every output query is decoded
    matmuls = [  # encoder cross-attention and its MLP
        (n_lat, dl, qk), (n_in, d, qk), (n_in, d, vc), (n_lat, vc, dl),
        (n_lat, dl, wc * dl), (n_lat, wc * dl, dl),
    ]
    attentions = [dict(b=batch, h=hc, i=k, j=seq_len, dk=qk // hc, dv=vc // hc, causal=False)]
    for _ in range(config["num_self_attends_per_block"] * config["num_blocks"]):
        matmuls += [(n_lat, dl, qk), (n_lat, dl, qk), (n_lat, dl, vc), (n_lat, vc, dl),
                    (n_lat, dl, ws * dl), (n_lat, ws * dl, dl)]
        attentions.append(dict(b=batch, h=hs, i=k, j=k, dk=qk // hs, dv=vc // hs, causal=False))
    matmuls += [  # decoder cross-attention (v channels d_model), its MLP, the tied head
        (n_out, d, qk), (n_lat, dl, qk), (n_lat, dl, d), (n_out, d, d),
        (n_out, d, wc * d), (n_out, wc * d, d), (n_out, d, vocab),
    ]
    attentions.append(dict(b=batch, h=hc, i=n_out // batch, j=k, dk=qk // hc, dv=d // hc, causal=False))
    return {"matmuls": matmuls, "attentions": attentions}
