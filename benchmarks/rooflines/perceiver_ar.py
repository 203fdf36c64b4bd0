"""The matrix products and attention calls of one Perceiver AR step."""
from __future__ import annotations


def train_step_work(config: dict, batch: int, seq_len: int) -> dict:
    """One training step at ``batch`` rows of ``seq_len`` tokens, with the
    prefix positions cross-attention dropout keeps and no others."""
    c, heads, v = config["num_channels"], config["num_heads"], config["vocab_size"]
    lat = config["max_latents"]
    prefix = seq_len - lat
    kept = prefix - int(prefix * config["cross_attention_dropout"])
    rows, d = batch * lat, c // heads
    wc, ws = config["cross_attention_widening_factor"], config["self_attention_widening_factor"]
    matmuls = [(rows, c, c), (batch * (kept + lat), c, c), (batch * (kept + lat), c, c),
               (rows, c, c), (rows, c, wc * c), (rows, wc * c, c)]
    attentions = [dict(b=batch, h=heads, i=lat, j=kept + lat, dk=d, dv=d, causal=True)]
    for _ in range(config["num_self_attention_layers"]):
        matmuls += [(rows, c, c)] * 4 + [(rows, c, ws * c), (rows, ws * c, c)]
        attentions.append(dict(b=batch, h=heads, i=lat, j=lat, dk=d, dv=d, causal=True))
    matmuls.append((rows, c, v))
    return {"matmuls": matmuls, "attentions": attentions}
