"""Model FLOPs of a decoder-only transformer, from its config's shapes.

Counts the operations training needs, not what the program happens to
execute:

* forward per token: the q/k/v/o projections, the gated MLP (three
  matrices), attention scores and the weighted sum of values, and the
  output head (tied or not, it is a d_model x vocab matrix product);
* attention is counted **causally**: query position i attends to i + 1
  keys, so a sequence of S tokens costs 2·H·hd·S(S+1)/2 multiply-adds for
  QK^T and as many for PV; the masked upper triangle that the program
  computes is not counted;
* backward = 2 x forward; ``remat`` recomputation is not counted;
* the embedding lookup, norms, rope, softmax and the optimizer are
  left out (elementwise, under 1% at these widths).
"""
from __future__ import annotations


def forward_flops_per_token(cfg: dict, seq_len: int) -> float:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    f = cfg["intermediate_size"]
    layers = cfg["num_hidden_layers"]
    vocab = cfg["vocab_size"]
    proj = 2 * d * h * hd + 2 * 2 * d * kv * hd + 2 * h * hd * d
    mlp = 3 * 2 * d * f
    attn = 2 * 2 * h * hd * (seq_len + 1) / 2
    head = 2 * d * vocab
    return layers * (proj + mlp + attn) + head


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    return 3.0 * forward_flops_per_token(cfg, seq_len)


def train_flops_per_block(cfg: dict, traffic: dict) -> float:
    tokens = traffic["block_microsteps"] * traffic["batch"] * traffic["seq_len"]
    return train_flops_per_token(cfg, traffic["seq_len"]) * tokens
