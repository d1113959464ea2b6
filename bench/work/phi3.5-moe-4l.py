"""FLOPs the model requires per token, from the configuration's sizes.

Two FLOPs per multiply-add of every weight a token meets: the attention
projections, the router, its top-k experts (gate, up and down) in each
layer, and the LM head; plus attention over its context (scores and the
weighted values).  Not what the program computes: dropped capacity,
padding and dense dispatch do not count.
"""


def active_params(cfg: dict) -> int:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    attn = d * (h + 2 * kv) * hd + h * hd * d
    experts = cfg["num_experts_per_tok"] * 3 * d * cfg["intermediate_size"]
    router = d * cfg["num_local_experts"]
    return cfg["num_hidden_layers"] * (attn + experts + router) + cfg["vocab_size"] * d


def flops_per_token(cfg: dict, context: int) -> int:
    """FLOPs for one token that attends to ``context`` positions (itself included)."""
    attn = cfg["num_hidden_layers"] * 4 * cfg["num_attention_heads"] * cfg["head_dim"] * context
    return 2 * active_params(cfg) + attn
