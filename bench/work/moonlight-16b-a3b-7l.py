"""FLOPs the model requires per token, and the least bytes a decode call
moves, from the configuration's sizes.

FLOPs: two per multiply-add of every weight a token meets: latent
attention's projections (W_Q, W_DKV with W_KR, W_UK with W_UV, W_O), the
dense SwiGLU of the leading layers, in each MoE layer the router, its
top-k experts and the shared experts, and the LM head; plus attention
over its context, scores over ``qk_nope_head_dim + qk_rope_head_dim`` and
values over ``v_head_dim`` a head.  Not what the program computes:
the absorbed decode's different order, dense dispatch and capacity
padding do not count.

Least bytes of decode calls (bfloat16): each call reads every weight but
the routed experts and the embedding table, of which it reads a row a
slot; the calls read ``experts_reached`` experts (gate, up and down; the
distinct experts a call's live slots route to, summed over calls and
layers) and ``latent_positions`` rows of the latent cache that live slots
attend, ``kv_lora_rank + qk_rope_head_dim`` values each.  A live slot
carries a real token; the idle slots of a prompt-token or lockstep call
carry token 0 and need nothing.
"""

BYTES = 2  # bfloat16


def attn_params(cfg: dict) -> int:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    r, nope, rope, v = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    return d * h * (nope + rope) + d * (r + rope) + r * h * (nope + v) + h * v * d


def _expert(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _layers(cfg: dict):
    dense = cfg["first_k_dense_replace"]
    return dense, cfg["num_hidden_layers"] - dense


def active_params(cfg: dict) -> int:
    d = cfg["hidden_size"]
    dense, moe = _layers(cfg)
    shared = cfg["n_shared_experts"] * _expert(cfg)
    per_moe = d * cfg["n_routed_experts"] + cfg["num_experts_per_tok"] * _expert(cfg) + shared
    return ((dense + moe) * attn_params(cfg) + dense * 3 * d * cfg["intermediate_size"]
            + moe * per_moe + cfg["vocab_size"] * d)


def flops_per_token(cfg: dict, context: int) -> int:
    """FLOPs for one token that attends to ``context`` positions (itself included)."""
    per_head = 2 * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) + 2 * cfg["v_head_dim"]
    attn = cfg["num_hidden_layers"] * cfg["num_attention_heads"] * per_head * context
    return 2 * active_params(cfg) + attn


def fixed_weights(cfg: dict) -> int:
    """Weights every decode call reads whatever the routing: all but the
    routed experts and the embedding table (norm gains and the correction
    bias included)."""
    d = cfg["hidden_size"]
    dense, moe = _layers(cfg)
    layers = dense + moe
    norms = layers * (2 * d + cfg["kv_lora_rank"]) + d
    shared = cfg["n_shared_experts"] * _expert(cfg)
    router = d * cfg["n_routed_experts"] + cfg["n_routed_experts"]
    return (layers * attn_params(cfg) + dense * 3 * d * cfg["intermediate_size"]
            + moe * (router + shared) + norms + d * cfg["vocab_size"])


def least_bytes(cfg: dict, calls: int, experts_reached: int, latent_positions: int) -> int:
    """The least bytes ``calls`` decode calls move, given their summed counts."""
    row = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return BYTES * (calls * fixed_weights(cfg) + experts_reached * _expert(cfg) + latent_positions * row)
