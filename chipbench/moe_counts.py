"""The least bytes and the operations of a serving wave of an MLA + MoE
model's one-chip share (``chipbench/references/lm_mla_moe.py``), from its
shapes and the wave's expert counters.

A wave runs micro-steps, one token a slot each.  The least bytes of a
micro-step in which some slot is fed are what any implementation of it
has to move: every weight outside the routed experts once (attention, the
dense layers, routers, shared experts, the output head, norms), the
embedding rows of the tokens fed, the weights of each held expert that a
fed token picked in each layer (the program's ``moe_experts_touched``
counter), and the latent rows each fed token attends over, its own (new)
row included.  The operations are the matmuls each fed token multiplies
by, its held routed experts (the ``moe_held_assignments`` counter), and
attention's scores and weighted values at the published head dims over
each token's live context.
"""
from __future__ import annotations


def _item(cfg: dict) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[cfg["dtype"]]


def _attn_params(cfg: dict) -> int:
    """Matmul weights of one MLA layer."""
    d, h, hd = cfg["d_model"], cfg["num_heads"], cfg["head_dim"]
    r, rd = cfg["kv_lora_rank"], cfg["rope_head_dim"]
    return d * h * (hd + rd) + d * r + 2 * r * h * hd + d * rd + h * hd * d


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * cfg["d_model"] * cfg["moe_d_ff"]


def token_matmul_params(cfg: dict) -> int:
    """Weights every token multiplies by, routed experts aside: attention
    of every layer, the dense MLPs, routers and shared experts of the MoE
    layers, and the output head."""
    d, layers, lead = cfg["d_model"], cfg["num_layers"], cfg["first_k_dense"]
    moe = layers - lead
    return (layers * _attn_params(cfg) + lead * 3 * d * cfg["d_ff"]
            + moe * (d * cfg["num_experts"]
                     + cfg["num_shared_experts"] * expert_params(cfg))
            + cfg["vocab_size"] * d)


def step_weight_bytes(cfg: dict) -> int:
    """Bytes of the weights a fed micro-step reads whole: every weight
    outside the routed experts and the embedding (the router in float32,
    as the program holds it), norms included."""
    d, layers, lead = cfg["d_model"], cfg["num_layers"], cfg["first_k_dense"]
    router = (layers - lead) * d * cfg["num_experts"]
    norms = (2 * layers + 1) * d + layers * cfg["kv_lora_rank"]
    return _item(cfg) * (token_matmul_params(cfg) - router + norms) + \
        4 * router


def latent_row_bytes(cfg: dict) -> int:
    """One position's cache row in every layer: latent plus shared k_pe."""
    return cfg["num_layers"] * (cfg["kv_lora_rank"] + cfg["rope_head_dim"]) \
        * _item(cfg)


def wave_least_bytes(cfg: dict, steps: int, tokens: int, touched: int,
                     context_sum: int) -> int:
    """A wave of ``steps`` micro-steps in which some slot is fed, feeding
    ``tokens`` tokens whose contexts (each its own position included) add
    up to ``context_sum``, with ``touched`` (layer, micro-step, held
    expert) triples that got a token."""
    return (steps * step_weight_bytes(cfg)
            + tokens * cfg["d_model"] * _item(cfg)
            + touched * expert_params(cfg) * _item(cfg)
            + context_sum * latent_row_bytes(cfg))


def wave_flops(cfg: dict, tokens: int, assignments: int,
               context_sum: int) -> float:
    """FLOPs of ``tokens`` tokens with ``assignments`` picks of held
    experts between them: the matmuls, plus scores (query-key head dim
    ``head_dim + rope_head_dim``) and weighted values (``head_dim``) over
    the context in every layer."""
    h, hd, rd = cfg["num_heads"], cfg["head_dim"], cfg["rope_head_dim"]
    return 2.0 * (token_matmul_params(cfg) * tokens
                  + expert_params(cfg) * assignments) + \
        2.0 * cfg["num_layers"] * h * (2 * hd + rd) * context_sum
