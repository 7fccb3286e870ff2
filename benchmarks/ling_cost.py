"""Bytes the two kernels of a Ling decode step must move, computed from a
configuration file's sizes and nothing else: the numerators of
`expert_ffn_roofline_share.serve` and `kda_update_roofline_share.serve`.
What an implementation adds (the gather into the grouped layout, padding
rows, a second read of a weight block) does not count.

The configuration states what is run: `layers_held` (published indices;
their kinds follow from `layer_group_size` and `first_k_dense_replace`),
`num_experts` held of `num_experts_published` routed over,
`num_experts_per_tok`, the widths, and `torch_dtype`.
"""
from __future__ import annotations

_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def layers_held(cfg: dict) -> list:
    return cfg.get("layers_held") or list(range(cfg["num_hidden_layers"]))


def expert_layers(cfg: dict) -> int:
    return sum(1 for l in layers_held(cfg) if l >= cfg["first_k_dense_replace"])


def kda_layers(cfg: dict) -> int:
    return sum(1 for l in layers_held(cfg)
               if (l + 1) % cfg["layer_group_size"])


def experts_reached(cfg: dict, rows: float) -> float:
    """The held experts that `rows` rows reach in one layer, in
    expectation under a router that picks every expert equally often (the
    runner balances the seed's router bias so: `ling_serve.
    balance_router_bias`): held x (1 - (1 - top_k / routed over)^rows)."""
    total = cfg.get("num_experts_published", cfg["num_experts"])
    miss = 1.0 - cfg["num_experts_per_tok"] / total
    return cfg["num_experts"] * (1.0 - miss ** rows)


def expert_step_bytes(cfg: dict, rows: float) -> float:
    """Bytes one decode step whose `rows` rows are all DISTINCT must read
    for the routed experts: the three projections of every held expert the
    rows reach, every expert layer. The rows themselves (a few hundred KB)
    are left out."""
    weights = (3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
               * _ITEMSIZE[cfg.get("torch_dtype", "float32")])
    return experts_reached(cfg, rows) * weights * expert_layers(cfg)


def kda_step_bytes(cfg: dict, num_slots: int) -> int:
    """Bytes one decode step must move for the KDA update: one read and one
    write of every slot's state S [heads, head_dim, head_dim] float32, every
    KDA layer (free slots ride along: the step's shape does not depend on
    occupancy). Exact."""
    state = cfg["num_attention_heads"] * cfg["head_dim"] ** 2 * 4
    return 2 * num_slots * kda_layers(cfg) * state


def roofline_share_pct(nbytes: float, seconds: float,
                       bytes_per_s: float) -> float:
    """100 x (the least time the memory system allows) / (the time taken)."""
    return 100.0 * nbytes / bytes_per_s / seconds
