"""Bytes the expert pass of an LFM2-MoE decode step must move, computed
from a configuration file's sizes and nothing else: the numerator of
`expert_read_roofline_share.serve`. What an implementation adds (the
gather into the grouped layout, its padding rows, a second read of a
weight block) does not count.

The configuration states what is run: `layers_held` (published indices;
the FFN of those before `num_dense_layers` is dense, the others' routed),
`num_experts` held (all of them here), `num_experts_per_tok`, the widths
and `torch_dtype`.
"""
from __future__ import annotations

_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def layers_held(cfg: dict) -> list:
    return cfg.get("layers_held") or list(range(cfg["num_hidden_layers"]))


def expert_layers(cfg: dict) -> int:
    return sum(1 for l in layers_held(cfg) if l >= cfg["num_dense_layers"])


def expert_bytes(cfg: dict) -> int:
    """One expert's three projections, W1 and W3 [hidden, width] and W2
    [width, hidden]."""
    return (3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
            * _ITEMSIZE[cfg.get("torch_dtype", "float32")])


def route_bytes(cfg: dict) -> int:
    """One route's activations through the two grouped matmuls: its row
    read and its [width] written by the first, read and its row written
    by the second."""
    return (2 * (cfg["hidden_size"] + cfg["moe_intermediate_size"])
            * _ITEMSIZE[cfg.get("torch_dtype", "float32")])


def expert_read_bytes(cfg: dict, reached: float, routes: float) -> float:
    """Bytes of a decode step whose expert layers reached `reached` held
    experts and sent `routes` (row, route) pairs to held experts, each
    summed over the expert layers (`moe.decode.experts_reached`,
    `moe.decode.routes_reached`): every reached expert's weights once,
    every route's activations. Exact for those counts."""
    return reached * expert_bytes(cfg) + routes * route_bytes(cfg)


def experts_reached(cfg: dict, rows: float) -> float:
    """The held experts that `rows` distinct rows reach in one layer, in
    expectation under a router that picks every expert equally often:
    held x (1 - (1 - top_k / experts)^rows)."""
    miss = 1.0 - cfg["num_experts_per_tok"] / cfg.get(
        "num_experts_published", cfg["num_experts"])
    return cfg["num_experts"] * (1.0 - miss ** rows)
