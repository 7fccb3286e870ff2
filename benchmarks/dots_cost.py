"""Bytes the kernels of a Dots decode step must move, computed from a
configuration file's sizes and nothing else: the numerator of
`mla_decode_roofline_share.serve`, and the experts a step's rows reach (the
runner's count stands beside it). What an implementation adds (a block read
past a slot's last row, a free slot's block) does not count.

The configuration states what is run: `layers_held` (published indices),
`n_routed_experts` held of `n_routed_experts_published` routed over,
`num_experts_per_tok`, the widths, and `torch_dtype`.
"""
from __future__ import annotations

_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}
PAGE_LANES = 128       # a page's row is held in whole 128 lanes


def layers_held(cfg: dict) -> list:
    return cfg.get("layers_held") or list(range(cfg["num_hidden_layers"]))


def page_row_bytes(cfg: dict) -> int:
    """Bytes of one position's row in one layer's page: [latent; rotary
    key] in whole 128 lanes of the weights' dtype (576 held in 640)."""
    width = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return -(-width // PAGE_LANES) * PAGE_LANES \
        * _ITEMSIZE[cfg.get("torch_dtype", "float32")]


def mla_step_bytes(cfg: dict, rows: float) -> float:
    """Bytes one decode step must read for latent attention when its
    dispatched slots hold `rows` live rows in all (each slot's cached
    prefix and the row being written): every live row of every layer's
    page ONCE (with the up-projection absorbed a row is key and value at
    once). The queries and contexts (heads x 640 a slot) are left out.
    The MXU's floor lies just under it at 128 heads: a row of a layer is
    2 x heads x (576 + 512) = 278,528 FLOP, 1.41 ns at 197 TFLOP/s, against
    1,280 B, 1.56 ns at 819 GB/s."""
    return rows * page_row_bytes(cfg) * len(layers_held(cfg))


def experts_reached(cfg: dict, rows: float) -> float:
    """The held experts that `rows` rows reach in one layer, in expectation
    under a router that picks every expert equally often (the runner
    balances the seed's router bias so): held x (1 - (1 - top_k / routed
    over)^rows)."""
    total = cfg.get("n_routed_experts_published", cfg["n_routed_experts"])
    miss = 1.0 - cfg["num_experts_per_tok"] / total
    return cfg["n_routed_experts"] * (1.0 - miss ** rows)
