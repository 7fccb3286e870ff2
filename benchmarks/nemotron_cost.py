"""Bytes the state-space step of a Nemotron-H decode step must move,
computed from a configuration file's sizes and nothing else: the numerator
of `ssd_update_roofline_share.serve`. What an implementation adds (a
column repeated for a head's decay, the output's padding) does not count.

The configuration states what is run: `layers_held` (published indices;
their kinds follow from `hybrid_override_pattern`), the Mamba widths
(`mamba_num_heads`, `mamba_head_dim`, `n_groups`, `ssm_state_size`).
"""
from __future__ import annotations

F32 = 4


def layers_held(cfg: dict) -> list:
    return cfg.get("layers_held") or list(range(cfg["num_hidden_layers"]))


def mamba_layers(cfg: dict) -> int:
    pattern = cfg["hybrid_override_pattern"]
    return sum(1 for l in layers_held(cfg) if pattern[l] == "M")


def ssd_step_bytes(cfg: dict, num_slots: int) -> int:
    """Bytes one decode step must move for the state-space update: one
    read and one write of every slot's float32 state S [heads, head_dim,
    state] (free slots ride along: the step's shape does not depend on
    occupancy), and in float32 its inputs x [heads, head_dim], Delta
    [heads], B and C [groups, state] and its output y [heads, head_dim],
    every Mamba layer. Exact."""
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    n, g = cfg["ssm_state_size"], cfg["n_groups"]
    slot = 2 * h * p * n + 2 * h * p + h + 2 * g * n
    return F32 * slot * num_slots * mamba_layers(cfg)
