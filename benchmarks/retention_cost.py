"""Bytes and operations power retention needs, computed from a
configuration file's sizes and nothing else: the numerators of the
retention kernels' roofline shares. What an implementation adds (a second
read of the state, layout copies, phi built by a matmul) does not count.

The configuration states what is held: `state.rows_held` rows (the
feature map's head_dim (head_dim + 1) / 2 entries, rounded up as the file
says), `state.columns` = head_dim + 1 (the values and the normaliser) and
`state.itemsize` bytes an entry, a key/value head, a layer and a sequence.
"""
from __future__ import annotations


def state_bytes(cfg: dict, num_slots: int, columns: int = None) -> int:
    """Bytes of state the engine holds for `num_slots` sequences, all
    layers; `columns` counts a part of a state's columns (default: all)."""
    st = cfg["state"]
    cols = st["columns"] if columns is None else columns
    return (cfg["num_hidden_layers"] * num_slots * cfg["num_key_value_heads"]
            * st["rows_held"] * cols * st["itemsize"])


def step_bytes(cfg: dict, num_slots: int, part: str = "all") -> int:
    """Bytes one decode step must move for the state update: one read and
    one write of every slot's state (free slots ride along: the step's
    shape does not depend on occupancy). `part="matrix"` counts the
    head_dim value columns only, the part the named update kernel streams
    (the normaliser's column is updated beside it)."""
    if part not in ("all", "matrix"):
        raise ValueError(f"retention_cost: unknown part {part!r}")
    cols = cfg["head_dim"] if part == "matrix" else None
    return 2 * state_bytes(cfg, num_slots, cols)


def step_flops(cfg: dict, num_slots: int) -> int:
    """Operations of one decode step's state update and read-out: an entry
    of a key/value head's state takes a multiply for the decay and a
    multiply-add for the rank-1 product, and a multiply-add for each query
    head that reads it."""
    st = cfg["state"]
    entries = (cfg["num_hidden_layers"] * num_slots * st["rows_held"]
               * st["columns"])
    return entries * (3 * cfg["num_key_value_heads"]
                      + 2 * cfg["num_attention_heads"])


def roofline_share_pct(nbytes: float, seconds: float,
                       bytes_per_s: float) -> float:
    """100 x (the least time the memory system allows) / (the time taken)."""
    return 100.0 * nbytes / bytes_per_s / seconds
