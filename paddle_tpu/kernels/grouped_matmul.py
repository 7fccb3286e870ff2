"""Grouped matmul: rows sorted by group, one weight matrix a group.

The expert pass of a routed feed-forward layer (`nn.RoutedExperts`): rows
that chose the same expert lie together, every group's first row on a
tile boundary, and a grid step multiplies ONE tile of `tile_rows` rows
by ONE block of its group's weights. No capacity, no dropped row, no
[rows, groups, capacity] array: a group takes as many tiles as its rows
need, and the layout's bound (`padded_rows`) is what every row landing
anywhere can need, whatever the routing.

    layout(group_of_row, groups, tile_rows)    where each row goes
    grouped_matmul(x, tile_group, active, ws)  x @ ws[0][g] a tile, or
                                               silu(x @ ws[0][g]) * (x @ ws[1][g])

`tile_group[i]` names tile i's group; tiles past `active` are not
computed (their output rows are unspecified) and their block indices
repeat the last active tile's, so they move nothing. Weights a group a
decode step's rows do not reach are never read: the step costs the
experts reached, not the experts held.

On a TPU it is one Pallas kernel a call (`moe_experts` in a trace; bfloat16
or float32 operands to the matmul unit in their own dtype, float32
accumulation; ws (None, w) squares relu(x @ w) after it); `jax.lax.ragged_dot`
over the same layout is the CPU path and the kernel's reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

KERNEL = "moe_experts"
WEIGHT_BLOCK_BYTES = 2 ** 21      # one weight block in VMEM (double-buffered)
VMEM_LIMIT_BYTES = 48 * 2 ** 20


def tile_rows_for(rows: int) -> int:
    """Rows a tile: 128 (the matmul unit's) for a prompt, 16 (a bfloat16
    sublane tile) for a decode step's few rows, where most tiles hold one
    or two rows and a taller tile is empty work."""
    return 128 if rows >= 2048 else 16


def padded_rows(rows: int, groups: int, tile_rows: int) -> int:
    """Rows of the grouped layout: every group may leave one tile partly
    empty."""
    return (rows // tile_rows + groups) * tile_rows


def layout(group_of_row, groups: int, tile_rows: int):
    """Where each row goes. group_of_row [R] int32 in 0..groups (`groups`
    itself: the row belongs to no group held here and goes nowhere).
    Returns (place [R] int32: the row's index in the grouped layout, or
    `padded_rows` for a row that goes nowhere; tile_group [tiles] int32;
    active [1] int32: the tiles that hold rows; tiles_of [groups] int32)."""
    rows = group_of_row.shape[0]
    tiles = padded_rows(rows, groups, tile_rows) // tile_rows
    sizes = jnp.zeros((groups + 1,), jnp.int32).at[group_of_row].add(1)
    tiles_of = -(-sizes[:groups] // tile_rows)
    tile_end = jnp.cumsum(tiles_of)
    first_row = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                 tile_end * tile_rows])  # [groups + 1]
    sorted_start = jnp.cumsum(sizes) - sizes              # [groups + 1]
    order = jnp.argsort(group_of_row, stable=True)
    group_sorted = group_of_row[order]
    rank = jnp.arange(rows, dtype=jnp.int32) - sorted_start[group_sorted]
    place_sorted = jnp.where(group_sorted < groups,
                             first_row[group_sorted] + rank,
                             tiles * tile_rows)
    place = jnp.zeros((rows,), jnp.int32).at[order].set(place_sorted)
    active = tile_end[-1:]
    # a tile past the live ones names the last live tile's group
    live = jnp.minimum(jnp.arange(tiles, dtype=jnp.int32),
                       jnp.maximum(active[0] - 1, 0))
    tile_group = jnp.minimum(
        jnp.searchsorted(tile_end, live, side="right").astype(jnp.int32),
        groups - 1)
    return place, tile_group, active, tiles_of


def _kernel(group_ref, active_ref, x_ref, *refs):
    *w_refs, o_ref = refs

    @pl.when(pl.program_id(0) < active_ref[0])
    def _():
        x = x_ref[...]
        acc = jnp.dot(x, w_refs[0][0], preferred_element_type=jnp.float32)
        if len(w_refs) == 2:
            acc = jax.nn.silu(acc) * jnp.dot(
                x, w_refs[1][0], preferred_element_type=jnp.float32)
        o_ref[...] = acc.astype(o_ref.dtype)


def _block_cols(k: int, n: int, itemsize: int) -> int:
    """The widest block of a [k, n] weight that divides n in whole 128s
    and stays under `WEIGHT_BLOCK_BYTES`."""
    if n % 128:
        return n
    best = 128
    for m in range(1, n // 128 + 1):
        if (n // 128) % m == 0 and k * m * 128 * itemsize <= WEIGHT_BLOCK_BYTES:
            best = m * 128
    return best


def _pallas(x, tile_group, active, ws, tile_rows, interpret):
    rows, k = x.shape
    n = ws[0].shape[2]
    tn = _block_cols(k, n, ws[0].dtype.itemsize)
    nj = n // tn

    def tile(i, j, group, active):
        return jnp.maximum(jnp.minimum(i, active[0] - 1), 0), 0

    def weight(i, j, group, active):
        return group[i], 0, jnp.where(i < active[0], j, nj - 1)

    return pl.pallas_call(
        _kernel, name=KERNEL,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(rows // tile_rows, nj),
            in_specs=[pl.BlockSpec((tile_rows, k), tile)]
            + [pl.BlockSpec((1, k, tn), weight)] * len(ws),
            out_specs=pl.BlockSpec((tile_rows, tn),
                                   lambda i, j, group, active: (i, j))),
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(tile_group, active, x, *ws)


def _ragged(x, tiles_of, ws, tile_rows):
    sizes = tiles_of * tile_rows
    dot = functools.partial(jax.lax.ragged_dot, group_sizes=sizes,
                            preferred_element_type=jnp.float32)
    acc = dot(x, ws[0])
    if len(ws) == 2:
        acc = jax.nn.silu(acc) * dot(x, ws[1])
    return acc.astype(x.dtype)


def grouped_matmul(x, tile_group, active, tiles_of, ws, tile_rows: int):
    """x [padded_rows, k] in the grouped layout times `ws` (`_relu2` below
    for ws (None, w)); rows of tiles past `active` are unspecified."""
    if ws[0] is None:
        return _relu2(x, tile_group, active, tiles_of, ws[1], tile_rows)
    if jax.default_backend() == "tpu":
        return _pallas(x, tile_group, active, ws, tile_rows, False)
    return _ragged(x, tiles_of, ws, tile_rows)


def _relu2(x, tile_group, active, tiles_of, w, tile_rows):
    """relu(x @ w)^2 a group, for experts of two projections: the kernel
    with the one weight, then the square in float32, rounded to x's dtype
    as the kernel rounds its own output."""
    h = grouped_matmul(x, tile_group, active, tiles_of, (w,), tile_rows)
    return jnp.square(jax.nn.relu(h.astype(jnp.float32))).astype(x.dtype)
