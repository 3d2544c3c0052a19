"""Routed experts: one layer that is told which experts it holds.

ABSENT from the reference (delegated to hosted frameworks,
SURVEY.md §2.5 "Expert parallel"). The layer routes every token over
all ``E`` published experts (sigmoid scores in float32, a bias that
selects and does not weigh, top-k, weights normalised over the chosen
and scaled), and computes the part of the result that its own experts
``held = (first, count)`` give. Experts it does not hold add nothing:
what they would have added lies on the chips that hold them. No
capacity, no dropped token.

Static shapes: the ``T x k`` (token, expert) pairs are sorted by
expert, the held experts first, so the rows of held expert ``g`` are
the slice ``[starts[g], ends[g])`` of the sorted rows and everything
past ``ends[-1]`` belongs to experts held elsewhere. ``gmm`` (a Pallas
kernel: rows grouped by expert times that expert's matrix) visits
only the row tiles that hold a held expert's rows; the others are
skipped, not computed and discarded, and their output rows are left
unwritten (the combine does not read them: it selects, it does not
multiply by zero). The stages round the kernel (gather, activation,
combine) pass over a static prefix of the sorted rows, the shortest of
a short ladder that holds ``ends[-1]`` rows, chosen on the device for
each call.

The pairs' order, their sorted positions and the held experts' bounds
come from sorts and counts: a TPU sorts scalars fast and gathers or
scatters them one at a time. The order is the payload of the stable
sort of the keys, each pair's position the payload of a second sort of
the order, and a held expert's bounds are running sums of its rows
counted by a compare; every result is the one the gathers, the scatter
and the binary searches gave, bit for bit.

Shapes: tokens ``m [T, D]``; ``router [D, E]``, ``bias [E]``;
``wg, wi [count, D, F]``, ``wo [count, F, D]``.

Forward only: ``gmm`` has no backward kernel yet and says so by name
when a gradient is asked of it.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.ops.flash_attention import _for_lowering_platform, _round_up

_HIGHEST = lax.Precision.HIGHEST
# Row tile of ``gmm`` unless the caller names it. An expert of the
# benchmark's cells sees 130-260 rows a prefill, so a row tile of 256
# keeps most experts to one or two visits. The column tile is as wide
# as the matrix where that fits (3,072 at one cell's experts, 1,024 and
# 2,304 at another's): each row tile is then read once, which measured
# fastest of seven pairs (PERF.md §6, PR 28).
_TILE_M = 256
# Mosaic's default scope, and the most asked of a v5e core's 128 MiB. A
# call took 1.56 times what ``_gmm_vmem`` counts (float32 operands, my
# chip run, PR 28): the limit asked for is 1.75 times the count.
_VMEM_DEFAULT, _VMEM_MOST, _VMEM_MARGIN = 16 * 2 ** 20, 96 * 2 ** 20, 1.75


def _gmm_vmem(tile_m: int, tile_n: int, k: int, itemsize: int) -> int:
    """Row tile, matrix slice and result tile, each double-buffered,
    and the float32 product."""
    return (2 * tile_m * k * itemsize + 2 * k * tile_n * itemsize
            + 2 * tile_m * tile_n * itemsize + 2 * tile_m * tile_n * 4)


# --------------------------------------------------------------------------
# Routing
# --------------------------------------------------------------------------

def route(m, router, bias, *, top_k: int, route_scale: float):
    """-> (experts [T, k] int32, weights [T, k] float32). Scores are
    sigmoids in float32 at full precision; ``bias`` moves the selection
    only; the weights are the chosen scores over their sum, scaled."""
    scores = jax.nn.sigmoid(jnp.dot(
        m.astype(jnp.float32), router.astype(jnp.float32),
        precision=_HIGHEST))
    _, experts = lax.top_k(scores + bias.astype(jnp.float32), top_k)
    # Gathered, though a one-hot select gives the same scores: in a whole
    # program on the chip XLA then compiles the sum below in another
    # fusion, its bits differ, and ties turn in the layers after
    # (PERF.md §6).
    chosen = jnp.take_along_axis(scores, experts, axis=-1)
    weights = route_scale * chosen / (
        jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    return experts.astype(jnp.int32), weights


def _sorted_by_expert(experts, n_experts: int, held, rows: int):
    """The pairs in expert order, the held experts first. -> (token of
    each sorted row [rows], sorted position of each pair [T, k], starts
    and ends [count] of the held experts' rows). ``rows`` pads the
    sorted list (padding sorts last and belongs to no expert)."""
    first, count = held
    t, k = experts.shape
    key = ((experts - first) % n_experts).reshape(-1)
    key = jnp.pad(key, (0, rows - t * k), constant_values=n_experts)
    pair = lax.iota(jnp.int32, rows)
    # the stable sort ``argsort`` runs; the pairs ride it as its payload
    _, order = lax.sort((key, pair), num_keys=1, is_stable=True)
    # sorting the order hands each pair its sorted position: the inverse
    _, position = lax.sort((order, pair), num_keys=1)
    position = position[:t * k].reshape(t, k)
    # a held expert's rows counted, and its bounds their running sums
    given = jnp.sum(key == jnp.arange(count, dtype=key.dtype)[:, None],
                    axis=1, dtype=jnp.int32)
    ends = jnp.cumsum(given, dtype=jnp.int32)
    token = jnp.minimum(order // k, t - 1)
    return token, position, ends - given, ends


# --------------------------------------------------------------------------
# Grouped matmul
# --------------------------------------------------------------------------

def _visits(starts, ends, tile_m: int, tiles_m: int):
    """The (row tile, group) pairs the kernel visits, in order: each
    group's tiles from the one that holds its first row to the one that
    holds its last, so a tile that two groups share is visited once for
    each. ``tiles_m + groups - 1`` bounds their number; the visits past
    the real ones repeat the last real one (no block changes, nothing
    is fetched) and are skipped by the kernel."""
    groups = starts.shape[0]
    first_tile = starts // tile_m
    tiles = jnp.where(ends > starts,
                      (ends - 1) // tile_m - first_tile + 1, 0)
    upto = jnp.cumsum(tiles)
    real = upto[-1]
    v = jnp.arange(tiles_m + groups - 1, dtype=jnp.int32)
    v = jnp.minimum(v, jnp.maximum(real - 1, 0))
    group = jnp.minimum(jnp.searchsorted(upto, v, side="right"),
                        groups - 1).astype(jnp.int32)
    tile = first_tile[group] + v - (upto - tiles)[group]
    return (jnp.clip(tile, 0, tiles_m - 1).astype(jnp.int32), group,
            real.astype(jnp.int32).reshape(1))


def _gmm_kernel(tile_ref, group_ref, starts_ref, ends_ref, real_ref,
                lhs_ref, rhs_ref, out_ref):
    # lhs_ref [tile_m, K]; rhs_ref [1, K, tile_n]; out_ref [tile_m, tile_n]
    v = pl.program_id(1)

    @pl.when(v < real_ref[0])
    def _():
        group = group_ref[v]
        tile_m = lhs_ref.shape[0]
        product = lax.dot_general(
            lhs_ref[...], rhs_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        row = tile_ref[v] * tile_m + lax.broadcasted_iota(
            jnp.int32, product.shape, 0)
        mine = (row >= starts_ref[group]) & (row < ends_ref[group])
        # rows of the tile that are another group's keep what that
        # group's visit wrote (or will write over whatever is here)
        out_ref[...] = jnp.where(mine, product.astype(out_ref.dtype),
                                 out_ref[...])


def _gmm_call(lhs, rhs, starts, ends, *, tile_m, tile_n, interpret):
    m, k = lhs.shape
    groups, _k, n = rhs.shape
    tiles_m = m // tile_m
    tile, group, real = _visits(starts, ends, tile_m, tiles_m)
    need = _gmm_vmem(tile_m, tile_n, k, lhs.dtype.itemsize)
    call = pl.pallas_call(
        _gmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n // tile_n, tiles_m + groups - 1),
            in_specs=[
                pl.BlockSpec((tile_m, k),
                             lambda j, v, tile, *_: (tile[v], 0)),
                pl.BlockSpec((1, k, tile_n),
                             lambda j, v, tile, group, *_: (group[v], 0, j)),
            ],
            out_specs=pl.BlockSpec((tile_m, tile_n),
                                   lambda j, v, tile, *_: (tile[v], j)),
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=max(_VMEM_DEFAULT,
                                 int(need * _VMEM_MARGIN))),
    )

    # A device profile names an operation by its HLO instruction, and a
    # Mosaic call takes the name of the innermost jitted function round
    # it: this one's name is the kernel's name in every trace.
    def moe_gmm(*operands):
        return call(*operands)

    return jax.jit(moe_gmm)(tile, group, starts, ends, real, lhs, rhs)


def _gmm(lhs, rhs, starts, ends, tile_m, tile_n, interpret):
    m, k = lhs.shape
    n = rhs.shape[-1]
    tile_m = min(tile_m or _TILE_M, m)
    # the widest column tile that divides and fits, else the whole
    tile_n = tile_n or next(
        (t for t in range(n, 0, -128) if n % t == 0
         and _gmm_vmem(tile_m, t, k, lhs.dtype.itemsize) * _VMEM_MARGIN
         <= _VMEM_MOST), n)
    if m % tile_m or n % tile_n:
        raise ValueError(f"gmm: {m} rows and {n} columns are not whole "
                         f"tiles of ({tile_m}, {tile_n})")
    return _for_lowering_platform(
        functools.partial(_gmm_call, tile_m=tile_m, tile_n=tile_n),
        interpret, lhs, rhs, starts.astype(jnp.int32),
        ends.astype(jnp.int32))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def gmm(lhs, rhs, starts, ends, tile_m: Optional[int] = None,
        tile_n: Optional[int] = None, interpret: Optional[bool] = None):
    """Grouped matmul. ``lhs [M, K]`` holds rows grouped so that group
    ``g``'s are ``[starts[g], ends[g])`` (disjoint, in rising order);
    ``rhs [G, K, N]``. -> ``[M, N]`` with ``lhs[r] @ rhs[g]`` in the
    rows of group ``g``; a row of no group is left unwritten (read it
    through a select). Operands as given, float32 accumulation. ``M``
    is a multiple of the row tile and ``N`` of the column tile."""
    return _gmm(lhs, rhs, starts, ends, tile_m, tile_n, interpret)


def _gmm_fwd(lhs, rhs, starts, ends, tile_m, tile_n, interpret):
    return _gmm(lhs, rhs, starts, ends, tile_m, tile_n, interpret), None


def _gmm_bwd(tile_m, tile_n, interpret, residuals, g):
    raise NotImplementedError(
        "ray_tpu.ops.moe.gmm has no backward kernel: the routed layer "
        "runs forward only (serving); training through it needs the "
        "transposed grouped matmuls (ROADMAP R4)")


gmm.defvjp(_gmm_fwd, _gmm_bwd)


def gmm_reference(lhs, rhs, starts, ends):
    """The same by an einsum over the groups; rows of no group are 0."""
    row = jnp.arange(lhs.shape[0])[None, :]
    member = (row >= starts[:, None]) & (row < ends[:, None])   # [G, M]
    return jnp.einsum("gm,mk,gkn->mn", member.astype(lhs.dtype), lhs, rhs,
                      preferred_element_type=jnp.float32).astype(lhs.dtype)


# --------------------------------------------------------------------------
# The layer
# --------------------------------------------------------------------------

def _ladder(rows: int, count: int, n_experts: int,
            tile_m: int) -> Tuple[int, ...]:
    """The row counts a call chooses among, from shapes alone: whole
    row tiles that double from twice the even share of the rows
    (``rows * count / n_experts``) up to ``rows`` itself, the worst
    case, which holds whatever the router does. A call that holds half
    the experts or more has that one rung."""
    rung = _round_up(-(-2 * rows * count // n_experts), tile_m)
    rungs = []
    while rung < rows:
        rungs.append(rung)
        rung *= 2
    return (*rungs, rows)


def _rung(held_rows, ladder: Tuple[int, ...]):
    """Index of the smallest rung that holds ``held_rows`` rows: the
    device's scalar and the host's counts alike."""
    return sum(held_rows > rung for rung in ladder[:-1])


# The ladder of each call traced in this process, by its (pairs, held
# experts): ``route_counts`` is told neither the published experts nor
# the row tile, and names the rung the device took from this.
_LADDERS = {}


# Jitted, so that the layers of a model that call it at the same shapes
# share one trace of the rungs (nine kernels, not nine a layer).
@functools.partial(jax.jit, static_argnames=("ladder", "tile_m", "tile_n",
                                             "interpret"))
def _held_part(m, token, position, starts, ends, weights, wg, wi, wo, *,
               ladder: Tuple[int, ...], tile_m: int, tile_n: Optional[int],
               interpret: Optional[bool]):
    """The sorted pairs through the held experts and back to their
    tokens, over the smallest rung of ``ladder`` that holds the held
    experts' ``ends[-1]`` rows. -> [T, D] in ``m``'s type."""
    # the row tile and the groups' bounds are the same on every rung, so
    # each held row's products are too
    run = functools.partial(gmm, starts=starts, ends=ends, tile_m=tile_m,
                            tile_n=tile_n, interpret=interpret)

    def part_over(r: int):
        x = m[token[:r]]                                    # [r, D]
        hidden = jax.nn.silu(run(x, wg)) * run(x, wi)
        y = run(hidden, wo)                                 # [r, D]
        # The combine selects by index, not by row: a pair whose expert
        # is held elsewhere reads a row of zeros past y's own, which
        # costs the gather next to nothing (0.6 ms for 3.2-3.4 at 16,384
        # tokens where such a pair reads a row of y and a select drops
        # it). The gathered rows stand as they are before the sum, so
        # that they are laid out for it in m's type and not in float32
        # (1.3 ms for 1.9). PERF.md §6, PR 31: bit for bit the sum it was.
        y = jnp.concatenate([y, jnp.zeros((8, y.shape[1]), y.dtype)])
        pairs = lax.optimization_barrier(
            y[jnp.where(position < ends[-1], position, r)])  # [T, k, D]
        return jnp.sum(pairs.astype(jnp.float32) * weights[..., None],
                       axis=1).astype(m.dtype)

    if len(ladder) == 1:
        return part_over(ladder[0])
    # the sum is the same text on every rung, and XLA would lift it out
    # of the branches to after them, handing it all T x k gathered rows
    # in float32: it stays where the rows are
    return lax.switch(
        _rung(ends[-1], ladder),
        [lambda r=r: lax.optimization_barrier(part_over(r)) for r in ladder])


def routed_experts(m, router, bias, wg, wi, wo, *, held: Tuple, top_k: int,
                   route_scale: float, tile_m: Optional[int] = None,
                   tile_n: Optional[int] = None,
                   interpret: Optional[bool] = None):
    """``m [T, D]`` -> (the held experts' weighted part of the routed
    result [T, D], rows routed to each held expert [count] int32).
    ``held = (first, count)``: this call holds experts ``first ..
    first + count - 1`` of the ``router.shape[1]`` published ones
    (``first`` may be traced: an axis index under ``shard_map``).

    The held experts' rows are the first ``ends[-1]`` of the sorted
    rows, so gather, matmuls, activation and combine pass over the
    smallest rung of ``_ladder`` that holds them, chosen on the device;
    the top rung is every row, so no row is ever left out."""
    t, _d = m.shape
    n_experts = router.shape[1]
    count = wg.shape[0]
    if held[1] != count:
        raise ValueError(f"held names {held[1]} experts, the weights "
                         f"hold {count}")
    experts, weights = route(m, router, bias, top_k=top_k,
                             route_scale=route_scale)
    tile_m = min(tile_m or _TILE_M, _round_up(t * top_k, 8))
    rows = _round_up(t * top_k, tile_m)
    token, position, starts, ends = _sorted_by_expert(
        experts, n_experts, held, rows)
    dt = m.dtype
    ladder = _LADDERS[t * top_k, count] = _ladder(rows, count, n_experts,
                                                  tile_m)
    out = _held_part(m, token, position, starts, ends, weights,
                     wg.astype(dt), wi.astype(dt), wo.astype(dt),
                     ladder=ladder, tile_m=tile_m, tile_n=tile_n,
                     interpret=interpret)
    return out, ends - starts


def route_counts(rows_by_layer, tokens: int, top_k: int) -> dict:
    """The counts of one forward's ``model.moe.route`` record, from the
    rows each held expert of each routed layer was given
    (``rows_by_layer [layers, count]``, on the host). ``rows_computed``:
    the rung each layer took, summed (the worst case for a layer that
    this process did not trace)."""
    rows = np.asarray(rows_by_layer)
    layers, count = rows.shape
    ladder = _LADDERS.get((tokens * top_k, count), (tokens * top_k,))
    taken = [ladder[_rung(int(held), ladder)] for held in rows.sum(axis=1)]
    return {"layers": layers,
            "rows_total": layers * tokens * top_k,
            "rows_held": int(rows.sum()),
            "rows_computed": sum(taken),
            "load_max": int(rows.max(initial=0)),
            "load_mean": float(rows.mean()) if rows.size else 0.0}


def record_route(rows_by_layer, tokens: int, top_k: int, start_ns: int,
                 end_ns: int, request: Optional[str] = None) -> None:
    """One ``model.moe.route`` record for a forward whose row counts
    came back with its logits; ``start_ns`` and ``end_ns`` are the
    forward's own two readings of ``time.perf_counter_ns()``."""
    from ray_tpu.util import tracing
    if np.asarray(rows_by_layer).size:
        tracing.record("model.moe.route", start_ns, end_ns, request,
                       **route_counts(rows_by_layer, tokens, top_k))


# --------------------------------------------------------------------------
# Experts divided over a mesh axis
# --------------------------------------------------------------------------

def make_moe_fn(mesh: Mesh, *, top_k: int, route_scale: float,
                ep_axis: str = "tp"):
    """The layer with its experts divided over ``ep_axis``: every shard
    is given all the tokens, computes its own experts' part, and the
    parts are summed over the axis. -> fn(m, router, bias, wg, wi, wo)
    -> (routed result [T, D], rows of every expert [E])."""
    def body(m, router, bias, wg, wi, wo):
        count = wg.shape[0]
        part, rows = routed_experts(
            m, router, bias, wg, wi, wo,
            held=(lax.axis_index(ep_axis) * count, count), top_k=top_k,
            route_scale=route_scale)
        return lax.psum(part.astype(jnp.float32), ep_axis).astype(
            m.dtype), rows

    whole, split = P(None, None), P(ep_axis, None, None)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(whole, whole, P(None), split, split, split),
        out_specs=(whole, P(ep_axis)), check_vma=False)
