"""Switch-style mixture-of-experts with expert parallelism (EP).

BEYOND-PARITY EXTENSION (the reference is a 2016 CNN framework with no
MoE; SURVEY.md §2.3 lists EP "absent — not required", and the named-mesh
design note makes the axis additive). This is the TPU-idiomatic GShard/
Switch formulation: top-1 routing realized as DENSE one-hot dispatch
einsums (no data-dependent shapes — everything jits), experts sharded
over an ``expert`` mesh axis, tokens exchanged with ``lax.all_to_all``
over ICI.

Data layout inside ``shard_map`` over the expert axis (size n):

- every device carries its own token batch (the expert axis doubles as
  the data axis — the classic dp==ep fusion);
- expert weights are sharded on their leading dim: device i owns experts
  ``[i*E/n, (i+1)*E/n)``;
- dispatch: route local tokens into per-expert capacity slots
  ``[E, C, d]``, all-to-all so each device holds its experts' slots from
  EVERY peer ``[E/n, n*C, d]``, apply the local experts, all-to-all
  back, combine scaled by the gate probability.

Tokens beyond an expert's capacity are dropped (the residual stream
carries them unchanged) — Switch semantics. With ``axis_name=None`` the
same code runs dense on one device (the test oracle and the small-scale
fallback).

Beside it, :func:`route_topk` and :func:`routed_experts`: top-k routing
over ALL experts with NO capacity and NO drop, computed for the share of
the experts that this chip holds (``first`` .. ``first + count`` of
``total``) by grouped matrix products over the token-expert pairs sorted
by expert (:mod:`theanompi_tpu.ops.pallas_moe`). What the absent experts
would add is left out: on one chip the layer runs without its exchange.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from theanompi_tpu.ops.pallas_moe import gmm, group_tiles, padded_rows


class MoEStats(NamedTuple):
    aux_loss: jax.Array  # load-balance penalty (Switch: E * sum f_e * P_e)
    dropped_frac: jax.Array  # fraction of tokens beyond capacity


def switch_moe(
    x: jax.Array,  # [S, d] local tokens (flatten batch x seq first)
    gate_w: jax.Array,  # [d, E] replicated router
    expert_in: jax.Array,  # [E_local, d, h] this device's experts
    expert_out: jax.Array,  # [E_local, h, d]
    axis_name: Optional[str],
    capacity_factor: float = 1.25,
    stats_axes: Optional[tuple] = None,
) -> tuple[jax.Array, MoEStats]:
    """Top-1 (Switch) MoE layer. Returns ``(y [S, d], MoEStats)`` where
    ``y`` is zero for dropped tokens (caller adds the residual).

    ``E = n * E_local`` experts globally; capacity per expert per device
    ``C = ceil(S * capacity_factor / E)``. The load-balance ``aux_loss``
    uses GLOBAL token statistics — averaged over ``stats_axes`` (default:
    the expert axis; pass every axis the tokens are sharded over, e.g.
    ``(expert, seq)``) — so its value, and therefore the training
    objective, is identical to the dense single-device computation
    (tested in tests/test_moe.py).
    """
    if stats_axes is None:
        stats_axes = (axis_name,) if axis_name is not None else ()
    stats_axes = tuple(a for a in stats_axes if a is not None)
    S, d = x.shape
    E_local = expert_in.shape[0]
    n = lax.psum(1, axis_name) if axis_name is not None else 1
    E = n * E_local
    C = math.ceil(S * capacity_factor / E)

    logits = x @ gate_w  # [S, E]
    probs = jax.nn.softmax(logits, axis=-1)
    p = jnp.max(probs, axis=-1)  # [S] gate scale of the chosen expert
    e = jnp.argmax(probs, axis=-1)  # [S]
    # routing bookkeeping in f32 regardless of x.dtype: a bf16 cumsum
    # cannot count past 256 (8 mantissa bits), which would collide
    # capacity-slot assignments for popular experts with no error
    onehot = jax.nn.one_hot(e, E, dtype=jnp.float32)  # [S, E]

    # slot position of each token within its expert's capacity buffer
    pos = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot  # [S, E]
    kept = (pos < C) & (onehot > 0)
    dropped = 1.0 - kept.any(axis=-1).astype(jnp.float32)
    slot = jax.nn.one_hot(pos.sum(axis=-1).astype(jnp.int32), C, dtype=jnp.float32)
    dispatch = (kept.astype(jnp.float32)[:, :, None] * slot[:, None, :]).astype(
        x.dtype
    )  # [S, E, C]

    buf = jnp.einsum("sec,sd->ecd", dispatch, x)  # [E, C, d]
    if axis_name is not None:
        # scatter experts to their owners, gather every peer's slots
        buf = lax.all_to_all(
            buf, axis_name, split_axis=0, concat_axis=1, tiled=True
        )  # [E_local, n*C, d]
    h = jax.nn.gelu(jnp.einsum("ecd,edh->ech", buf, expert_in))
    out = jnp.einsum("ech,ehd->ecd", h, expert_out)  # [E_local, n*C, d]
    if axis_name is not None:
        out = lax.all_to_all(
            out, axis_name, split_axis=1, concat_axis=0, tiled=True
        )  # [E, C, d]
    y = jnp.einsum("sec,ecd->sd", dispatch, out) * p[:, None]

    # Switch load balance on GLOBAL stats: f_e = fraction of tokens
    # routed to e, P_e = mean router prob of e
    f_e = jnp.mean(onehot, axis=0)
    P_e = jnp.mean(probs, axis=0)
    n_drop = jnp.sum(dropped)
    for a in stats_axes:
        f_e = lax.pmean(f_e, a)
        P_e = lax.pmean(P_e, a)
        n_drop = lax.pmean(n_drop, a)
    aux = E * jnp.sum(f_e * P_e)
    return y, MoEStats(aux_loss=aux, dropped_frac=n_drop / S)


# -- top-k routing without dropped tokens over a share of the experts --------


class RoutedStats(NamedTuple):
    counts: jax.Array  # [total] int32: this step's tokens of EVERY expert
    pairs_here: jax.Array  # token-expert pairs computed here
    pairs_absent: jax.Array  # pairs that fell to experts held elsewhere
    pad_rows: jax.Array  # rows of padding inside the grouped products' live tiles
    load_max_over_mean: jax.Array  # largest over mean rows of a held expert


def route_topk(h, router_w, bias, k: int, scale: float, scoring: str = "sigmoid"):
    """Scores over all experts in fp32 (``highest``: a score's rounding
    decides which expert a pair lands on), the ``k`` best chosen, weights
    the chosen scores normalised to ``scale``. ``scoring="sigmoid"``: a
    sigmoid a logit, chosen by ``score + bias`` (the bias selects only);
    ``"softmax"``: a softmax over all the logits, no bias (``bias`` is
    ``None``). -> (idx [T, k] int32, w [T, k] fp32)."""
    logits = jnp.dot(h.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    if scoring == "sigmoid":
        s = jax.nn.sigmoid(logits)
        _, idx = lax.top_k(s + lax.stop_gradient(bias), k)
    elif scoring == "softmax":
        s = jax.nn.softmax(logits, axis=-1)
        _, idx = lax.top_k(s, k)
    else:
        raise ValueError(f"route_topk: scoring {scoring!r} (sigmoid|softmax)")
    sel = jnp.take_along_axis(s, idx, axis=-1)
    return idx, sel / (jnp.sum(sel, axis=-1, keepdims=True) + 1e-20) * scale


@jax.custom_vjp
def _to_rows(x, row_pair, pos):
    """``[T, d] -> [M, d]``: buffer row ``r`` is the token of pair
    ``row_pair[r]``; rows of no pair are zero. Transposed as a gather
    through ``pos`` (the pairs' rows), so no row of a dead tile is read."""
    return jnp.take(x, row_pair // pos.shape[1], axis=0, mode="fill", fill_value=0)


def _to_rows_bwd(res, g):
    row_pair, pos = res
    back = jnp.take(g, pos, axis=0, mode="fill", fill_value=0)  # [T, k, d]
    return jnp.sum(back.astype(jnp.float32), axis=1).astype(g.dtype), None, None


_to_rows.defvjp(lambda x, row_pair, pos: (_to_rows(x, row_pair, pos), (row_pair, pos)),
                _to_rows_bwd)


@jax.custom_vjp
def _from_rows(y, row_pair, pos):
    """``[M, d] -> [T, k, d]``: each pair's row, zero for a pair whose
    expert is absent (``pos`` past the buffer). Transposed as a gather
    through ``row_pair``: every buffer row has one pair or none."""
    return jnp.take(y, pos, axis=0, mode="fill", fill_value=0)


def _from_rows_bwd(res, g):
    row_pair, pos = res
    flat = g.reshape(-1, g.shape[-1])
    return jnp.take(flat, row_pair, axis=0, mode="fill", fill_value=0), None, None


_from_rows.defvjp(lambda y, row_pair, pos: (_from_rows(y, row_pair, pos), (row_pair, pos)),
                  _from_rows_bwd)


def routed_experts(
    x: jax.Array,  # [T, d] tokens, compute dtype
    idx: jax.Array,  # [T, k] int32 chosen experts of `total`
    w: jax.Array,  # [T, k] fp32 their weights
    w_gate: jax.Array,  # [count, d, f] the experts held here
    w_up: jax.Array,  # [count, d, f]
    w_down: jax.Array,  # [count, f, d]
    first: int,  # the first expert held: first .. first + count of total
    total: int,
    *,
    tm: int = 256,
) -> tuple[jax.Array, RoutedStats]:
    """``sum_j w[t, j] * swiglu_{idx[t, j]}(x[t])`` over the chosen experts
    that are held here. No capacity and no drop: the pairs whose expert
    is held are sorted by expert (stable) into a buffer sized for the
    case that ALL ``T * k`` pairs land here, each expert's rows starting
    at a tile; gate, up and down projection are one grouped product each
    (tiles past the real rows skip their work); the weighted rows are
    gathered back by token."""
    T, k = idx.shape
    count, P = w_gate.shape[0], T * k
    M = padded_rows(P, count, tm)
    le = idx.reshape(P) - first
    le = jnp.where((le >= 0) & (le < count), le, count)  # `count`: held elsewhere
    sizes_all = jnp.zeros((count + 1,), jnp.int32).at[le].add(1)
    sizes = sizes_all[:count]
    tiles = group_tiles(sizes, tm)
    # where each group starts, among the sorted pairs and in the padded buffer
    sorted_start = jnp.cumsum(sizes_all) - sizes_all
    row_start = jnp.append(tm * (jnp.cumsum(tiles) - tiles), M)
    order = jnp.argsort(le, stable=True)  # pair ids by expert, absent last
    g = le[order]
    dest = jnp.where(g < count, row_start[g] + jnp.arange(P) - sorted_start[g], M)
    row_pair = jnp.full((M,), P, jnp.int32).at[dest].set(order.astype(jnp.int32), mode="drop")
    pos = jnp.zeros((P,), jnp.int32).at[order].set(dest.astype(jnp.int32)).reshape(T, k)

    xs = _to_rows(x, row_pair, pos)
    gate = gmm(xs, w_gate, sizes, tm=tm)
    up = gmm(xs, w_up, sizes, tm=tm)
    mid = (jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)).astype(x.dtype)
    ys = _from_rows(gmm(mid, w_down, sizes, tm=tm), row_pair, pos)
    y = jnp.einsum("tk,tkd->td", w, ys.astype(jnp.float32)).astype(x.dtype)

    here = jnp.sum(sizes)
    stats = RoutedStats(
        counts=jnp.zeros((total,), jnp.int32).at[idx.reshape(P)].add(1),
        pairs_here=here,
        pairs_absent=P - here,
        pad_rows=tm * jnp.sum(tiles) - here,
        load_max_over_mean=jnp.max(sizes) * count / jnp.maximum(here, 1).astype(jnp.float32),
    )
    return y, stats
