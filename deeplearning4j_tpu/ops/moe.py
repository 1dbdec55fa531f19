"""One chip's share of a sigmoid-routed expert layer.

An expert-parallel deployment gives every chip a contiguous run of the
routed experts, ``held = [first, first + n_held)``, and every chip the
same router.  `moe_ffn` is what one of those chips computes for the rows
it is handed: route each row over ALL ``n_routed`` experts (the router
keeps its published width), keep the assignments that fall on held
experts, sort them by expert, run one grouped product over the held
experts' stacked matrices (`lax.ragged_dot`), weight by the gates, and
add the shared expert, which every chip computes alike.  Dropless: the
grouped product has room for every assignment, so a router that sends
every row to one held expert costs time and never a row.  What the
absent experts would have added is not computed and not stood in for:
the partial sum is the layer's result on this chip, and the sum of all
chips' routed parts plus ONE shared expert is the uncut layer
(`tests/test_latent_serving.py`).

Routing follows the DeepSeek-V3 family's ``noaux_tc`` with one group:
``s = sigmoid(h W_r)``, the top ``k`` of ``s + bias`` are chosen, the
gates are ``scale * s_e / sum_chosen s`` (``norm_topk_prob``).  The router
product runs in f32 at the highest precision — it is ``d x n_routed``,
nothing beside an expert — because a bf16 score flips the top-k boundary
far more often than the rounding of the activations does.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def gated_ffn(h, wg, wu, wd):
    """``(silu(h Wg) * (h Wu)) Wd`` in h's type."""
    dt = h.dtype
    return (jax.nn.silu(h @ wg.astype(dt)) * (h @ wu.astype(dt))) @ wd.astype(dt)


def route(h, router, bias, *, top_k: int, scale: float):
    """Rows h: (n, d) -> (expert ids (n, k) int32, gates (n, k) f32)."""
    with jax.named_scope("moe_route"):
        z = jnp.dot(h.astype(jnp.float32), router.astype(jnp.float32),
                    precision=lax.Precision.HIGHEST)
        s = jax.nn.sigmoid(z)
        _, ids = lax.top_k(s + bias.astype(jnp.float32), top_k)
        chosen = jnp.take_along_axis(s, ids, axis=-1)
        gates = scale * chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    return ids.astype(jnp.int32), gates


def moe_ffn(h, lp, *, first: int, top_k: int, scale: float, count_rows=None,
            row_tile: int | None = None):
    """The held experts' part of the layer plus the shared expert.

    h: (n, d) rows; ``lp``: ``router`` (d, n_routed), ``router_bias``
    (n_routed,), ``experts`` {Wg, Wu: (n_held, d, f); Wd: (n_held, f, d)}
    and ``shared`` {Wg, Wu, Wd}.  Returns ``(y (n, d), counts (n_held +
    1,) int32)``: assignments of the rows ``count_rows`` marks (all rows
    when None) per held expert, and in the last place those that fell on
    experts held elsewhere.

    The sorted assignments go through the grouped product ``row_tile``
    at a time, for as many tiles as the HELD assignments fill — a run-time
    count, so a chip that holds 16 of 256 experts multiplies about a
    sixteenth of the ``n x top_k`` rows and still drops none when they all
    land here.  ``row_tile=None`` is one static pass over all of them: the
    differentiable form, and the cheaper one for a handful of rows."""
    n, d = h.shape
    ex = lp["experts"]
    n_held = ex["Wg"].shape[0]
    wg, wu, wd = (ex[k].astype(h.dtype) for k in ("Wg", "Wu", "Wd"))
    ids, gates = route(h, lp["router"], lp["router_bias"], top_k=top_k,
                       scale=scale)
    with jax.named_scope("moe_experts"):
        local = ids - first                                  # (n, k)
        held = (local >= 0) & (local < n_held)
        group = jnp.where(held, local, n_held).reshape(-1)   # absent: last
        order = jnp.argsort(group, stable=True)              # by expert
        sizes = jnp.bincount(group, length=n_held + 1).astype(jnp.int32)
        gate = jnp.where(held, gates, 0.0).reshape(-1)[order]
        ends = jnp.cumsum(sizes[:n_held])

        def tile(start, order_t, gate_t, routed):
            """Rows [start, start + len) of the sorted assignments."""
            hi = jnp.clip(ends - start, 0, order_t.shape[0])
            sizes_t = hi - jnp.concatenate([jnp.zeros(1, hi.dtype), hi[:-1]])
            src = order_t // top_k                           # row of each
            rows = h[src]
            g = lax.ragged_dot(rows, wg, sizes_t)
            u = lax.ragged_dot(rows, wu, sizes_t)
            y = lax.ragged_dot(jax.nn.silu(g) * u, wd, sizes_t)
            # rows past the held groups belong to no group: whatever the
            # grouped product left there is dropped with their zero gate
            y = jnp.where(gate_t[:, None] > 0.0,
                          y.astype(jnp.float32) * gate_t[:, None], 0.0)
            return routed.at[src].add(y)

        routed = jnp.zeros((n, d), jnp.float32)
        m = n * top_k
        if row_tile is None or m <= row_tile:
            routed = tile(0, order, gate, routed)
        else:
            pad = -m % row_tile
            order_p = jnp.concatenate([order, jnp.zeros(pad, order.dtype)])
            gate_p = jnp.concatenate([gate, jnp.zeros(pad, gate.dtype)])

            def body(i, routed):
                start = i * row_tile
                return tile(
                    start, lax.dynamic_slice(order_p, (start,), (row_tile,)),
                    lax.dynamic_slice(gate_p, (start,), (row_tile,)), routed)

            routed = lax.fori_loop(0, -(-ends[-1] // row_tile), body, routed)
        sh = lp["shared"]
        out = routed.astype(h.dtype) + gated_ffn(h, sh["Wg"], sh["Wu"],
                                                  sh["Wd"])
    if count_rows is None:
        counts = sizes
    else:
        counted = jnp.where(count_rows[:, None], group.reshape(n, top_k),
                            n_held + 1)
        counts = jnp.bincount(counted.reshape(-1),
                              length=n_held + 2)[:n_held + 1].astype(jnp.int32)
    return out, counts
