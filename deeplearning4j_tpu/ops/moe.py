"""One chip's share of a sigmoid-routed expert layer.

An expert-parallel deployment gives every chip a contiguous run of the
routed experts, ``held = [first, first + n_held)``, and every chip the
same router.  `moe_ffn` is what one of those chips computes for the rows
it is handed: route each row over ALL ``n_routed`` experts (the router
keeps its published width), keep the assignments that fall on held
experts, sort them by expert, run one grouped product over the held
experts' stacked matrices (`lax.ragged_dot`; a decode step's few rows on a
TPU: `held_gmm`, which fetches only the experts that have rows), weight
by the gates, and add the shared expert, which every chip computes
alike.  Dropless: the grouped product has room for every assignment, so a
router that sends every row to one held expert costs time and never a
row.  What the
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

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.ops.held_expert_matmul import held_gmm


def gated_ffn(h, wg, wu, wd):
    """``(silu(h Wg) * (h Wu)) Wd`` in h's type."""
    dt = h.dtype
    return (jax.nn.silu(h @ wg.astype(dt)) * (h @ wu.astype(dt))) @ wd.astype(dt)


class PolyNorm(NamedTuple):
    """The ``poly_norm`` activation (Motif): ``scale * (w0 N(g^3) + w1
    N(g^2) + w2 N(g) + clamp(b, +-clamp))`` with ``N`` the RMS
    normalisation over the row's width, in f32.  An FFN that uses it holds
    ``poly_w`` (3,) and ``poly_b`` () beside its matrices (an expert stack:
    one of each per expert)."""

    scale: float
    clamp: float
    eps: float

    def __call__(self, g, w, b):
        """g (m, f) f32; w (m, 3) or (3,); b (m,) or ()."""
        def norm(v):
            return v * lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + self.eps)

        w, b = w.astype(jnp.float32), b.astype(jnp.float32)
        return self.scale * (w[..., 0:1] * norm(g * g * g)
                             + w[..., 1:2] * norm(g * g)
                             + w[..., 2:3] * norm(g)
                             + jnp.clip(b, -self.clamp, self.clamp)[..., None])


def act_ffn(h, f, act: PolyNorm):
    """``(act(h Wg) * (h Wu)) Wd`` of one FFN ``f`` with its own activation
    weights; the products take h's type and accumulate in f32, and what
    lies between them is f32 -> (n, d) f32."""
    dt = h.dtype
    mm = lambda a, w: jnp.dot(a.astype(dt), w.astype(dt),
                              preferred_element_type=jnp.float32)
    g = act(mm(h, f["Wg"]), f["poly_w"], f["poly_b"])
    return mm(g * mm(h, f["Wu"]), f["Wd"])


def _on_tpu() -> bool:
    from deeplearning4j_tpu.runtime.backend import backend

    return backend().is_tpu


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
            row_tile: int | None = None, act: PolyNorm | None = None):
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
    differentiable form, and the cheaper one for a handful of rows.

    ``act`` None: the SiLU experts, y in h's type.  A `PolyNorm`: each
    expert's own activation weights on its rows, the gate and up products
    accumulated in f32 and the activation taken in f32; y is f32.

    The engine's static pass (``row_tile`` given, ``n x top_k`` within it:
    a decode step's handful of rows, most held experts without one) runs
    its three products through `held_gmm` on a TPU, which fetches only the
    held experts that have rows; ``fetched`` is their number (int32), None
    from the tiled pass, whose tiles give rows to every held expert."""
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

        m = n * top_k
        static = row_tile is None or m <= row_tile
        pad = 0 if static else -m % row_tile
        grouped = (held_gmm if static and row_tile is not None and _on_tpu()
                   else lax.ragged_dot)
        if act is not None:
            # the held expert of each sorted row (past the held groups: any)
            expert_of = jnp.pad(jnp.minimum(group[order], n_held - 1),
                                (0, pad))

        def tile(start, order_t, gate_t, routed):
            """Rows [start, start + len) of the sorted assignments."""
            hi = jnp.clip(ends - start, 0, order_t.shape[0])
            sizes_t = hi - jnp.concatenate([jnp.zeros(1, hi.dtype), hi[:-1]])
            src = order_t // top_k                           # row of each
            rows = h[src]
            if act is None:
                g = grouped(rows, wg, sizes_t)
                u = grouped(rows, wu, sizes_t)
                y = grouped(jax.nn.silu(g) * u, wd, sizes_t)
            else:
                e = lax.dynamic_slice_in_dim(expert_of, start,
                                             order_t.shape[0])
                f32 = dict(preferred_element_type=jnp.float32)
                g = act(grouped(rows, wg, sizes_t, **f32),
                        ex["poly_w"][e], ex["poly_b"][e])
                u = grouped(rows, wu, sizes_t, **f32)
                y = grouped((g * u).astype(h.dtype), wd, sizes_t, **f32)
            # rows past the held groups belong to no group: whatever the
            # grouped product left there is dropped with their zero gate
            y = jnp.where(gate_t[:, None] > 0.0,
                          y.astype(jnp.float32) * gate_t[:, None], 0.0)
            return routed.at[src].add(y)

        routed = jnp.zeros((n, d), jnp.float32)
        fetched = None
        if static:
            routed = tile(0, order, gate, routed)
            fetched = jnp.count_nonzero(sizes[:n_held]).astype(jnp.int32)
        else:
            order_p = jnp.concatenate([order, jnp.zeros(pad, order.dtype)])
            gate_p = jnp.concatenate([gate, jnp.zeros(pad, gate.dtype)])

            def body(i, routed):
                start = i * row_tile
                return tile(
                    start, lax.dynamic_slice(order_p, (start,), (row_tile,)),
                    lax.dynamic_slice(gate_p, (start,), (row_tile,)), routed)

            routed = lax.fori_loop(0, -(-ends[-1] // row_tile), body, routed)
        sh = lp["shared"]
        if act is None:
            out = routed.astype(h.dtype) + gated_ffn(h, sh["Wg"], sh["Wu"],
                                                      sh["Wd"])
        else:
            out = routed + act_ffn(h, sh, act)
    if count_rows is None:
        counts = sizes
    else:
        counted = jnp.where(count_rows[:, None], group.reshape(n, top_k),
                            n_held + 1)
        counts = jnp.bincount(counted.reshape(-1),
                              length=n_held + 2)[:n_held + 1].astype(jnp.int32)
    return out, counts, fetched
