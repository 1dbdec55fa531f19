"""Pipeline parallelism — GPipe-style microbatch pipeline over a mesh axis.

Absent from the reference (SURVEY.md §2.3: "Pipeline parallel: NO");
first-class here.  All `pipe`-axis devices run the same shard_map program:
each holds ONE stage's params; activations flow stage-to-stage via
lax.ppermute.  The schedule runs n_micro + n_stages - 1 ticks (the classic
GPipe bubble); every tick each device applies its stage to whatever just
arrived and passes the result on.  The whole schedule is one lax.scan —
differentiable end-to-end (ppermute transposes to the reverse permute), so
jax.grad through `pipeline_apply` IS the backward pipeline.

The stage fn must be shape-preserving in its pipelined activation
(classic transformer-block stacks) — inter-stage reshapes belong inside a
stage.
"""

from __future__ import annotations

from collections.abc import Callable

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.runtime.mesh import shard_map


def pipeline_apply(
    stage_fn: Callable,
    stage_params,
    x_micro: jax.Array,
    *,
    axis: str,
):
    """Run the pipelined stack under shard_map.

    stage_fn(params, x) -> y, applied by every device to its own stage.
    stage_params: the LOCAL stage's params (leading stage dim already
    sharded away by shard_map in_specs).
    x_micro: (n_micro, B_micro, ...) microbatches — full copy on stage 0's
    view (replicated in_spec); only stage 0 feeds them in.
    Returns (n_micro, B_micro, ...) outputs valid on the LAST stage
    (read them with an out_spec that takes the last pipe shard).
    """
    n_stages = lax.axis_size(axis)
    stage = lax.axis_index(axis)
    n_micro = x_micro.shape[0]
    total = n_micro + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    buf_shape = x_micro.shape[1:]
    state = jnp.zeros(buf_shape, x_micro.dtype)
    outputs = jnp.zeros((n_micro,) + buf_shape, x_micro.dtype)

    def tick(carry, t):
        state, outputs = carry
        # stage 0 ingests microbatch t (while t < n_micro)
        feed = x_micro[jnp.minimum(t, n_micro - 1)]
        state = jnp.where(stage == 0, feed, state)
        y = stage_fn(stage_params, state)
        # last stage writes its result for microbatch (t - n_stages + 1)
        out_idx = t - (n_stages - 1)
        valid = (stage == n_stages - 1) & (out_idx >= 0)
        outputs = lax.cond(
            valid,
            lambda o: lax.dynamic_update_index_in_dim(
                o, y, jnp.maximum(out_idx, 0), axis=0
            ),
            lambda o: o,
            outputs,
        )
        # pass activations to the next stage
        state = lax.ppermute(y, axis, perm)
        return (state, outputs), None

    (_, outputs), _ = lax.scan(tick, (state, outputs), jnp.arange(total))
    # only the last stage holds real outputs; psum the masked buffers so
    # every device returns the same tensor (enables replicated out_specs
    # and keeps the consumer oblivious to which shard "owns" the result)
    outputs = lax.psum(
        jnp.where(stage == n_stages - 1, outputs, jnp.zeros_like(outputs)), axis
    )
    return outputs


def pipeline_train_1f1b(
    stage_fn: Callable,
    stage_params,
    x_micro: jax.Array,
    loss_grad_fn: Callable,
    *,
    axis: str,
):
    """One-forward-one-backward (1F1B) training schedule in a single scan.

    GPipe (`pipeline_apply` + jax.grad) lets XLA transpose the forward
    scan, which stashes one stage-input per microbatch — O(n_micro)
    activation memory per device.  1F1B interleaves each microbatch's
    backward as soon as the last stage finishes its forward, so a stage
    input only lives for the ticks its backward takes to arrive: the
    stash here is a static ring of 2*n_stages-1 slots, O(n_stages) —
    microbatch count no longer affects activation memory, which is what
    makes deep-pipeline long-batch training fit in HBM.

    Schedule (stage s, microbatch m, k stages):
      forward  of m on s at tick  m + s
      loss+∂   of m on k-1 at tick m + k - 1  (fwd then bwd, same tick)
      backward of m on s at tick  m + 2(k-1) - s
    Total ticks: n_micro + 2k - 2.  Both the +1 (activations) and -1
    (cotangents) ppermute rings run every tick; each device does at most
    one forward and one backward compute per tick — the 1F1B steady state.

    Args:
      stage_fn(params, h) -> h' — the stage transform (shape-preserving).
      stage_params — the LOCAL stage's params (sharded by shard_map).
      x_micro — (n_micro, B_micro, ...) microbatches (stage 0 feeds them).
      loss_grad_fn(y, m) -> (loss_m, dL/dy[, extra_grads]) — evaluated on
        the LAST stage's output for microbatch index m (close over
        labels).  The optional third element is a pytree of additional
        gradients (e.g. the post-segment head's param grads when the loss
        runs through layers after the pipelined segment); it is summed
        over microbatches on the last stage and psum-replicated.
    Returns (mean_loss, stage_grads, dx_micro[, extra_grads]): loss
    averaged over microbatches (same on all devices), the LOCAL stage's
    param gradients (sum over microbatches), dL/dx per microbatch (valid
    on every device via psum — feeds backprop of layers before the
    segment), and — iff loss_grad_fn returns a third element — the
    accumulated extra grads, averaged over microbatches.
    """
    n_stages = lax.axis_size(axis)
    stage = lax.axis_index(axis)
    n_micro = x_micro.shape[0]
    total = n_micro + 2 * n_stages - 2
    stash_n = 2 * n_stages - 1
    fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    bwd_perm = [((i + 1) % n_stages, i) for i in range(n_stages)]

    buf_shape = x_micro.shape[1:]
    zero_buf = jnp.zeros(buf_shape, x_micro.dtype)
    # does loss_grad_fn carry extra (post-segment) grads?
    probe = jax.eval_shape(
        lambda y: loss_grad_fn(y, 0), jax.ShapeDtypeStruct(buf_shape, x_micro.dtype)
    )
    has_extra = len(probe) == 3
    carry = dict(
        fwd=zero_buf,                                  # activation arriving
        bwd=zero_buf,                                  # cotangent arriving
        stash=jnp.zeros((stash_n,) + buf_shape, x_micro.dtype),
        grads=jax.tree.map(jnp.zeros_like, stage_params),
        loss=jnp.zeros((), jnp.float32),
        dx=jnp.zeros((n_micro,) + buf_shape, x_micro.dtype),
        extra=(
            jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), probe[2])
            if has_extra else ()
        ),
    )

    def tick(c, t):
        # ---- forward: microbatch m_f = t - stage ----
        m_f = t - stage
        fwd_valid = (m_f >= 0) & (m_f < n_micro)
        feed = x_micro[jnp.clip(m_f, 0, n_micro - 1)]
        h_in = jnp.where(stage == 0, feed, c["fwd"])
        stash = lax.dynamic_update_index_in_dim(
            c["stash"], jnp.where(fwd_valid, h_in, 0.0),
            jnp.clip(m_f, 0, n_micro - 1) % stash_n, axis=0,
        )
        stash = jnp.where(fwd_valid, stash, c["stash"])
        y = stage_fn(stage_params, h_in)

        # ---- last stage: loss + seed cotangent, same tick ----
        lg = loss_grad_fn(y, jnp.clip(m_f, 0, n_micro - 1))
        loss_m, g_seed = lg[0], lg[1]
        is_last = stage == n_stages - 1
        seed_now = is_last & fwd_valid
        loss = c["loss"] + jnp.where(seed_now, loss_m, 0.0)
        extra = c["extra"]
        if has_extra:
            live_e = jnp.where(seed_now, 1.0, 0.0)
            extra = jax.tree.map(
                lambda a, d: a + d.astype(a.dtype) * live_e, extra, lg[2]
            )

        # ---- backward: microbatch m_b = t - 2(k-1) + stage ----
        m_b = t - 2 * (n_stages - 1) + stage
        bwd_valid = (m_b >= 0) & (m_b < n_micro)
        g_in = jnp.where(seed_now, g_seed.astype(x_micro.dtype), c["bwd"])
        h_saved = stash[jnp.clip(m_b, 0, n_micro - 1) % stash_n]
        _, vjp = jax.vjp(stage_fn, stage_params, h_saved)
        dp, dh = vjp(g_in)
        live = jnp.where(bwd_valid, 1.0, 0.0).astype(x_micro.dtype)
        grads = jax.tree.map(
            lambda a, d: a + d.astype(a.dtype) * live, c["grads"], dp
        )
        # stage 0's dh is dL/dx for microbatch m_b
        dx = lax.dynamic_update_index_in_dim(
            c["dx"],
            jnp.where((stage == 0) & bwd_valid, dh, 0.0),
            jnp.clip(m_b, 0, n_micro - 1),
            axis=0,
        )

        return dict(
            fwd=lax.ppermute(y, axis, fwd_perm),
            bwd=lax.ppermute(dh * live, axis, bwd_perm),
            stash=stash,
            grads=grads,
            loss=loss,
            dx=dx,
            extra=extra,
        ), None

    c, _ = lax.scan(tick, carry, jnp.arange(total))
    mean_loss = lax.psum(
        jnp.where(stage == n_stages - 1, c["loss"], 0.0), axis
    ) / n_micro
    # objective is the MEAN over microbatches: scale both grad outputs
    dx_micro = lax.psum(c["dx"], axis) / n_micro
    grads = jax.tree.map(lambda a: a / n_micro, c["grads"])
    if has_extra:
        # accumulated on the last stage only; replicate and average
        extra = jax.tree.map(
            lambda a: lax.psum(a, axis) / n_micro, c["extra"]
        )
        return mean_loss, grads, dx_micro, extra
    return mean_loss, grads, dx_micro


def split_microbatches(x: jax.Array, n_micro: int) -> jax.Array:
    """(B, ...) -> (n_micro, B/n_micro, ...)."""
    b = x.shape[0]
    if b % n_micro:
        raise ValueError(f"batch {b} not divisible into {n_micro} microbatches")
    return x.reshape((n_micro, b // n_micro) + x.shape[1:])


def merge_microbatches(y: jax.Array) -> jax.Array:
    return y.reshape((-1,) + y.shape[2:])


# ---------------------------------------------------------------------------
# Model integration: pipeline a SequentialModel's repeated-block segment
# ---------------------------------------------------------------------------

import dataclasses as _dataclasses


@_dataclasses.dataclass(frozen=True)
class PipelinePlan:
    """How a sequential layer stack maps onto the pipe axis.

    The pipelined segment is a contiguous run of IDENTICALLY-configured,
    shape-preserving, stateless blocks (the transformer-stack shape PP
    exists for), n_blocks = k stages x m blocks each.  Layers before/after
    the segment run replicated on every pipe device (embeddings and output
    heads are cheap relative to the block stack).
    """

    start: int                 # first layer index in the segment
    end: int                   # one past the last layer index
    block_names: tuple[str, ...]
    block_config: object       # the shared LayerConfig (names differ only)
    k: int                     # pipeline stages
    n_micro: int               # microbatches per global batch


def plan_sequential_pipeline(layers, params, itypes, k: int,
                             n_micro: int = 0, net_state=None) -> PipelinePlan:
    """Choose the pipelined segment of a sequential stack, or raise with an
    actionable reason.  Requirements per block: identical config (except
    name), identical param tree (structure+shapes), input type preserved,
    no dropout (rng is not threaded through the pipeline scan), no state
    (BatchNorm running stats cannot live inside the ppermute loop)."""

    def strip(cfg):
        return _dataclasses.replace(cfg, name="")

    def shapes(name):
        return jax.tree.map(lambda a: (a.shape, str(a.dtype)), params.get(name, {}))

    best = (0, 0)
    i = 0
    while i < len(layers):
        j = i
        while (
            j + 1 < len(layers)
            and type(layers[j + 1]) is type(layers[i])
            and strip(layers[j + 1]) == strip(layers[i])
            and shapes(layers[j + 1].name) == shapes(layers[i].name)
            and itypes[j + 1] == itypes[i]
        ):
            j += 1
        # run is [i, j]; shape-preserving check: next layer's input type
        # (== run's output type) must equal the run's input type
        run_ok = j > i and (
            (j + 1 < len(itypes) and itypes[j + 1] == itypes[i])
            or j + 1 == len(itypes)
        )
        if run_ok and (j + 1 - i) > (best[1] - best[0]):
            best = (i, j + 1)
        i = j + 1
    start, end = best
    n_blocks = end - start
    if n_blocks < k:
        raise ValueError(
            f"pipeline parallelism over {k} stages needs a contiguous run of "
            f">= {k} identical shape-preserving layers; longest found is "
            f"{n_blocks}. Pipeline the repeated-block segment of a "
            "transformer-style stack, or drop the pipe axis."
        )
    if n_blocks % k:
        raise ValueError(
            f"pipelined segment has {n_blocks} blocks, not divisible into "
            f"{k} stages"
        )
    seg = layers[start:end]
    for l in seg:
        if getattr(l, "dropout_rate", None):
            raise ValueError(
                f"layer {l.name!r}: dropout inside the pipelined segment is "
                "not supported (per-block rng is not threaded through the "
                "pipeline scan)"
            )
        if net_state and net_state.get(l.name):
            raise ValueError(
                f"layer {l.name!r}: stateful layers (BatchNorm running "
                "stats etc.) cannot be pipelined — state updates cannot "
                "live inside the ppermute schedule"
            )
    # reject blocks that EMIT state/aux during training even when they hold
    # none at rest (MoELayer's load-balancing aux loss): the stage fn
    # discards apply()'s state channel, which would silently drop it
    rep = seg[0]
    it = itypes[start]
    if it.kind == "rnn":
        t = it.shape[0] if it.shape[0] > 0 else 4
        x_spec = jax.ShapeDtypeStruct((2, t, it.shape[1]), jnp.float32)
    else:
        x_spec = jax.ShapeDtypeStruct((2,) + tuple(it.shape), jnp.float32)
    _, emitted = jax.eval_shape(
        lambda p, x: rep.apply(p, {}, x, training=True, rng=None),
        params.get(rep.name, {}), x_spec,
    )
    if emitted:
        raise ValueError(
            f"layer {rep.name!r} ({type(rep).__name__}) emits state/aux "
            f"during training ({sorted(emitted)}); the pipeline schedule "
            "cannot carry it — keep such layers outside the pipelined "
            "segment"
        )
    return PipelinePlan(
        start=start,
        end=end,
        block_names=tuple(l.name for l in seg),
        block_config=seg[0],
        k=k,
        n_micro=n_micro or 2 * k,
    )


def run_pipelined_segment(plan: PipelinePlan, params, x, *, mesh, axis: str,
                          training: bool):
    """Execute the planned segment: stack block params, GPipe them over the
    pipe mesh axis, return the merged activations.

    Block params stay replicated in HBM; the in-jit stack is annotated
    P(pipe) so each device materializes only its stage's slice after GSPMD
    partitioning.  Stages are rematerialized (jax.checkpoint) — the GPipe
    memory model: activations of in-flight microbatches only.
    """
    from jax.sharding import PartitionSpec as P

    k, m = plan.k, len(plan.block_names) // plan.k
    cfg = plan.block_config
    stacked = jax.tree.map(
        lambda *xs: jnp.stack(xs), *[params[n] for n in plan.block_names]
    )
    stacked = jax.tree.map(lambda a: a.reshape((k, m) + a.shape[1:]), stacked)

    @jax.checkpoint
    def stage_fn(sp, h):
        def body(h, p):
            y, _ = cfg.apply(p, {}, h, training=training, rng=None)
            return y, None
        h, _ = lax.scan(body, h, sp)
        return h

    x_micro = split_microbatches(x, plan.n_micro)
    out = shard_map(
        lambda sp, xm: pipeline_apply(
            stage_fn, jax.tree.map(lambda a: a[0], sp), xm, axis=axis
        ),
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        axis_names={axis},
        check_vma=False,
    )(stacked, x_micro)
    return merge_microbatches(out)
