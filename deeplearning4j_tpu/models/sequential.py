"""SequentialModel — the MultiLayerNetwork role, compiled whole-step.

The reference's MultiLayerNetwork.fit() interprets the layer stack op-by-op
across JNI per minibatch (SURVEY.md §3.1: feedForwardToLayer →
calcBackpropGradients → updater, one native call per op).  Here the ENTIRE
training iteration — forward, loss (+regularization), backward, gradient
clipping, updater, BN-stat update — is ONE jit-compiled XLA computation
with donated param/opt-state buffers: zero host round-trips inside a step,
everything resident in HBM, elementwise work fused into the matmuls.

This is the north-star differentiator named in BASELINE.json.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.data.iterator import DataSetIterator, NumpyDataSetIterator
from deeplearning4j_tpu.models._cast import entry_cast
from deeplearning4j_tpu.models.model import Model
from deeplearning4j_tpu.nn.activations import Activation
from deeplearning4j_tpu.nn.conf.input_type import InputType
from deeplearning4j_tpu.nn.conf.neural_net_configuration import SequentialConfiguration
from deeplearning4j_tpu.nn.losses import Loss, compute as compute_loss
from deeplearning4j_tpu.nn.updaters import with_gradient_clipping
from deeplearning4j_tpu.models._common import (
    mask_frozen_tx,
    pop_aux_losses,
    regularization_loss,
    resolve_output_spec,
)
from deeplearning4j_tpu.runtime.backend import backend
from deeplearning4j_tpu.runtime.rng import SeedStream


def _as_iterator(data, batch_size: int | None) -> DataSetIterator:
    if isinstance(data, DataSetIterator):
        return data
    if isinstance(data, DataSet):
        from deeplearning4j_tpu.data.iterator import ExistingDataSetIterator

        if batch_size:
            return ExistingDataSetIterator(data.split_batches(batch_size))
        return ExistingDataSetIterator([data])
    if isinstance(data, tuple) and len(data) == 2:
        return NumpyDataSetIterator(data[0], data[1], batch_size or 32)
    if isinstance(data, list) and data and all(
        isinstance(b, DataSet) for b in data
    ):
        # non-empty only: fit([]) must stay a loud error, not silent
        # zero-batch "training"
        from deeplearning4j_tpu.data.iterator import ExistingDataSetIterator

        return ExistingDataSetIterator(data)
    raise TypeError(f"cannot interpret {type(data)} as training data")


class SequentialModel(Model):
    """Sequential layer stack with whole-step-compiled fit()."""

    def __init__(self, conf: SequentialConfiguration):
        super().__init__()
        self.conf = conf
        self._itypes = conf.layer_input_types()
        self._flatten_before = conf.flatten_flags()
        self._loss, self._out_activation, self._fused_loss = self._resolve_output()
        self._bf16 = (
            conf.bf16_compute if conf.bf16_compute is not None else backend().is_tpu
        )
        self._tx = with_gradient_clipping(
            conf.updater.to_optax(conf.steps_per_epoch),
            conf.gradient_clip_value,
            conf.gradient_clip_norm,
        )
        self._tx = self._mask_frozen(self._tx)
        self._stream = SeedStream(conf.seed)
        self._step_fns: dict[Any, Any] = {}
        self._rnn_runs = self._find_rnn_runs()

    def _find_rnn_runs(self) -> dict[int, int]:
        """Maximal runs (start index -> length) of >=2 consecutive
        recurrent layers that can execute as ONE fused time scan: no
        dropout on non-first members (fused stacks apply only the first
        layer's dropout) and no flatten boundary inside the run."""
        from deeplearning4j_tpu.nn.conf.recurrent import RecurrentLayerConfig

        runs: dict[int, int] = {}
        layers = self.conf.layers
        i = 0
        while i < len(layers):
            if not isinstance(layers[i], RecurrentLayerConfig):
                i += 1
                continue
            j = i + 1
            while (
                j < len(layers)
                and isinstance(layers[j], RecurrentLayerConfig)
                and not layers[j].dropout_rate
                and not self._flatten_before[j]
            ):
                j += 1
            if j - i >= 2:
                runs[i] = j - i
            i = j
        return runs

    # -- construction ------------------------------------------------------
    def _resolve_output(self) -> tuple[Loss, Activation, bool]:
        last = self.conf.layers[-1]
        # layers with their own loss function (e.g. Yolo2OutputLayer) bypass
        # the enum-based loss dispatch entirely; _with_params variants
        # (CenterLossOutputLayer) additionally see their own param dict
        self._custom_loss_layer = None
        if hasattr(last, "compute_loss_with_params"):
            self._custom_loss = last.compute_loss_with_params
            self._custom_loss_layer = last.name
            return Loss.MSE, Activation.IDENTITY, False
        if hasattr(last, "compute_loss"):
            self._custom_loss = last.compute_loss
            return Loss.MSE, Activation.IDENTITY, False
        self._custom_loss = None
        if not hasattr(last, "loss"):
            raise ValueError(
                "last layer must be an OutputLayer, RnnOutputLayer or "
                "LossLayer declaring the loss"
            )
        return resolve_output_spec(last)

    def _mask_frozen(self, tx):
        return mask_frozen_tx(tx, {l.name for l in self.conf.layers if l.frozen})

    def init(self) -> "SequentialModel":
        params, state = {}, {}
        for layer, itype in zip(self.conf.layers, self._itypes):
            p, s = layer.init(self._stream.key(f"init/{layer.name}"), itype)
            if p:
                params[layer.name] = p
            if s:
                state[layer.name] = s
        self.params = params
        self.net_state = state
        self.opt_state = self._tx.init(params)
        return self

    # -- pure forward (traced) --------------------------------------------
    def _forward(
        self, params, net_state, x, *, training: bool, rng, fmask=None, carries=None
    ):
        """carries: {rnn_layer_name: carry} initial RNN states (TBPTT /
        streaming inference); when given, the third return value holds the
        final carries.  fmask: (B, T) sequence mask threaded into
        mask-aware layers until the time axis collapses."""
        from deeplearning4j_tpu.nn.conf.recurrent import RecurrentLayerConfig

        x = entry_cast(x, self._bf16)
        new_state, new_carries = {}, {}
        mask = fmask
        plan = self._active_pipeline_plan()
        skip = set()
        if plan is not None:
            skip = set(range(plan.start, plan.end))
        fuse_until = -1
        for i, layer in enumerate(self.conf.layers):
            if i < fuse_until:
                continue
            if i in skip:
                if i == plan.start:
                    from deeplearning4j_tpu.parallel.pipeline import (
                        run_pipelined_segment,
                    )
                    from deeplearning4j_tpu.runtime.mesh import PIPE_AXIS, active_mesh

                    if mask is not None:
                        raise ValueError(
                            "sequence masks are not supported through a "
                            "pipelined segment yet; drop the pipe axis or "
                            "the mask"
                        )
                    x = run_pipelined_segment(
                        plan, params, x, mesh=active_mesh(), axis=PIPE_AXIS,
                        training=training,
                    )
                continue
            if self._flatten_before[i]:
                x = x.reshape(x.shape[0], -1)
            run = self._rnn_runs.get(i, 0)
            if run >= 2 and not any((i + k) in skip for k in range(run)):
                from deeplearning4j_tpu.nn.conf.recurrent import fused_rnn_scan

                lys = self.conf.layers[i : i + run]
                cs = []
                for l in lys:
                    c = carries.get(l.name) if carries is not None else None
                    cs.append(c if c is not None else l.init_carry(x.shape[0], x.dtype))
                x, fins = fused_rnn_scan(
                    lys,
                    [params.get(l.name, {}) for l in lys],
                    x,
                    cs,
                    mask,
                    training=training,
                    rng=jax.random.fold_in(rng, i) if rng is not None else None,
                )
                if carries is not None:
                    for l, fc in zip(lys, fins):
                        new_carries[l.name] = fc
                fuse_until = i + run
                continue
            lp = params.get(layer.name, {})
            ls = net_state.get(layer.name, {})
            lrng = jax.random.fold_in(rng, i) if rng is not None else None
            if carries is not None and isinstance(layer, RecurrentLayerConfig):
                carry = carries.get(layer.name)
                if carry is None:
                    carry = layer.init_carry(x.shape[0], x.dtype)
                x, fin = layer.apply_with_carry(
                    lp, x, carry, mask=mask, training=training, rng=lrng
                )
                new_carries[layer.name] = fin
                ns = {}
            elif layer.ACCEPTS_MASK:
                x, ns = layer.apply(
                    lp, ls, x, training=training, rng=lrng, mask=mask
                )
            else:
                x, ns = layer.apply(lp, ls, x, training=training, rng=lrng)
            if ns:
                new_state[layer.name] = ns
            # once the time axis collapses (RNN -> FF), the mask is spent
            if self._itypes[i].kind == "rnn" and layer.output_type(self._itypes[i]).kind != "rnn":
                mask = None
        if carries is not None:
            return x, new_state, new_carries
        return x, new_state

    def _forward_range(self, params, net_state, x, lo: int, hi: int, *,
                       training: bool, rng):
        """Forward of layers [lo, hi) only — the pre/post-segment pieces of
        the 1F1B pipeline step (no masks/carries: the pipelined path
        rejects them before tracing).  bf16 cast applies at the network
        entry (lo == 0)."""
        if lo == 0:
            x = entry_cast(x, self._bf16)
        new_state = {}
        for i in range(lo, hi):
            layer = self.conf.layers[i]
            if self._flatten_before[i]:
                x = x.reshape(x.shape[0], -1)
            lrng = jax.random.fold_in(rng, i) if rng is not None else None
            x, ns = layer.apply(
                params.get(layer.name, {}), net_state.get(layer.name, {}),
                x, training=training, rng=lrng,
            )
            if ns:
                new_state[layer.name] = ns
        return x, new_state

    def _get_step_fn_1f1b(self):
        """The 1F1B pipeline training step: pre-segment vjp + interleaved-
        backward pipeline over the segment + post-segment (head) grads
        accumulated on the last stage — one compiled program.

        vs GPipe (run_pipelined_segment under jax.grad): identical math,
        but the activation stash is a static 2*pipe-1 ring instead of
        O(n_micro), so microbatch count no longer affects HBM.
        Limitations (documented): no masks/TBPTT, and state/aux emitted by
        POST-segment layers inside the per-microbatch loss is discarded
        (plan_sequential_pipeline already keeps such layers out of the
        segment itself)."""
        key = ("train_1f1b",)
        if key not in self._step_fns:
            from jax.sharding import PartitionSpec as P
            from deeplearning4j_tpu.parallel.pipeline import (
                pipeline_train_1f1b,
                split_microbatches,
            )
            from deeplearning4j_tpu.runtime.mesh import PIPE_AXIS as _PA, shard_map

            plan = self._pipeline_plan
            mesh = self._mesh
            n_layers = len(self.conf.layers)
            k, m = plan.k, len(plan.block_names) // plan.k
            cfg = plan.block_config

            @partial(jax.jit, donate_argnums=(0, 1, 2))
            def step(params, opt_state, net_state, step_i, features, labels):
                rng = SeedStream.fold(self._stream.root, step_i)
                p_pre = {
                    n: params[n]
                    for n in (l.name for l in self.conf.layers[: plan.start])
                    if n in params
                }
                p_post = {
                    n: params[n]
                    for n in (l.name for l in self.conf.layers[plan.end:])
                    if n in params
                }

                # ---- pre-segment forward; vjp saved for the pipeline's dx
                def f_pre(pp, x):
                    return self._forward_range(
                        pp, net_state, x, 0, plan.start, training=True, rng=rng
                    )

                x1, vjp_pre, st_pre = jax.vjp(f_pre, p_pre, features,
                                              has_aux=True)

                # ---- segment params stacked (k, m, ...), stage-major
                stacked = jax.tree.map(
                    lambda *xs: jnp.stack(xs),
                    *[params[n] for n in plan.block_names],
                )
                stacked = jax.tree.map(
                    lambda a: a.reshape((k, m) + a.shape[1:]), stacked
                )

                @jax.checkpoint
                def stage_fn(sp, h):
                    def body(h, p):
                        y, _ = cfg.apply(p, {}, h, training=True, rng=None)
                        return y, None
                    h, _ = jax.lax.scan(body, h, sp)
                    return h

                x_micro = split_microbatches(x1, plan.n_micro)
                labels_micro = split_microbatches(labels, plan.n_micro)

                def inner(sp, xm, lm):
                    sp_local = jax.tree.map(lambda a: a[0], sp)

                    def loss_grad(y, mi):
                        lbl = lm[mi]

                        def post_loss(pp, yy):
                            out, _ = self._forward_range(
                                pp, net_state, yy, plan.end, n_layers,
                                training=True, rng=rng,
                            )
                            if self._custom_loss is not None:
                                return self._data_loss_custom(
                                    {**pp}, out, lbl, None
                                )
                            if not self._fused_loss:
                                out = self._out_activation(
                                    out.astype(jnp.float32)
                                )
                            return compute_loss(
                                self._loss, out, lbl, None,
                                from_logits=self._fused_loss,
                            )

                        loss_m, (dpost, dy) = jax.value_and_grad(
                            post_loss, argnums=(0, 1)
                        )(p_post, y)
                        return loss_m, dy, dpost

                    return pipeline_train_1f1b(
                        stage_fn, sp_local, xm, loss_grad,
                        axis=_PA,
                    )

                loss, seg_grads, dx_micro, post_grads = shard_map(
                    inner,
                    mesh=mesh,
                    in_specs=(P(_PA), P(), P()),
                    out_specs=(P(), P(_PA), P(), P()),
                    axis_names={_PA},
                    check_vma=False,
                )(stacked, x_micro, labels_micro)

                # ---- assemble the full gradient tree
                dx = dx_micro.reshape((-1,) + dx_micro.shape[2:])
                pre_grads, _dfeat = vjp_pre(dx)
                # shard_map returned (k*m, ...) leaves in block order
                grads = dict(pre_grads)
                for bi, name in enumerate(plan.block_names):
                    grads[name] = jax.tree.map(lambda a, _b=bi: a[_b], seg_grads)
                grads.update(post_grads)
                # regularization is param-local; add its gradient directly
                reg_grads = jax.grad(self._reg_loss)(params)
                grads = jax.tree.map(
                    lambda g, r: g + r.astype(g.dtype), grads, reg_grads
                )
                loss = loss + self._reg_loss(params)

                params, opt_state = self._apply_grads(params, opt_state, grads)
                merged_state = {**net_state, **st_pre}
                return params, opt_state, merged_state, loss

            self._step_fns[key] = self._register_program(key, step)
        return self._step_fns[key]

    def _run_step_1f1b(self, batch: DataSet) -> None:
        from deeplearning4j_tpu.parallel.data_parallel import place_batch
        from deeplearning4j_tpu.runtime.crash import oom_report_scope
        from deeplearning4j_tpu.runtime.mesh import active_mesh_scope

        if batch.labels_mask is not None or batch.features_mask is not None:
            raise ValueError(
                "masks are not supported through the 1f1b pipeline schedule; "
                "drop the masks or use schedule='gpipe' without masks"
            )
        step = self._get_step_fn_1f1b()
        with self._observe_step() as obs:
            with oom_report_scope(), active_mesh_scope(self._mesh):
                with obs.phase("host_stage"):
                    feats = place_batch(self, batch.features)
                    labs = place_batch(self, batch.labels, is_label=True)
                with obs.phase("dispatch"):
                    self.params, self.opt_state, self.net_state, loss = step(
                        self.params,
                        self.opt_state,
                        self.net_state,
                        jnp.uint32(self.iteration),
                        feats, labs,
                    )
                with obs.phase("device_sync"):
                    obs.sync(loss)
            self._last_score = loss
            self.last_batch_size = batch.num_examples
            self.iteration += 1
            with obs.phase("listeners"):
                self._dispatch_iteration(loss)

    # -- pipeline parallelism ---------------------------------------------
    def _setup_pipeline(self, mesh, n_micro: int = 0,
                        schedule: str = "gpipe") -> None:
        """Called by distribute() when the mesh carries a pipe axis: plan
        which contiguous block run pipelines over it (raises with an
        actionable message when the stack has no pipelineable segment).
        schedule: "gpipe" runs inside the ordinary compiled step via
        _forward; "1f1b" swaps fit() onto a dedicated step whose backward
        is interleaved into the pipeline (O(pipe) activation stash)."""
        from deeplearning4j_tpu.parallel.pipeline import plan_sequential_pipeline
        from deeplearning4j_tpu.runtime.mesh import PIPE_AXIS

        if schedule not in ("gpipe", "1f1b"):
            raise ValueError(
                f"unknown pipeline schedule {schedule!r}; "
                "options: 'gpipe', '1f1b'"
            )
        self._pipeline_plan = plan_sequential_pipeline(
            self.conf.layers, self.params, self._itypes,
            mesh.shape[PIPE_AXIS], n_micro, net_state=self.net_state,
        )
        self._pipeline_schedule = schedule
        self._step_fns.clear()

    def _active_pipeline_plan(self):
        """The plan, iff tracing under a mesh whose pipe axis is real."""
        from deeplearning4j_tpu.runtime.mesh import PIPE_AXIS, active_mesh

        plan = getattr(self, "_pipeline_plan", None)
        if plan is None:
            return None
        mesh = active_mesh()
        if (
            mesh is None
            or PIPE_AXIS not in mesh.axis_names
            or mesh.shape[PIPE_AXIS] < 2
        ):
            return None
        return plan

    def _reg_loss(self, params):
        return regularization_loss(params, [(l.name, l) for l in self.conf.layers])

    def _data_loss_custom(self, p, out, labels, lmask):
        if self._custom_loss_layer is not None:
            return self._custom_loss(
                p.get(self._custom_loss_layer, {}), out, labels, lmask
            )
        return self._custom_loss(out, labels, lmask)

    # -- compiled train step ----------------------------------------------
    def _step_loss(self, p, net_state, feats, labs, *, lmask=None, fmask=None,
                   rng=None, carries=None):
        """The SHARED traced loss body of every training-step program
        (single, TBPTT window, grouped, grouped-TBPTT): forward + data
        loss (custom or enum) + aux + regularization.  Returns
        (loss, new_state, new_carries) — new_carries is {} when carries
        weren't threaded."""
        fwd = self._forward(
            p, net_state, feats, training=True, rng=rng,
            fmask=fmask, carries=carries,
        )
        if carries is not None:
            out, new_state, new_carries = fwd
        else:
            out, new_state = fwd
            new_carries = {}
        if self._custom_loss is not None:
            data_loss = self._data_loss_custom(p, out, labs, lmask)
        else:
            if not self._fused_loss:
                out = self._out_activation(out.astype(jnp.float32))
            data_loss = compute_loss(
                self._loss, out, labs, lmask, from_logits=self._fused_loss
            )
        aux, new_state = pop_aux_losses(new_state)
        return data_loss + self._reg_loss(p) + aux, new_state, new_carries

    # _apply_grads — the shared update epilogue (replicated or ZeRO-1
    # sharded) — lives on the Model base; every builder below calls it.

    def _get_step_fn(self, has_lmask: bool, has_fmask: bool, with_carries: bool,
                     decode=None):
        """The single-batch step program.  With `decode` set (the
        fused-decode fit), the program takes raw bytes and runs the
        lowered transform chain as its first stage — the chain, not
        the batch, produces the masks (sequence padding), and the loss
        body below is shared so fused and host training cannot
        diverge."""
        key = (("train", has_lmask, has_fmask, with_carries)
               if decode is None else ("train_fused", decode.fingerprint))
        key = key + self._step_key_suffix()
        if key not in self._step_fns:

            def core(params, opt_state, net_state, step_i, features,
                     labels, lm, fm, carries):
                rng = SeedStream.fold(self._stream.root, step_i)
                zp = self._zero_placement
                accum = getattr(zp, "accum", 1) if zp is not None else 1
                if accum > 1 and not with_carries:
                    # ZeRO-2 microbatch accumulation: scan over m
                    # microbatches with the grad accumulator SHARDED in
                    # the carry (parallel/zero.py scan_accumulate) — no
                    # full replicated gradient persists across the
                    # accumulation, activation memory drops ~1/m
                    from deeplearning4j_tpu.parallel.zero import (
                        split_accum_microbatches,
                    )

                    micro = split_accum_microbatches(
                        (features, labels, lm, fm), accum
                    )

                    def loss_grad_fn(p, state, arrays, micro_i):
                        f, l, lmm, fmm = arrays
                        # distinct noise per microbatch: dropout et al.
                        # must not repeat the same mask m times
                        rng_i = SeedStream.fold(rng, micro_i)

                        def lf(pp):
                            loss, new_state, _ = self._step_loss(
                                pp, state, f, l, lmask=lmm, fmask=fmm,
                                rng=rng_i, carries=None,
                            )
                            return loss, {**state, **new_state}

                        return jax.value_and_grad(lf, has_aux=True)(p)

                    loss, merged_state, grads = zp.scan_accumulate(
                        loss_grad_fn, params, net_state, micro
                    )
                    params, opt_state = self._apply_grads(
                        params, opt_state, grads
                    )
                    return params, opt_state, merged_state, loss, {}

                def loss_fn(p):
                    loss, new_state, new_carries = self._step_loss(
                        p, net_state, features, labels,
                        lmask=lm, fmask=fm, rng=rng,
                        carries=carries if with_carries else None,
                    )
                    return loss, (new_state, new_carries)

                (loss, (new_state, new_carries)), grads = jax.value_and_grad(
                    loss_fn, has_aux=True
                )(params)
                params, opt_state = self._apply_grads(params, opt_state, grads)
                # carry unchanged state subtrees forward
                merged_state = {**net_state, **new_state}
                return params, opt_state, merged_state, loss, new_carries

            if decode is None:

                @partial(jax.jit, donate_argnums=(0, 1, 2))
                def step(params, opt_state, net_state, step_i, features,
                         labels, lmask, fmask, carries):
                    return core(
                        params, opt_state, net_state, step_i, features,
                        labels,
                        lmask if has_lmask else None,
                        fmask if has_fmask else None,
                        carries,
                    )

            else:
                dec = decode.fn

                @partial(jax.jit, donate_argnums=(0, 1, 2))
                def step(params, opt_state, net_state, step_i, dec_step,
                         raw_feats, raw_labels):
                    # dec_step is the feed's augmentation index (the
                    # batch's _decode_step), NOT model.iteration: the
                    # host fallback folds keys from the same feed
                    # counter, keeping the two paths numerically equal
                    feats, labs, fm, lm = dec(dec_step, raw_feats,
                                              raw_labels)
                    return core(params, opt_state, net_state, step_i,
                                feats, labs, lm, fm, {})

            self._step_fns[key] = self._register_program(key, step)
        return self._step_fns[key]

    def _fused_decode_reason(self) -> str | None:
        """Why THIS model's fit cannot fuse a device decode, or None.
        The variants with their own step programs (compressed, 1F1B,
        TBPTT) keep host transforms — their programs were not built to
        compose a decode stage."""
        if getattr(self, "_grad_compression", None):
            return "grad-compression fit path"
        if (getattr(self, "_pipeline_schedule", "gpipe") == "1f1b"
                and getattr(self, "_pipeline_plan", None) is not None):
            return "1F1B pipeline fit path"
        if self.conf.backprop_type == "tbptt" and self.conf.tbptt_length > 0:
            return "TBPTT fit path"
        return None

    def _get_step_fn_tbptt(self, has_lmask: bool, has_fmask: bool):
        """Whole-batch TBPTT as ONE compiled XLA program: a lax.scan over
        the time windows, each scan iteration doing grad + updater for its
        window with RNN carries (values only) flowing to the next.  The
        reference runs one fit per window from Java; a per-window jit
        dispatch can cost more than a small window's compute, so the
        window loop belongs inside the program."""
        key = ("train_tbptt", has_lmask, has_fmask) + self._step_key_suffix()
        if key not in self._step_fns:
            from deeplearning4j_tpu.nn.conf.recurrent import (
                RecurrentLayerConfig,
            )

            L = self.conf.tbptt_length
            rnn_layers = [
                l for l in self.conf.layers
                if isinstance(l, RecurrentLayerConfig)
            ]

            @partial(jax.jit, donate_argnums=(0, 1, 2))
            def step(params, opt_state, net_state, step_i, features,
                     labels, lmask, fmask):
                # window + carry setup live INSIDE the program: every
                # un-jitted host dispatch can cost more than a small
                # window's compute
                B, T = features.shape[0], features.shape[1]
                W = T // L
                cdtype = (
                    jnp.bfloat16
                    if self._bf16 and jnp.issubdtype(features.dtype, jnp.floating)
                    else features.dtype
                )
                carries = {
                    l.name: l.init_carry(B, cdtype) for l in rnn_layers
                }

                def windowed(a):
                    a = a[:, : W * L].reshape((B, W, L) + a.shape[2:])
                    return jnp.moveaxis(a, 1, 0)

                features_w = windowed(features)
                labels_w = windowed(labels)
                lmask_w = windowed(lmask) if has_lmask else jnp.zeros((W, 0))
                fmask_w = windowed(fmask) if has_fmask else jnp.zeros((W, 0))

                def window(carry, inp):
                    params, opt_state, net_state, carries, si = carry
                    feats, labs, lm, fm = inp
                    rng = SeedStream.fold(self._stream.root, si)

                    def loss_fn(p):
                        loss, new_state, new_carries = self._step_loss(
                            p, net_state, feats, labs,
                            lmask=lm if has_lmask else None,
                            fmask=fm if has_fmask else None,
                            rng=rng, carries=carries,
                        )
                        return loss, (new_state, new_carries)

                    (loss, (new_state, new_carries)), grads = jax.value_and_grad(
                        loss_fn, has_aux=True
                    )(params)
                    params, opt_state = self._apply_grads(params, opt_state, grads)
                    merged_state = {**net_state, **new_state}
                    return (
                        (params, opt_state, merged_state, new_carries, si + 1),
                        loss,
                    )

                (params, opt_state, net_state, carries, si), losses = jax.lax.scan(
                    window,
                    (params, opt_state, net_state, carries, step_i),
                    (features_w, labels_w, lmask_w, fmask_w),
                )
                return params, opt_state, net_state, losses, carries, si

            self._step_fns[key] = self._register_program(key, step)
        return self._step_fns[key]

    def _get_step_fn_tbptt_grouped(self):
        """steps_per_execution x TBPTT composed: an OUTER scan over k
        stacked batches, each iteration running the full window loop with
        freshly-zeroed RNN carries (batch boundaries reset state; window
        boundaries carry it) — k*W optimizer steps, ONE dispatch."""
        key = ("train_tbptt_grouped",) + self._step_key_suffix()
        if key not in self._step_fns:
            from deeplearning4j_tpu.nn.conf.recurrent import (
                RecurrentLayerConfig,
            )

            L = self.conf.tbptt_length
            rnn_layers = [
                l for l in self.conf.layers
                if isinstance(l, RecurrentLayerConfig)
            ]

            @partial(jax.jit, donate_argnums=(0, 1, 2))
            def step(params, opt_state, net_state, step_i, features_k, labels_k):
                B, T = features_k.shape[1], features_k.shape[2]
                W = T // L
                cdtype = (
                    jnp.bfloat16
                    if self._bf16
                    and jnp.issubdtype(features_k.dtype, jnp.floating)
                    else features_k.dtype
                )

                def windowed(a):
                    a = a[:, : W * L].reshape((B, W, L) + a.shape[2:])
                    return jnp.moveaxis(a, 1, 0)

                def one_batch(carry, inp):
                    params, opt_state, net_state, si = carry
                    feats, labs = inp
                    carries = {
                        l.name: l.init_carry(B, cdtype) for l in rnn_layers
                    }

                    def window(c, winp):
                        params, opt_state, net_state, carries, si = c
                        wf, wl = winp
                        rng = SeedStream.fold(self._stream.root, si)

                        def loss_fn(p):
                            loss, new_state, new_carries = self._step_loss(
                                p, net_state, wf, wl, rng=rng, carries=carries
                            )
                            return loss, (new_state, new_carries)

                        (loss, (new_state, new_carries)), grads = (
                            jax.value_and_grad(loss_fn, has_aux=True)(params)
                        )
                        params, opt_state = self._apply_grads(
                            params, opt_state, grads
                        )
                        merged = {**net_state, **new_state}
                        return (
                            (params, opt_state, merged, new_carries, si + 1),
                            loss,
                        )

                    (params, opt_state, net_state, _, si), losses = (
                        jax.lax.scan(
                            window,
                            (params, opt_state, net_state, carries, si),
                            (windowed(feats), windowed(labs)),
                        )
                    )
                    return (params, opt_state, net_state, si), losses

                (params, opt_state, net_state, si), losses = jax.lax.scan(
                    one_batch,
                    (params, opt_state, net_state, step_i),
                    (features_k, labels_k),
                )
                return params, opt_state, net_state, losses.reshape(-1), si

            self._step_fns[key] = self._register_program(key, step)
        return self._step_fns[key]

    # -- compressed-gradient DP step (int8 allreduce over the data axis) ---
    def _setup_grad_compression(self, mesh) -> None:
        """Called by distribute(ParallelConfig(grad_compression="int8")):
        switch fit() to the shard_map step that exchanges gradients as
        error-feedback int8 (parallel/compression.py).  The residual
        carries one slot per data shard (leading dim sharded on the data
        axis)."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from deeplearning4j_tpu.runtime.mesh import DATA_AXIS

        n = mesh.shape[DATA_AXIS]
        if n < 2:
            return
        self._grad_compression = "int8"
        self._grad_residual = jax.device_put(
            jax.tree.map(
                lambda p: jnp.zeros((n,) + p.shape, p.dtype), self.params
            ),
            NamedSharding(mesh, P(DATA_AXIS)),
        )
        self._step_fns.clear()

    def _get_step_fn_compressed(self, has_lmask: bool, has_fmask: bool):
        key = ("train_q", has_lmask, has_fmask)
        if key not in self._step_fns:
            from jax.sharding import PartitionSpec as P
            from deeplearning4j_tpu.parallel.compression import (
                quantized_allreduce_tree,
            )
            from deeplearning4j_tpu.runtime.mesh import DATA_AXIS, shard_map

            mesh = self._mesh

            def shard_body(params, opt_state, net_state, resid, step_i,
                           features, labels, lmask, fmask):
                rng = SeedStream.fold(self._stream.root, step_i)
                # per-shard dropout streams (each shard sees different data)
                rng = jax.random.fold_in(rng, jax.lax.axis_index(DATA_AXIS))

                def loss_fn(p):
                    out, new_state = self._forward(
                        p, net_state, features, training=True, rng=rng,
                        fmask=fmask if has_fmask else None,
                    )
                    if self._custom_loss is not None:
                        data_loss = self._data_loss_custom(
                            p, out, labels, lmask if has_lmask else None
                        )
                    else:
                        if not self._fused_loss:
                            out = self._out_activation(out.astype(jnp.float32))
                        data_loss = compute_loss(
                            self._loss, out, labels,
                            lmask if has_lmask else None,
                            from_logits=self._fused_loss,
                        )
                    aux, new_state = pop_aux_losses(new_state)
                    return data_loss + self._reg_loss(p) + aux, new_state

                (loss, new_state), grads = jax.value_and_grad(
                    loss_fn, has_aux=True
                )(params)
                resid_local = jax.tree.map(lambda a: a[0], resid)
                grads, resid_local = quantized_allreduce_tree(
                    grads, resid_local, axis=DATA_AXIS,
                    key=jax.random.fold_in(rng, 0x51),
                )
                loss = jax.lax.pmean(loss, DATA_AXIS)
                new_state = jax.tree.map(
                    lambda a: jax.lax.pmean(a, DATA_AXIS), new_state
                )
                updates, new_opt = self._tx.update(grads, opt_state, params)
                params = jax.tree.map(
                    lambda p, u: p + u.astype(p.dtype), params, updates
                )
                merged = {**net_state, **new_state}
                resid = jax.tree.map(lambda a: a[None], resid_local)
                return params, new_opt, merged, resid, loss

            @partial(jax.jit, donate_argnums=(0, 1, 2, 3))
            def step(params, opt_state, net_state, resid, step_i,
                     features, labels, lmask, fmask):
                return shard_map(
                    shard_body,
                    mesh=mesh,
                    in_specs=(P(), P(), P(), P(DATA_AXIS), P(),
                              P(DATA_AXIS), P(DATA_AXIS),
                              P(DATA_AXIS) if has_lmask else P(),
                              P(DATA_AXIS) if has_fmask else P()),
                    out_specs=(P(), P(), P(), P(DATA_AXIS), P()),
                    check_vma=False,
                )(params, opt_state, net_state, resid, step_i,
                  features, labels, lmask, fmask)

            self._step_fns[key] = self._register_program(key, step)
        return self._step_fns[key]

    def _run_step_compressed(self, batch: DataSet):
        from deeplearning4j_tpu.parallel.data_parallel import place_batch
        from deeplearning4j_tpu.runtime.crash import oom_report_scope
        from deeplearning4j_tpu.runtime.mesh import active_mesh_scope

        has_lmask = batch.labels_mask is not None
        has_fmask = batch.features_mask is not None
        step = self._get_step_fn_compressed(has_lmask, has_fmask)
        empty = np.zeros((0,), np.float32)
        with self._observe_step() as obs:
            with oom_report_scope(), active_mesh_scope(self._mesh):
                with obs.phase("host_stage"):
                    feats = place_batch(self, batch.features)
                    labs = place_batch(self, batch.labels, is_label=True)
                    lm = (place_batch(self, batch.labels_mask, is_mask=True)
                          if has_lmask else empty)
                    fm = (place_batch(self, batch.features_mask, is_mask=True)
                          if has_fmask else empty)
                with obs.phase("dispatch"):
                    (self.params, self.opt_state, self.net_state,
                     self._grad_residual, loss) = step(
                        self.params,
                        self.opt_state,
                        self.net_state,
                        self._grad_residual,
                        jnp.uint32(self.iteration),
                        feats, labs, lm, fm,
                    )
                with obs.phase("device_sync"):
                    obs.sync(loss)
            self._last_score = loss
            self.last_batch_size = batch.num_examples
            self.iteration += 1
            with obs.phase("listeners"):
                self._dispatch_iteration(loss)

    def fit(self, data, epochs: int = 1, batch_size: int | None = None,
            steps_per_execution: int = 1) -> None:
        """steps_per_execution > 1 runs that many optimizer steps as ONE
        compiled XLA program (a lax.scan over stacked batches) — the
        tf.keras steps_per_execution knob.  On a TPU whose per-dispatch
        latency rivals a small model's step time this is the difference
        between dispatch-bound and compute-bound training.  TBPTT models
        compose: k batches' full window loops run in one program (RNN
        carries reset at batch boundaries).  Ragged/mismatched batches and
        the compressed / 1F1B-pipelined / distributed paths fall back to
        per-batch stepping (they have their own step programs).

        Listener caveat (shared with Keras): per-iteration listeners fire
        AFTER each group completes, so a state-READING listener
        (checkpoint/evaluative) invoked for a mid-group iteration sees the
        END-of-group params; losses/scores are exact per step.  Keep
        steps_per_execution=1 when mid-group snapshots must be exact."""
        if self.params is None:
            self.init()
        iterator = _as_iterator(data, batch_size)
        self._donation_checked = False     # re-arm the one-time alias check
        self._ensure_watchdog()            # step-deadline hang detection
        use_multi = (
            steps_per_execution > 1
            and not getattr(self, "_grad_compression", None)
            and getattr(self, "_pipeline_schedule", "gpipe") != "1f1b"
            and getattr(self, "_batch_sharding", None) is None
        )
        # device-compiled data pipeline: an iterator advertising a
        # lowerable transform chain feeds RAW bytes and the chain runs
        # inside the step program (datavec/device.py); unsupported fit
        # variants and non-lowerable chains keep host transforms
        feed_src, decode = self._device_decode_feed(
            iterator, self._fused_decode_reason()
        )
        self._device_decode = decode
        # software pipelining: batch N+1 is pulled + staged to device on
        # a background thread while step N computes (flags.prefetch_depth
        # deep; 0 = serial).  close() in the finally stops the producer
        # even when a step raises mid-epoch.
        feed = self._prefetch_feed(feed_src)
        try:
            for _ in range(epochs):
                for lst in self.listeners:
                    lst.on_epoch_start(self, self.epoch)
                if use_multi:
                    self._fit_epoch_multi(feed, steps_per_execution)
                else:
                    for batch in self._timed_batches(feed):
                        self._fit_one(batch)
                for lst in self.listeners:
                    lst.on_epoch_end(self, self.epoch)
                self.epoch += 1
                iterator.reset()
        finally:
            self._device_decode = None
            if feed is not feed_src:
                feed.close()
        for lst in self.listeners:
            # getattr: on_fit_end is newer than the SPI — tolerate
            # duck-typed listeners written against the original three hooks
            getattr(lst, "on_fit_end", lambda m: None)(self)

    def _fit_epoch_multi(self, iterator, spe: int) -> None:
        def group_ok(buf):
            f0, l0 = buf[0].features, buf[0].labels
            # raw-tag uniformity: a group mixing raw-tagged and
            # host-decoded batches must degrade to the per-batch path
            # (which routes tags correctly) — the grouped program would
            # stack the tagged batches' undecoded bytes into the loss
            raw0 = bool(getattr(buf[0], "_raw_for_device_decode", False))
            return all(
                b.features.shape == f0.shape
                and b.labels.shape == l0.shape
                and b.features_mask is None
                and b.labels_mask is None
                and bool(getattr(b, "_raw_for_device_decode", False)) == raw0
                for b in buf
            )

        # the device-resident step counter is only valid while EVERY step
        # goes through the grouped program; any single-step fallback (or
        # steps taken before this fit) advances self.iteration outside it
        tbptt = (
            self.conf.backprop_type == "tbptt" and self.conf.tbptt_length > 0
        )

        def flush(buf):
            if not group_ok(buf):
                for b in buf:
                    self._fit_one(b)
                self._multi_iter_dev = None
                return
            if tbptt:
                T = buf[0].features.shape[1]
                if T % self.conf.tbptt_length or not getattr(
                    self, "_tbptt_scan", True
                ):
                    # no remainder-window leg in the grouped program, and
                    # _tbptt_scan=False (the scan-miscompile escape hatch)
                    # must keep forcing the per-window path
                    for b in buf:
                        self._fit_one(b)
                    self._multi_iter_dev = None
                    return
                self._fit_group(buf, self._run_steps_grouped_tbptt)
            else:
                self._fit_group(buf, self._run_steps_grouped)

        self._multi_iter_dev = None
        buf: list[DataSet] = []
        for batch in self._timed_batches(iterator):
            buf.append(batch)
            if len(buf) == spe:
                flush(buf)
                buf = []
        for b in buf:                       # ragged tail group
            self._fit_one(b)
            self._multi_iter_dev = None

    def _get_step_fn_multi(self, decode=None):
        """k optimizer steps in one program: lax.scan over the stacked
        batch axis, same body as the single step.  With `decode` set,
        each scan iteration runs the lowered transform chain first —
        raw stacked bytes in, k losses out."""
        key = (("train_multi",) if decode is None
               else ("train_multi_fused", decode.fingerprint))
        key = key + self._step_key_suffix()
        if key not in self._step_fns:
            dec = None if decode is None else decode.fn

            @partial(jax.jit, donate_argnums=(0, 1, 2))
            def step(params, opt_state, net_state, step_i, features_k,
                     labels_k, dec_steps_k=None):
                def one(carry, inp):
                    params, opt_state, net_state, si = carry
                    fmask = lmask = None
                    if dec is not None:
                        # per-batch feed augmentation indices, not si:
                        # see _get_step_fn's fused signature
                        feats, labs, ds = inp
                        feats, labs, fmask, lmask = dec(ds, feats, labs)
                    else:
                        feats, labs = inp
                    rng = SeedStream.fold(self._stream.root, si)

                    def loss_fn(p):
                        loss, new_state, _ = self._step_loss(
                            p, net_state, feats, labs,
                            lmask=lmask, fmask=fmask, rng=rng,
                        )
                        return loss, new_state

                    (loss, new_state), grads = jax.value_and_grad(
                        loss_fn, has_aux=True
                    )(params)
                    params, opt_state = self._apply_grads(params, opt_state, grads)
                    merged = {**net_state, **new_state}
                    return (params, opt_state, merged, si + 1), loss

                xs = ((features_k, labels_k) if dec is None
                      else (features_k, labels_k, dec_steps_k))
                (params, opt_state, net_state, si), losses = jax.lax.scan(
                    one,
                    (params, opt_state, net_state, step_i),
                    xs,
                )
                return params, opt_state, net_state, losses, si

            self._step_fns[key] = self._register_program(key, step)
        return self._step_fns[key]

    def _run_steps_grouped_tbptt(self, batches: list) -> None:
        from deeplearning4j_tpu.nn.conf.recurrent import Bidirectional
        from deeplearning4j_tpu.runtime.crash import oom_report_scope

        # same config-level preconditions the per-batch TBPTT path raises on
        if self.conf.output_type().kind != "rnn":
            raise ValueError(
                "TBPTT requires a per-timestep output (RnnOutputLayer)"
            )
        if any(isinstance(l, Bidirectional) for l in self.conf.layers):
            raise ValueError("TBPTT is undefined for bidirectional networks")
        T = batches[0].features.shape[1]
        if batches[0].labels.ndim < 2 or batches[0].labels.shape[1] != T:
            raise ValueError(
                "TBPTT needs per-timestep labels with a (B, T, ...) time axis"
            )
        step = self._get_step_fn_tbptt_grouped()
        k = len(batches)
        W = T // self.conf.tbptt_length
        with self._observe_step(k * W) as obs:
            with oom_report_scope():
                with obs.phase("host_stage"):
                    feats = jnp.stack(
                        [jnp.asarray(b.features) for b in batches]
                    )
                    labs = jnp.stack([jnp.asarray(b.labels) for b in batches])
                    if getattr(self, "_multi_iter_dev", None) is None:
                        self._multi_iter_dev = jax.device_put(
                            np.uint32(self.iteration)
                        )
                with obs.phase("dispatch"):
                    (self.params, self.opt_state, self.net_state, losses,
                     self._multi_iter_dev) = step(
                        self.params, self.opt_state, self.net_state,
                        self._multi_iter_dev, feats, labs,
                    )
                with obs.phase("device_sync"):
                    obs.sync(losses)
            self.last_batch_size = batches[-1].num_examples
            self._finish_grouped_steps(losses, k * W)
        # the per-batch TBPTT path keeps its own device counter; resync
        self._tbptt_iter_dev = None

    def _run_steps_grouped(self, batches: list) -> None:
        from deeplearning4j_tpu.runtime import faults
        from deeplearning4j_tpu.runtime.crash import oom_report_scope

        decode = self._device_decode if (
            self._device_decode is not None
            and all(getattr(b, "_raw_for_device_decode", False)
                    for b in batches)
        ) else None
        step = self._get_step_fn_multi(decode)
        k = len(batches)
        with self._observe_step(k) as obs:
            with oom_report_scope():
                with obs.phase("host_stage"):
                    extra = ()
                    if decode is not None:
                        # fused-decode host boundary (see _run_step_fused)
                        faults.maybe_fail("data.device_decode")
                        extra = (jnp.asarray(
                            [getattr(b, "_decode_step", self.iteration + i)
                             for i, b in enumerate(batches)], jnp.uint32,
                        ),)
                    feats = jnp.stack(
                        [jnp.asarray(b.features) for b in batches]
                    )
                    labs = jnp.stack([jnp.asarray(b.labels) for b in batches])
                    if getattr(self, "_multi_iter_dev", None) is None:
                        self._multi_iter_dev = jax.device_put(
                            np.uint32(self.iteration)
                        )
                with obs.phase("dispatch"):
                    (self.params, self.opt_state, self.net_state, losses,
                     self._multi_iter_dev) = step(
                        self.params, self.opt_state, self.net_state,
                        self._multi_iter_dev, feats, labs, *extra,
                    )
                with obs.phase("device_sync"):
                    obs.sync(losses)
            self.last_batch_size = batches[-1].num_examples
            if decode is not None:
                self._count_device_decode(
                    decode, batches[0].features, batches[0].labels, k=k
                )
            # listeners span lives in _finish_grouped_steps
            self._finish_grouped_steps(losses, k)

    def fit_batch(self, batch: DataSet) -> None:
        if self.params is None:
            self.init()
        if getattr(self, "_grad_compression", None):
            if self.conf.backprop_type == "tbptt" and self.conf.tbptt_length > 0:
                raise ValueError(
                    "grad_compression does not compose with TBPTT "
                    "(per-window carries cross the compressed-sync "
                    "boundary); use standard backprop or drop compression"
                )
            self._run_step_compressed(batch)
            return
        if (
            getattr(self, "_pipeline_schedule", "gpipe") == "1f1b"
            and getattr(self, "_pipeline_plan", None) is not None
            and getattr(self, "_mesh", None) is not None
        ):
            # NOT _active_pipeline_plan(): that checks the ambient mesh
            # scope, which only exists INSIDE a running step — at routing
            # time it would always be None and 1F1B would silently fall
            # back to GPipe
            self._run_step_1f1b(batch)
            return
        if self.conf.backprop_type == "tbptt" and self.conf.tbptt_length > 0:
            self._fit_batch_tbptt(batch)
            return
        self._run_step(batch, carries=None)

    def _run_step(self, batch: DataSet, carries):
        from deeplearning4j_tpu.parallel.data_parallel import place_batch
        from deeplearning4j_tpu.runtime.mesh import active_mesh_scope

        if (self._device_decode is not None and carries is None
                and getattr(batch, "_raw_for_device_decode", False)):
            if batch.features_mask is None and batch.labels_mask is None:
                return self._run_step_fused(batch, self._device_decode)
            # a raw batch carrying its OWN masks: the fused program
            # cannot see them (it stages features/labels only), so
            # decode on the host — masks thread through the chain —
            # and fall through to the normal masked step.  (_RawFeed
            # host-decodes masked batches itself; this is the defensive
            # net for hand-tagged batches.)
            batch = self._device_decode.host(
                getattr(batch, "_decode_step", self.iteration), batch
            )
        has_lmask = batch.labels_mask is not None
        has_fmask = batch.features_mask is not None
        with_carries = carries is not None
        step = self._get_step_fn(has_lmask, has_fmask, with_carries)
        from deeplearning4j_tpu.runtime.crash import oom_report_scope

        empty = np.zeros((0,), np.float32)
        with self._observe_step() as obs:
            # staging stays INSIDE the oom/mesh scopes (a device OOM while
            # placing the batch must still write the crash report)
            with oom_report_scope(), active_mesh_scope(
                getattr(self, "_mesh", None)
            ):
                with obs.phase("host_stage"):
                    feats = place_batch(self, batch.features)
                    labs = place_batch(self, batch.labels, is_label=True)
                    lm = (place_batch(self, batch.labels_mask, is_mask=True)
                          if has_lmask else empty)
                    fm = (place_batch(self, batch.features_mask, is_mask=True)
                          if has_fmask else empty)
                with obs.phase("dispatch"):
                    (self.params, self.opt_state, self.net_state, loss,
                     new_carries) = step(
                        self.params,
                        self.opt_state,
                        self.net_state,
                        jnp.uint32(self.iteration),
                        feats, labs, lm, fm,
                        carries if with_carries else {},
                    )
                with obs.phase("device_sync"):
                    obs.sync(loss)
            self._last_score = loss
            self.last_batch_size = batch.num_examples
            self.iteration += 1
            with obs.phase("listeners"):
                self._dispatch_iteration(loss)
        return new_carries

    def _run_step_fused(self, batch: DataSet, decode) -> None:
        """Dispatch one fused decode+train program over a raw batch:
        the host stages undecoded bytes (smaller or cheaper transfers,
        zero per-batch transform work) and the chain runs as the first
        stage of the compiled step."""
        from deeplearning4j_tpu.parallel.data_parallel import place_batch
        from deeplearning4j_tpu.runtime import faults
        from deeplearning4j_tpu.runtime.crash import oom_report_scope
        from deeplearning4j_tpu.runtime.mesh import active_mesh_scope

        step = self._get_step_fn(False, False, False, decode)
        with self._observe_step() as obs:
            with oom_report_scope(), active_mesh_scope(
                getattr(self, "_mesh", None)
            ):
                with obs.phase("host_stage"):
                    # fault site: the fused-decode host boundary (armed
                    # plans provoke decode-stage failures; disarmed this
                    # is one attribute check)
                    faults.maybe_fail("data.device_decode")
                    feats = place_batch(self, batch.features)
                    labs = place_batch(self, batch.labels, is_label=True)
                with obs.phase("dispatch"):
                    (self.params, self.opt_state, self.net_state,
                     loss, _) = step(
                        self.params, self.opt_state, self.net_state,
                        jnp.uint32(self.iteration),
                        jnp.uint32(getattr(batch, "_decode_step",
                                           self.iteration)),
                        feats, labs,
                    )
                with obs.phase("device_sync"):
                    obs.sync(loss)
            self._last_score = loss
            self.last_batch_size = batch.num_examples
            self.iteration += 1
            self._count_device_decode(decode, feats, labs)
            with obs.phase("listeners"):
                self._dispatch_iteration(loss)

    def _fit_batch_tbptt(self, batch: DataSet) -> None:
        """Truncated BPTT: split the time axis into tbptt_length windows;
        gradients are confined to each window, RNN carries flow across
        windows (values only — the window boundary stops the gradient,
        matching BackpropType.TruncatedBPTT)."""
        from deeplearning4j_tpu.nn.conf.recurrent import Bidirectional

        T = batch.features.shape[1]
        L = self.conf.tbptt_length
        if self.conf.output_type().kind != "rnn":
            raise ValueError(
                "TBPTT requires a per-timestep output (RnnOutputLayer); this "
                "network collapses the time axis — use standard backprop"
            )
        if any(isinstance(l, Bidirectional) for l in self.conf.layers):
            raise ValueError(
                "TBPTT is undefined for bidirectional networks (the backward "
                "direction crosses window boundaries) — use standard backprop"
            )
        if batch.labels.ndim < 2 or batch.labels.shape[1] != T:
            raise ValueError(
                "TBPTT needs per-timestep labels with a (B, T, ...) time "
                f"axis matching features; got {batch.labels.shape} for T={T}"
            )
        W, rem = divmod(T, L)
        if (
            not getattr(self, "_tbptt_scan", True)
            or getattr(self, "_batch_sharding", None) is not None
            or W < 2
        ):
            # distributed models keep the per-window path (place_batch
            # shards axis 0; the scanned layout's leading axis is windows)
            carries: dict = {}
            for t0 in range(0, T, L):
                sl = slice(t0, min(t0 + L, T))
                window = DataSet(
                    batch.features[:, sl],
                    batch.labels[:, sl],
                    None if batch.features_mask is None else batch.features_mask[:, sl],
                    None if batch.labels_mask is None else batch.labels_mask[:, sl],
                )
                carries = self._run_step(window, carries=carries)
            return

        from deeplearning4j_tpu.runtime.crash import oom_report_scope

        has_lmask = batch.labels_mask is not None
        has_fmask = batch.features_mask is not None
        step = self._get_step_fn_tbptt(has_lmask, has_fmask)
        # device-resident step counter + cached empty: per-call
        # host->device traffic is held to the batch handles alone
        with self._observe_step(W) as obs:
            with oom_report_scope():
                with obs.phase("host_stage"):
                    if getattr(self, "_tbptt_iter_dev", None) is None:
                        self._tbptt_iter_dev = jax.device_put(
                            np.uint32(self.iteration)
                        )
                        self._empty_dev = jax.device_put(
                            np.zeros((0,), np.float32)
                        )
                with obs.phase("dispatch"):
                    (self.params, self.opt_state, self.net_state, losses,
                     carries, self._tbptt_iter_dev) = step(
                        self.params,
                        self.opt_state,
                        self.net_state,
                        self._tbptt_iter_dev,
                        batch.features,
                        batch.labels,
                        batch.labels_mask if has_lmask else self._empty_dev,
                        batch.features_mask if has_fmask else self._empty_dev,
                    )
                with obs.phase("device_sync"):
                    obs.sync(losses)
            self.last_batch_size = batch.num_examples
            self._finish_grouped_steps(losses, W)
        if rem:
            tail = slice(W * L, T)
            window = DataSet(
                batch.features[:, tail],
                batch.labels[:, tail],
                None if batch.features_mask is None else batch.features_mask[:, tail],
                None if batch.labels_mask is None else batch.labels_mask[:, tail],
            )
            self._run_step(window, carries=carries)
            # the tail step advanced self.iteration outside the device
            # counter; resync on the next batch
            self._tbptt_iter_dev = None

    # -- layerwise unsupervised pretraining --------------------------------
    def pretrain(self, data, epochs: int = 1, batch_size: int | None = None) -> None:
        """Greedy layerwise unsupervised pretraining (reference
        MultiLayerNetwork.pretrain(DataSetIterator)): every PRETRAINABLE
        layer (AutoEncoder / VariationalAutoencoder) is trained in stack
        order on the features only."""
        for i, layer in enumerate(self.conf.layers):
            if getattr(layer, "PRETRAINABLE", False):
                self.pretrain_layer(i, data, epochs=epochs, batch_size=batch_size)

    def pretrain_layer(
        self, index: int, data, epochs: int = 1, batch_size: int | None = None
    ) -> float:
        """Unsupervised pretraining of one layer (reference
        MultiLayerNetwork.pretrainLayer): the frozen prefix runs in
        inference mode, then (prefix-forward -> pretrain_loss -> grad ->
        updater) for THIS layer's params compiles into one donated-buffer
        XLA step.  Returns the last pretrain loss."""
        if self.params is None:
            self.init()
        layer = self.conf.layers[index]
        if not getattr(layer, "PRETRAINABLE", False):
            raise ValueError(
                f"layer {index} ({type(layer).__name__}) is not pretrainable; "
                "only AutoEncoder/VariationalAutoencoder layers support "
                "unsupervised pretraining"
            )
        tx = with_gradient_clipping(
            self.conf.updater.to_optax(self.conf.steps_per_epoch),
            self.conf.gradient_clip_value,
            self.conf.gradient_clip_norm,
        )
        opt_state = tx.init(self.params[layer.name])
        frozen_params = {
            k: v for k, v in self.params.items() if k != layer.name
        }

        @partial(jax.jit, donate_argnums=(0, 1))
        def pstep(lp, opt_state, frozen, step_i, features):
            rng = SeedStream.fold(self._stream.root, step_i)

            def loss_fn(lp):
                x = self._prefix_forward(frozen, features, index)
                return layer.pretrain_loss(lp, jax.lax.stop_gradient(x), rng)

            loss, grads = jax.value_and_grad(loss_fn)(lp)
            updates, opt_state = tx.update(grads, opt_state, lp)
            lp = jax.tree.map(lambda p, u: p + u.astype(p.dtype), lp, updates)
            return lp, opt_state, loss

        iterator = _as_iterator(data, batch_size)
        lp = self.params.pop(layer.name)
        loss = float("nan")
        step_i = 0
        try:
            for _ in range(epochs):
                for batch in iterator:
                    lp, opt_state, loss = pstep(
                        lp, opt_state, frozen_params, jnp.uint32(step_i),
                        jnp.asarray(batch.features),
                    )
                    step_i += 1
                iterator.reset()
        finally:
            self.params[layer.name] = lp
        return float(loss)

    def _prefix_forward(self, params, x, stop: int):
        """Inference-mode forward through layers [0, stop) — the pretrain
        prefix.  Pure/traced; BN etc. use stored state without updating."""
        x = entry_cast(x, self._bf16)
        for i, layer in enumerate(self.conf.layers[:stop]):
            if self._flatten_before[i]:
                x = x.reshape(x.shape[0], -1)
            lp = params.get(layer.name, {})
            ls = self.net_state.get(layer.name, {})
            x, _ = layer.apply(lp, ls, x, training=False, rng=None)
        if self._flatten_before[stop]:
            x = x.reshape(x.shape[0], -1)
        return x.astype(jnp.float32)

    # -- inference ---------------------------------------------------------
    def _get_infer_fn(self, has_fmask: bool = False):
        key = ("infer", has_fmask) + self._step_key_suffix()
        if key not in self._step_fns:

            @jax.jit
            def infer(params, net_state, features, fmask):
                out, _ = self._forward(
                    params,
                    net_state,
                    features,
                    training=False,
                    rng=None,
                    fmask=fmask if has_fmask else None,
                )
                return self._out_activation(out.astype(jnp.float32))

            self._step_fns[key] = self._register_program(key, infer)
        return self._step_fns[key]

    def output(self, features, features_mask=None) -> jax.Array:
        """Forward pass with the output activation applied (reference
        `MultiLayerNetwork.output()`)."""
        if self.params is None:
            self.init()
        from deeplearning4j_tpu.runtime.mesh import active_mesh_scope

        has_fmask = features_mask is not None
        with active_mesh_scope(getattr(self, "_mesh", None)):
            return self._get_infer_fn(has_fmask)(
                self.params,
                self.net_state,
                features,
                features_mask if has_fmask else np.zeros((0,), np.float32),
            )

    # -- stateful streaming inference (rnnTimeStep role) -------------------
    def _init_carries(self, batch: int) -> dict:
        from deeplearning4j_tpu.nn.conf.recurrent import RecurrentLayerConfig

        dtype = jnp.bfloat16 if self._bf16 else jnp.float32
        return {
            l.name: l.init_carry(batch, dtype)
            for l in self.conf.layers
            if isinstance(l, RecurrentLayerConfig)
        }

    def rnn_time_step(self, features) -> jax.Array:
        """Streaming RNN inference: feed a chunk (B, T, F), carry hidden
        state to the next call (the reference's rnnTimeStep).  Output
        activation applied.  Jitted (cached per chunk shape) so
        token-by-token generation loops stay fast."""
        if self.params is None:
            self.init()
        from deeplearning4j_tpu.nn.conf.recurrent import Bidirectional

        if any(isinstance(l, Bidirectional) for l in self.conf.layers):
            raise ValueError(
                "rnn_time_step is undefined for bidirectional networks (the "
                "backward pass needs the full future sequence) — use output()"
            )
        if not getattr(self, "_rnn_stream_state", None):
            self._rnn_stream_state = self._init_carries(features.shape[0])
        key = ("rnn_step",) + self._step_key_suffix()
        if key not in self._step_fns:

            @jax.jit
            def rnn_step(params, net_state, x, carries):
                out, _, new_carries = self._forward(
                    params, net_state, x, training=False, rng=None, carries=carries
                )
                return self._out_activation(out.astype(jnp.float32)), new_carries

            self._step_fns[key] = self._register_program(key, rnn_step)
        out, self._rnn_stream_state = self._step_fns[key](
            self.params, self.net_state, jnp.asarray(features), self._rnn_stream_state
        )
        return out

    def rnn_clear_previous_state(self) -> None:
        self._rnn_stream_state = {}

    def predict(self, features) -> np.ndarray:
        """Argmax class predictions (reference `predict()`)."""
        return np.asarray(jnp.argmax(self.output(features), axis=-1))

    def feed_forward(self, features) -> list[jax.Array]:
        """Per-layer activations (reference `feedForward()`); not jitted —
        debugging/inspection path."""
        acts = []
        x = jnp.asarray(features)
        x = entry_cast(x, self._bf16)
        for i, layer in enumerate(self.conf.layers):
            if self._flatten_before[i]:
                x = x.reshape(x.shape[0], -1)
            lp = self.params.get(layer.name, {})
            ls = self.net_state.get(layer.name, {})
            x, _ = layer.apply(lp, ls, x, training=False, rng=None)
            acts.append(x)
        return acts

    def score(self, ds: DataSet) -> float:
        """Loss (incl. regularization) on a dataset without updating."""
        out, _ = self._forward(
            self.params,
            self.net_state,
            jnp.asarray(ds.features),
            training=False,
            rng=None,
            fmask=ds.features_mask,
        )
        if self._custom_loss is not None:
            loss = self._data_loss_custom(
                self.params, out, jnp.asarray(ds.labels), ds.labels_mask
            )
        else:
            if not self._fused_loss:
                out = self._out_activation(out.astype(jnp.float32))
            loss = compute_loss(
                self._loss, out, jnp.asarray(ds.labels), ds.labels_mask,
                from_logits=self._fused_loss,
            )
        return float(loss + self._reg_loss(self.params))

    def evaluate(self, data, batch_size: int | None = None):
        from deeplearning4j_tpu.evaluation.evaluation import Evaluation

        iterator = _as_iterator(data, batch_size)
        ev = Evaluation()
        last = self.conf.layers[-1]
        for batch in iterator:
            probs = self.output(batch.features, batch.features_mask)
            if hasattr(last, "evaluation_output"):
                # custom heads (CenterLoss concat, ChunkedSoftmax hidden
                # states) need their logits extracted — a raw argmax over
                # apply()'s output would be garbage
                probs = last.evaluation_output(
                    self.params.get(last.name, {}), probs
                )
            labels = batch.labels
            parr = np.asarray(probs)
            larr = np.asarray(labels)
            n_out = parr.shape[-1]
            # int class ids (the chunked head's label form) are detected by
            # ELEMENT COUNT — one label per prediction position — exactly
            # as ChunkedSoftmaxOutputLayer's loss does; a trailing-dim
            # comparison would misread (B,T) ids as one-hot whenever
            # T == n_out
            if larr.ndim >= 1 and n_out > 1 and larr.size * n_out == parr.size:
                ids = larr.astype(np.int64)
                if ids.ndim == parr.ndim and ids.shape[-1] == 1:
                    ids = ids[..., 0]
                # build the one-hot batch directly — np.eye(vocab) would be
                # a vocab^2 identity for exactly the large-vocab case
                onehot = np.zeros(ids.shape + (n_out,), np.float32)
                np.put_along_axis(onehot, ids[..., None], 1.0, axis=-1)
                labels = onehot
            ev.eval(labels, np.asarray(probs), mask=batch.labels_mask)
        return ev

    # -- serialization helpers --------------------------------------------
    def clone(self) -> "SequentialModel":
        m = SequentialModel(self.conf)
        if self.params is not None:
            m.params = jax.tree.map(jnp.copy, self.params)
            m.net_state = jax.tree.map(jnp.copy, self.net_state)
            m.opt_state = jax.tree.map(jnp.copy, self.opt_state)
        return m
