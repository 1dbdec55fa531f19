"""A training cell: public `fit()` over fresh seeded batches for a fixed time.

The traffic file gives the sequence length, the batch per chip, the
data-parallel width and the unit the rate is counted in.  The model is
built by the configuration's family, initialised on the device in one
jitted call, optionally `distribute()`d, warmed by a short `fit()` (which
compiles; set-up), and then `fit()` runs until the deadline.  The window
ends when the parameters are ready and the last loss has been read back.

The benchmark watches `fit()` through the public listener SPI only.  The
listener keeps each step's loss as the device scalar it is handed and
blocks on the loss of two steps earlier: the host can never run more than
two steps ahead of the device, so the window closes within two steps of
its deadline, and the device never waits for the host.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks.harness import traffic as tg
from benchmarks.harness.device import memory_peak_bytes
from benchmarks.harness.observe import Observations
from benchmarks.harness.trace_reduce import start_profiler

# system (bf16 compute, flash attention, chunked loss) against the float32
# reference on the same parameters and batch.  The loss is a mean over
# thousands of tokens, so per-logit bf16 rounding (2^-8 relative) largely
# averages out: what remains is well under one bf16 ulp of the loss.  A
# forward that dropped a block, the positions or the causal mask moves the
# loss by percents; a step in an 8-bit float would leave about 2^-4.
TRAIN_LOSS_REL = 2.0 ** -8
RUN_AHEAD_STEPS = 2


def make_feed(family, cfg, rng, batch: int, seq_len: int):
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.data.iterator import DataSetIterator

    class SeededBatches(DataSetIterator):
        """Fresh next-token batches from the run's seed, until a step
        budget or a deadline is used up.  Lazy, so `fit()` feeds it through
        its own prefetch as it would any user's iterator."""

        def __init__(self):
            self.steps_left = 0
            self.deadline = None
            self.first = None          # the very first batch, for the check
            self.queued = []           # batches to hand out before fresh ones

        @property
        def batch_size(self) -> int:
            return batch

        def reset(self) -> None:
            pass

        def __iter__(self):
            while True:
                if self.deadline is not None:
                    if time.perf_counter() >= self.deadline:
                        return
                elif self.steps_left <= 0:
                    return
                self.steps_left -= 1
                ids, labels = (self.queued.pop(0) if self.queued else
                               family.token_batch(rng, cfg, batch, seq_len))
                if self.first is None:
                    self.first = (ids, labels)
                yield DataSet(ids, labels)

    return SeededBatches()


def make_watcher():
    import jax

    from deeplearning4j_tpu.train.listeners import TrainingListener

    class StepWatcher(TrainingListener):
        def __init__(self):
            self.scores = []           # device scalars, one per step
            self.on_step = None

        def iteration_done(self, model, iteration, epoch, score):
            self.scores.append(score)
            if len(self.scores) > RUN_AHEAD_STEPS:
                jax.block_until_ready(self.scores[-1 - RUN_AHEAD_STEPS])
            if self.on_step is not None:
                self.on_step(self)

    return StepWatcher()


class TraceSlice:
    """Profiles `steps` whole steps from the first step after `start_at`:
    starts without draining the queue (the device keeps its two steps of
    work while the profiler starts), ends after the last of those steps is
    ready."""

    def __init__(self, log_dir: str, start_at: float, steps: int):
        self.log_dir, self.start_at, self.steps = log_dir, start_at, steps
        self.first_step = None
        self.wall_s = None
        self._t0 = None

    def __call__(self, watcher) -> None:
        import jax

        if self.wall_s is not None:
            return
        n = len(watcher.scores)
        if self.first_step is None:
            if time.perf_counter() >= self.start_at:
                start_profiler(self.log_dir)
                self._t0 = time.perf_counter()
                self.first_step = n
        elif n >= self.first_step + self.steps:
            jax.block_until_ready(watcher.scores[-1])
            self.wall_s = time.perf_counter() - self._t0
            jax.profiler.stop_trace()


def run(ctx) -> tuple:
    """-> (correct, attempted, failed, Observations, info)."""
    import jax

    from deeplearning4j_tpu.runtime import compile_stats

    cell, family = ctx.cell, ctx.family
    cfg, tr = cell.config, cell.traffic
    n_data = int(tr["parallel"]["data"])
    batch = int(tr["batch_per_chip"]) * n_data
    seq_len = int(tr["seq_len"])
    devs = jax.devices()[:max(1, n_data)]

    model = family.build_model(cfg,
                               learning_rate=float(tr["learning_rate"]))
    family.init_on_device(model, seed=ctx.seed, optimizer_state=True)
    jax.block_until_ready(model.params)
    ctx.mark("weights_made")
    if n_data > 1:
        from deeplearning4j_tpu.parallel import ParallelConfig, distribute

        distribute(model, ParallelConfig(data=n_data), devices=devs)
    feed = make_feed(family, cfg, tg.train_batch_rng(ctx.seed), batch,
                     seq_len)
    watcher = make_watcher()
    model.set_listeners(watcher)

    feed.steps_left = int(tr["warmup_steps"])
    model.fit(feed)
    jax.block_until_ready(model.params)
    first_loss = float(watcher.scores[0])
    warm_steps = len(watcher.scores)
    ctx.mark("warmed")

    snap = compile_stats.snapshot()
    obs = Observations(cell=cell, family=family, device=ctx.device,
                       peaks=ctx.peaks)
    tracer = None
    t0 = time.perf_counter()
    setup_s = ctx.setup_s()
    if ctx.trace:
        tracer = TraceSlice(ctx.trace_dir, t0 + 0.4 * ctx.seconds,
                            int(tr["trace_steps"]))
        watcher.on_step = tracer
    feed.deadline = t0 + ctx.seconds
    model.fit(feed)
    jax.block_until_ready(model.params)
    last_loss = float(watcher.scores[-1])
    t1 = time.perf_counter()
    if tracer is not None and tracer.first_step is not None \
            and tracer.wall_s is None:
        tracer.wall_s = time.perf_counter() - tracer._t0
        jax.profiler.stop_trace()

    steps = len(watcher.scores) - warm_steps
    units_per_step = batch * (seq_len if tr["unit"] == "tokens" else 1)
    obs.close = t1
    obs.e2e = {
        "setup_s": setup_s,
        "train_rate_per_chip": steps * units_per_step / (t1 - t0) / len(devs),
    }
    obs.counters = {
        "compiles_in_window": (compile_stats.snapshot()
                               - snap).backend_compiles,
        "memory_peak_bytes": memory_peak_bytes(devs),
        "steps": steps, "batch": batch, "seq_len": seq_len,
        "chips": len(devs), "unit": tr["unit"],
    }
    if tracer is not None:
        obs.trace_wall_s = tracer.wall_s

    # -- correctness, outside the window --------------------------------------
    # Both comparisons use the loss `fit()` itself reports for a step, which
    # it computes on the parameters the step STARTS from: the first step's
    # against the reference on freshly made weights of the same seed, and one
    # more step's, after the window, against the reference on the trained
    # parameters (read before that step donates them).  `model.score()` is
    # not used: on a distribute()d model at these shapes it runs the flash
    # kernel outside the mesh scope and Mosaic refuses it (PERF.md, PR 22).
    losses = np.asarray([float(s) for s in watcher.scores])
    ref_loss = family.make_reference_loss(cfg)
    fresh = family.build_model(cfg)
    family.init_on_device(fresh, seed=ctx.seed, optimizer_state=False)
    ref_first = float(ref_loss(fresh.params, *feed.first))
    del fresh
    check = family.token_batch(np.random.default_rng([ctx.seed, 0xC0DE]),
                               cfg, batch, seq_len)
    ref_after = float(ref_loss(model.params, *check))
    watcher.on_step = None
    feed.queued, feed.deadline, feed.steps_left = [check], None, 1
    model.fit(feed)
    sys_after = float(watcher.scores[-1])
    rel_first = abs(first_loss - ref_first) / abs(ref_first)
    rel_after = abs(sys_after - ref_after) / abs(ref_after)
    correct = bool(steps > 0 and np.isfinite(losses).all()
                   and rel_first <= TRAIN_LOSS_REL
                   and rel_after <= TRAIN_LOSS_REL)
    info = {
        "steps": steps, "units_per_step": units_per_step,
        "window_s": t1 - t0, "first_loss": first_loss,
        "reference_first_loss": ref_first, "rel_first": rel_first,
        "last_loss": last_loss, "loss_after": sys_after,
        "reference_after": ref_after, "rel_after": rel_after,
        "tolerance": TRAIN_LOSS_REL,
        "losses_finite": bool(np.isfinite(losses).all()),
    }
    return correct, steps, 0, obs, info
