"""Trainer — the L3/L4 runtime (reference parity: ``DLTrainer`` in
``dl_trainer.py`` + the epoch loop of ``horovod_trainer.py``, SURVEY.md §2
C5/C6 and §3.1/§3.2).

Responsibilities, mapped from the reference:
  model-zoo dispatch        -> models.get_model
  dataset construction      -> data.make_dataset (+ background prefetch)
  distributed optimizer     -> parallel.trainstep (built here)
  LR schedule + warmup      -> training.lr_schedule (inside the jitted step)
  warm-up dense allreduce   -> Python-side dense/sparse step selection
  train/test loops, timers  -> Trainer.train / Trainer.test / PhaseTimers
  checkpoints               -> training.checkpoint (orbax, full state)
  metrics/logging           -> telemetry.EventBus (JSONL/Prometheus
                               exporters) + human log lines

Everything device-side lives in ONE jitted SPMD program per step kind; the
trainer is a thin host loop feeding batches and draining metrics
(SURVEY.md §7 design stance).
"""

from __future__ import annotations

import contextlib
import math
import os
import threading
import time
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import shard_map
from jax.sharding import PartitionSpec as P

from .. import data as data_lib
from .. import models as models_lib
from ..compressors import get_compressor
from ..parallel.bucketing import plan_for_params
from ..parallel.mesh import (batch_sharded, data_parallel_mesh, dp_sp_mesh,
                             hierarchical_dp_mesh, shard_batch)
from ..parallel.trainstep import build_dp_train_step
from .checkpoint import (gc_checkpoints, restore_checkpoint,
                         restore_latest_good, save_checkpoint)
from .config import TrainConfig
from .losses import make_eval_fn, make_loss_fn
from .lr_schedule import warmup_milestone_schedule
from .metrics import PhaseTimers, make_logger
from .resilience import (GracefulShutdown, ResilienceMonitor,
                         ResiliencePolicy, TrainingPreempted)
from ..telemetry import (EventBus, JSONLExporter,
                         PrometheusTextfileExporter, ThroughputTracker,
                         throughput)
from ..telemetry.health import (CRITICAL, PRE_ARM_CAUSES, HealthMonitor,
                                HealthServer)
from ..telemetry.profiler import ProfilerSession
from ..telemetry.tracing import TraceContext


# what Trainer._span hands out with tracing off
_NO_SPAN = contextlib.nullcontext()


class _Flight(NamedTuple):
    """A step program that was dispatched and has not been waited for."""

    done: int           # the global step it ends
    metrics: Any        # its StepMetrics: futures until the sync
    t0: float           # perf_counter as its dispatch opened
    io_s: float         # pulling and placing its batch
    dispatch_s: float


def _dtype_of(name: str):
    return {"bfloat16": jnp.bfloat16, "bf16": jnp.bfloat16,
            "float32": jnp.float32, "fp32": jnp.float32}[name]


class Trainer:
    def __init__(self, cfg: TrainConfig):
        self.cfg = cfg
        run_dir = os.path.join(cfg.output_dir, cfg.run_id)
        self.run_dir = run_dir
        self.logger = make_logger(log_file=os.path.join(run_dir, "train.log"))
        # telemetry spine (docs/OBSERVABILITY.md): every runtime event —
        # train intervals, loader io_retry (prefetch thread), resilience
        # skip/rollback/preempt, checkpoints, profiler windows — goes
        # through ONE bus that stamps schema_version/seq/ts and fans out
        # to the attached exporters in publish order
        exporters = [JSONLExporter(os.path.join(run_dir, "metrics.jsonl"))]
        if cfg.prom_textfile:
            exporters.append(PrometheusTextfileExporter(cfg.prom_textfile))
        self.bus = EventBus(exporters)
        # span-based step tracing (telemetry/tracing.py): opt-in — with
        # trace off, no stamp hook is installed and no span records are
        # emitted, so the stream is byte-identical to pre-tracing builds
        self.trace: Optional[TraceContext] = None
        self._traj_span: Optional[str] = None
        if cfg.trace == "on":
            # each span also opens the profiler's annotation of its name,
            # so a --profile-steps window shows the host spans beside the
            # device operations; the finished spans stay reachable after
            # close() through tracing.recorded(cfg.run_id)
            self.trace = TraceContext(
                self.bus, run_id=cfg.run_id,
                annotate=jax.profiler.TraceAnnotation,
                step_annotate=jax.profiler.StepTraceAnnotation).install()
        with self._span("construct"):
            self._construct(cfg, run_dir)
        # long-lived trajectory span: every host span and stamped record
        # between rollbacks parents to it; a rollback rotates it
        # (_rotate_trajectory), so each trajectory is one span-tree root
        if self.trace is not None:
            self._traj_span = self.trace.begin("trajectory", root=True,
                                               step=self.step)

    def _construct(self, cfg: TrainConfig, run_dir: str) -> None:
        """Everything ``__init__`` builds, under the ``construct`` span:
        ``build_data`` (both datasets), ``build_model`` (the module and its
        initial variables) and ``build_step`` (twice: the mesh; then the
        plan, the step programs and the placed state) are its children,
        the rest (resilience, policy, health, the eval step, a resume) its
        self time."""
        self.tracker = ThroughputTracker(window=cfg.telemetry_window)
        self._flops_per_step: Optional[float] = None
        self._peak_flops: Optional[float] = None
        self._mfu_probed = False
        self.timers = PhaseTimers()
        # perf_counter at the end of the last step the loop waited for
        self._t_synced = 0.0

        # ---- mesh (SURVEY.md §3.1: hvd.init + device binding -> mesh) ----
        self.sp = cfg.sp_size if cfg.sp_size > 1 else 0
        with self._span("build_step"):
            self._build_mesh(cfg)
        # sequence-parallel batches shard dim 1 (sequence) over 'sp'
        self._batch_spec = P(("dp",), "sp") if self.sp else None

        # ---- data first (its cardinality sizes the model head/vocab) ----
        dtype = _dtype_of(cfg.compute_dtype)
        local_bs = cfg.batch_size * self.nworkers * cfg.nsteps_update
        eval_bs = max(self.nworkers, local_bs // cfg.nsteps_update)
        train_kw = dict(train=True, batch_size=local_bs)
        test_kw = dict(train=False, batch_size=eval_bs)
        train_kw.update(cfg.dataset_kwargs)   # overrides win, never collide
        test_kw.update(cfg.dataset_kwargs)
        with self._span("build_data"):
            self.train_ds, card = data_lib.make_dataset(
                cfg.dataset, cfg.data_dir, **train_kw)
            self.test_ds, _ = data_lib.make_dataset(
                cfg.dataset, cfg.data_dir, **test_kw)

        with self._span("build_model"):
            params, model_state, state_rng = self._build_model(cfg, card,
                                                               dtype)
        n_params = sum(int(np.prod(x.shape))
                       for x in jax.tree_util.tree_leaves(params))

        with self._span("build_step"):
            input_norm = self._build_program(cfg, params, model_state,
                                             state_rng, local_bs)

        # ---- resilience runtime (docs/RESILIENCE.md) ----
        self.ckpt_dir = os.path.join(run_dir, "ckpt")
        self.shutdown = GracefulShutdown()   # handlers installed in fit()
        policy = ResiliencePolicy(
            max_consecutive_skips=(cfg.max_consecutive_skips
                                   if cfg.nonfinite_guard else 0),
            loss_spike_factor=cfg.loss_spike_factor,
            loss_ema_beta=cfg.loss_ema_beta,
            lr_backoff=cfg.lr_backoff,
            max_rollbacks=cfg.max_rollbacks)
        self.monitor = ResilienceMonitor(policy) if policy.active else None
        if self.monitor is not None and self.trace is not None:
            # instant marker the moment an anomaly first goes pending, so
            # the trace shows detection separately from the (later,
            # boundary-deferred) rollback span
            self.monitor.add_anomaly_hook(
                lambda reason, step: self.trace.instant(
                    "anomaly_pending", reason=reason, step=step))

        # ---- adaptive policy engine (docs/ADAPTIVE.md) ----
        # default 'static' builds NO engine object at all: the train loop's
        # policy branch is `if self.engine is not None` and everything else
        # is untouched, so static runs stay bit-identical to pre-policy
        # behavior
        self.engine = None
        if cfg.policy == "adaptive":
            if self.is_dense_only:
                raise ValueError(
                    "--policy adaptive retunes the sparse exchange; "
                    "--compressor none has no knobs to retune")
            from ..policy import PolicyEngine, default_rules
            self.engine = PolicyEngine(
                default_rules(cfg),
                publish=lambda event, payload: self.bus.publish(
                    {"event": event, **payload}),
                knobs=self._policy_knobs())
            # the engine rides the bus as an exporter: its emit() only
            # ingests signals (never publishes — the bus lock is held)
            self.bus.attach(self.engine)

        # ---- run-health monitor (docs/OBSERVABILITY.md "Run health") ----
        # same opt-in gating as tracing/policy: default 'off' attaches
        # nothing and publishes nothing, so the stream stays
        # byte-identical to pre-health builds. The monitor ingests as a
        # bus exporter; the verdict pass runs on this thread inside
        # _log_train, which is also the only publish site — and because
        # the published health_status records flow back through the bus
        # fan-out, the policy engine's signals pick them up with no extra
        # wiring (a non-ok state gates exploration, policy/engine.py)
        self.health: Optional[HealthMonitor] = None
        self._health_server: Optional[HealthServer] = None
        if cfg.health == "on" or cfg.health_port is not None:
            self.health = HealthMonitor(density_target=cfg.density)
            self.bus.attach(self.health)
            if cfg.health_port is not None:
                self._health_server = HealthServer(
                    self.health, port=cfg.health_port,
                    prom_path=cfg.prom_textfile).start()
                self.logger.info("health endpoint: http://127.0.0.1:%d"
                                 "/healthz", self._health_server.port)

        # ---- eval step: shard_map'd sum-reduce over dp ----
        eval_fn = make_eval_fn(self.spec, recurrent=self.recurrent,
                               input_norm=input_norm)
        axes = tuple(self.mesh.axis_names)
        self._eval_bs = eval_bs

        def eval_step(params, mstate, batch, *carry):
            if self.recurrent:
                sums, new_carry = eval_fn(params, mstate, batch, carry[0])
            else:
                sums, new_carry = eval_fn(params, mstate, batch), None
            sums = jax.tree.map(lambda x: jax.lax.psum(x, axes), sums)
            return (sums, new_carry) if self.recurrent else sums

        batch_in = self._batch_spec if self.sp else P(axes)
        in_specs = (P(), P(), batch_in) + ((P(axes),) if self.recurrent
                                           else ())
        out_specs = (P(), P(axes)) if self.recurrent else P()
        self.eval_step = jax.jit(shard_map(
            eval_step, mesh=self.mesh,
            in_specs=in_specs, out_specs=out_specs, check_vma=False))

        # ---- resume ----
        # a dir resumes from the newest restorable checkpoint (sealed-only
        # listing + corrupt-fallback, training/checkpoint.py); an explicit
        # step_XXXXXXXX path is trusted as given (fail loud if damaged)
        if cfg.resume:
            path = None
            if os.path.basename(cfg.resume).startswith("step_"):
                self.state = restore_checkpoint(
                    cfg.resume, self.state, self.mesh,
                    padded_numel=self.ts.ef_numel,
                    on_elastic=self._on_elastic_restore)
                path = cfg.resume
            else:
                try:
                    self.state, path = restore_latest_good(
                        cfg.resume, self.state, self.mesh,
                        on_skip=self._log_restore_skip,
                        padded_numel=self.ts.ef_numel,
                        on_elastic=self._on_elastic_restore)
                except FileNotFoundError:
                    # nothing committed yet (fresh run dir) — start cold,
                    # same as the pre-resilience behavior
                    path = None
            if path:
                self.logger.info("resumed from %s (step %d)", path,
                                 int(self.state.step))

        self.logger.info(
            "model=%s dataset=%s params=%.2fM workers=%d global_bs=%d "
            "compressor=%s kernel=%s density=%g buckets=%d k_total=%d "
            "steps/epoch=%d total_steps=%d",
            cfg.dnn, cfg.dataset, n_params / 1e6, self.nworkers,
            local_bs, self._comp.name, self.ts.kernel_mode, cfg.density,
            len(self.plan.buckets), self.plan.total_k,
            self.steps_per_epoch, self.total_steps)
        self.bus.publish({"event": "config", **{
            k: getattr(cfg, k) for k in ("dnn", "dataset", "batch_size",
                                         "compressor", "density", "lr")},
            "nworkers": self.nworkers, "n_params": n_params,
            "total_steps": self.total_steps})
        # jax.profiler trace window, armed for cfg.profile_steps — the
        # session owns start/stop state and records the covered steps as
        # `profile` events on the bus (telemetry/profiler.py)
        self.profiler = (ProfilerSession(
            os.path.join(run_dir, "profile"), cfg.profile_steps[0],
            cfg.profile_steps[1], bus=self.bus, logger=self.logger)
            if cfg.profile_steps else None)

    def _build_mesh(self, cfg: TrainConfig) -> None:
        if self.sp:
            if cfg.dnn.lower() not in ("transformer_lm", "transformerlm"):
                raise ValueError(
                    "sequence parallelism (--sp-size) is the transformer_lm "
                    "long-context path")
            if cfg.ici_size or cfg.dcn_size:
                raise ValueError(
                    "--sp-size and --ici-size/--dcn-size are mutually "
                    "exclusive mesh layouts")
            dp = cfg.nworkers if cfg.nworkers > 0 else (
                len(jax.devices()) // self.sp)
            self.mesh = dp_sp_mesh(dp, self.sp)
            self.nworkers = dp          # dp width: examples per step = bs*dp
        elif cfg.ici_size > 0 and cfg.dcn_size > 0:
            self.mesh = hierarchical_dp_mesh(cfg.ici_size, cfg.dcn_size)
            self.nworkers = self.mesh.size
        else:
            n = cfg.nworkers if cfg.nworkers > 0 else None
            self.mesh = data_parallel_mesh(n)
            self.nworkers = self.mesh.size

    def _build_model(self, cfg: TrainConfig, card: int, dtype):
        """The model's spec, its initial ``params`` and ``model_state``,
        and the key the train state starts from."""
        # ---- model: head size = explicit flag > dataset cardinality;
        # cfg.model_kwargs overrides EVERYTHING (single merged dict, so a
        # key like num_classes/dtype overrides instead of raising a
        # duplicate-keyword TypeError) ----
        model_kw = {"num_classes": cfg.num_classes or card, "dtype": dtype}
        if cfg.dnn.lower() in models_lib.TOKEN_MODELS:
            model_kw["vocab_size"] = cfg.num_classes or card
        elif cfg.dnn.lower() == "lstman4":
            model_kw["num_labels"] = cfg.num_classes or card
        model_kw.update(cfg.model_kwargs)
        if self.sp:
            model_kw["sp_axis"] = "sp"
        self.spec = models_lib.get_model(cfg.dnn, cfg.dataset, **model_kw)
        # mesh axis names only exist inside shard_map: initialize params via
        # the sp-free twin (identical param structure)
        init_module = (models_lib.get_model(
            cfg.dnn, cfg.dataset, **{**model_kw, "sp_axis": None}).module
            if self.sp else self.spec.module)
        self.steps_per_epoch = self.train_ds.steps_per_epoch
        self.total_steps = (cfg.max_steps if cfg.max_steps
                            else cfg.epochs * self.steps_per_epoch)

        # ---- init model variables ----
        rng = jax.random.PRNGKey(cfg.seed)
        init_rng, self.data_rng, state_rng = jax.random.split(rng, 3)
        dummy = self._dummy_inputs()
        # jitted: ONE compiled program (persistently cacheable) instead of
        # an eager compile per initializer primitive and parameter shape
        def init_variables(rngs, *inputs):
            return init_module.init(rngs, *inputs, train=False)

        variables = jax.jit(init_variables)(
            {"params": init_rng, "dropout": init_rng}, *dummy)
        params = variables["params"]
        model_state = {k: v for k, v in variables.items() if k != "params"}
        return params, model_state, state_rng

    def _build_program(self, cfg: TrainConfig, params, model_state,
                       state_rng, local_bs: int):
        """The compression plan, the loss, the step programs and the
        placed initial state. Returns the input normalisation, which the
        eval step shares."""
        # ---- compression plan + loss fn (static across step rebuilds) ----
        # LSTM bptt carry across windows (the reference's "repackaging",
        # SURVEY.md §3.2): hidden state lives in TrainState.carry,
        # batch-dim sharded; reset at epoch boundaries (train loop).
        self.recurrent = (cfg.dnn.lower() == "lstm" and cfg.carry_hidden)
        self._comp = get_compressor(cfg.compressor, density=cfg.density,
                                    sigma_scale=cfg.sigma_scale)
        self.plan = plan_for_params(params, cfg.density, cfg.bucket_size,
                                    policy=cfg.bucket_policy)
        # uint8 pixel batches (imagenet contract) normalize ON DEVICE —
        # the dtype check inside _prep_pixels is trace-time static, so
        # float batches pay nothing
        from .losses import IMAGENET_NORM
        input_norm = (IMAGENET_NORM if cfg.dataset.lower() == "imagenet"
                      else None)
        self._loss_fn = make_loss_fn(self.spec, cfg.label_smoothing,
                                     recurrent=self.recurrent,
                                     input_norm=input_norm)
        self.is_dense_only = self._comp.name == "none"

        # ---- schedule + optimizer + the fused step programs ----
        self._lr_scale = 1.0            # compounded rollback LR backoff
        self._build_steps()
        carry = (self.spec.module.initial_carry(local_bs)
                 if self.recurrent else ())
        self.state = self.ts.init_state(params, state_rng,
                                        model_state=model_state, carry=carry)
        return input_norm

    # ------------------------------------------------------------------
    def _build_steps(self) -> None:
        """(Re)build schedule + inner optimizer + the jitted step programs
        at the current ``_lr_scale``. Called at construction and again
        after a rollback (the backoff-scaled LR is baked into the traced
        programs, so they must recompile — rollback-rare, and the
        persistent compile cache usually softens it)."""
        cfg = self.cfg
        base = warmup_milestone_schedule(
            cfg.lr, self.nworkers, self.steps_per_epoch, self.total_steps,
            cfg.warmup_epochs, cfg.lr_milestones, cfg.lr_decay)
        scale = self._lr_scale
        self.schedule = (base if scale == 1.0
                         else (lambda s: base(s) * scale))
        # torch-SGD-equivalent chain (SURVEY.md §3.1)
        chain = []
        if cfg.weight_decay:
            # wd applied to the *exchanged* gradient, before momentum — the
            # torch SGD placement the reference inherits (SURVEY.md §3.1)
            chain.append(optax.add_decayed_weights(cfg.weight_decay))
        lr_for_opt = (lambda s: 1.0) if cfg.fold_lr else self.schedule
        chain.append(optax.sgd(lr_for_opt, momentum=cfg.momentum or None,
                               nesterov=cfg.nesterov))
        optimizer = optax.chain(*chain)
        # the flat sparse-aware update (parallel/flat_opt.py) covers the
        # torch-SGD-equivalent chain exactly (wd-before-momentum, schedule
        # on the lr) on 1-D meshes; nesterov/fold-lr/hierarchical fall
        # back to the optax path
        from ..parallel.flat_opt import FlatSGDM
        flat_opt = None
        if (not cfg.nesterov and not cfg.fold_lr
                and len(self.mesh.axis_names) == 1
                and (cfg.momentum or cfg.weight_decay)):
            # momentum-less, decay-less SGD needs NO optimizer state; the
            # flat path would still allocate and rewrite an n-sized zero
            # momentum buffer every step (wasted HBM traffic + a checkpoint
            # format change), so such runs stay on the optax path (ADVICE r5)
            flat_opt = FlatSGDM(lr=self.schedule,
                                momentum=cfg.momentum or 0.0,
                                weight_decay=cfg.weight_decay or 0.0)
        self.ts = build_dp_train_step(
            self._loss_fn,
            None if flat_opt is not None else optimizer, self._comp,
            self.plan, self.mesh,
            num_microbatches=cfg.nsteps_update,
            clip_norm=cfg.clip_norm,
            fold_lr=self.schedule if cfg.fold_lr else None,
            recurrent=self.recurrent,
            exchange=cfg.exchange,
            sp_axis="sp" if self.sp else None,
            flat_opt=flat_opt,
            guard_nonfinite=cfg.nonfinite_guard,
            decorrelate_comp_rng=cfg.decorrelate_comp_rng,
            wire=cfg.wire,
            overlap=cfg.overlap,
        )

    # ------------------------------------------------------------------
    @property
    def state(self):
        return self._state

    @state.setter
    def state(self, new_state) -> None:
        """Overwriting the state from OUTSIDE the train loop (resume,
        rollback, elastic handoff, tests assigning a restored state) moves
        ``state.step``, so the cached data iterator — which aligned its
        epoch/skip position to the OLD step when first built — would
        silently replay the wrong epoch position, and the cached Python
        step counter would desynchronize. Route every external assignment
        through this setter so both caches die with the stale step. The
        train loop itself advances ``self._state`` directly (its step
        increments match the stream position, and tearing down the
        prefetch thread every step would defeat it)."""
        self._state = new_state
        self._invalidate_data_iter()
        self.__dict__.pop("_step_cache", None)
        # a step the loop had dispatched ahead ran on the state this one
        # replaces: it is dropped with it, never waited for nor reported
        self._flight: Optional[_Flight] = None
        # external assignment starts a NEW trajectory: steps re-reached
        # after a resume-from-older/rollback may collide with sealed
        # checkpoints of the old one, which must be overwritten, not
        # idempotently skipped (_save_checkpoint)
        self._saved_steps: set = set()

    def _invalidate_data_iter(self) -> None:
        # the orphaned prefetch daemon thread (if any) parks on its full
        # queue and dies with the process — bounded by max_rollbacks, not
        # worth a teardown protocol
        self._iter = None

    def _span(self, name: str, **fields):
        """Host-phase span when tracing is on, else the shared nullcontext —
        call sites stay unconditional and trace-off stays zero-record."""
        return (self.trace.span(name, **fields) if self.trace is not None
                else _NO_SPAN)

    def _drain_spans(self) -> None:
        """Publish the spans finished since the last log step: the bus is
        not touched for them in between. What that costs the loop is a span
        of its own, ``trace_drain``."""
        if self.trace is not None:
            with self.trace.span("trace_drain"):
                self.trace.drain()

    def _rotate_trajectory(self, reason: str) -> None:
        """A rollback abandons the old trajectory: close its span and open
        a fresh root so post-rollback records parent to the new one."""
        if self.trace is None:
            return
        if self._traj_span is not None:
            self.trace.end(self._traj_span, reason=reason)
        self._traj_span = self.trace.begin("trajectory", root=True,
                                           step=self.step)

    # ------------------------------------------------------------------
    def _save_checkpoint(self) -> str:
        """Seal a checkpoint for the current step. A step already saved by
        THIS trajectory (e.g. epoch-boundary + final save landing on the
        same step) is an idempotent no-op; a step first reached on this
        trajectory OVERWRITES any sealed dir a previous trajectory left
        there (resume-from-an-older-checkpoint, post-rollback replay with
        a backed-off LR) — silently keeping the stale state would poison a
        later resume/rollback."""
        step = self.step
        with self._span("checkpoint_save", step=step):
            # unpadded_numel strips the fused-EF block pad (identity on
            # unpadded runs) so the on-disk format stays [P, total_numel]
            path = save_checkpoint(self.ckpt_dir, self._state,
                                   overwrite=step not in self._saved_steps,
                                   unpadded_numel=self.plan.total_numel)
            self._saved_steps.add(step)
            self.bus.publish({"event": "checkpoint", "step": step,
                              "path": path})
            if self.cfg.keep_checkpoints:
                removed = gc_checkpoints(self.ckpt_dir,
                                         self.cfg.keep_checkpoints)
                for r in removed:
                    self.logger.info("checkpoint GC: removed %s", r)
        return path

    def _log_restore_skip(self, path: str, exc: Exception) -> None:
        self.logger.warning("restore fallback: skipping %s (%s: %s)",
                            path, type(exc).__name__, exc)
        self.bus.publish({"event": "restore_fallback", "checkpoint": path,
                          "error": f"{type(exc).__name__}: {exc}"})

    def _on_elastic_restore(self, old_p: int, new_p: int) -> None:
        """The checkpoint being restored was written at a different
        worker count (elastic resize, service/): log the geometry change
        and drop the policy engine's geometry-derived signals — step-time
        and per-arm EMAs, bytes/step, EF-pressure window — so decisions
        after the re-mesh are never anchored on measurements of a mesh
        that no longer exists (policy/signals.py reset_for_geometry)."""
        self.logger.info(
            "elastic restore: checkpoint written at %d worker(s), "
            "resuming at %d — EF mass redistributed, carry reset, "
            "geometry-derived policy signals dropped", old_p, new_p)
        if self.engine is not None:
            self.engine.signals.reset_for_geometry(new_p)

    def _rollback(self, reason: str) -> None:
        """Automatic divergence recovery (docs/RESILIENCE.md): restore the
        newest restorable checkpoint OLDER than the observed anomaly (a
        checkpoint sealed at/after it already holds the diverged state),
        back off the LR, rebuild the step programs, and realign the data
        stream — the error-feedback residual, optimizer state, and step
        counter all rewind together because they are one checkpointed
        TrainState."""
        anomaly_step = self.monitor.pending_since
        n = self.monitor.note_rollback()   # raises when budget exhausted
        self._lr_scale = self.monitor.lr_scale
        try:
            try:
                state, path = restore_latest_good(
                    self.ckpt_dir, self._state, self.mesh,
                    on_skip=self._log_restore_skip,
                    before_step=anomaly_step,
                    padded_numel=self.ts.ef_numel)
            except FileNotFoundError:
                if anomaly_step is None:
                    raise
                # every sealed checkpoint is at/after the anomaly — the
                # pre-divergence trajectory was never saved. Restore the
                # newest anyway: only the LR backoff helps then, but it
                # beats killing the run while rollback budget remains.
                self.logger.warning(
                    "rollback: no checkpoint precedes anomalous step %d; "
                    "restoring the newest sealed one instead",
                    anomaly_step)
                state, path = restore_latest_good(
                    self.ckpt_dir, self._state, self.mesh,
                    on_skip=self._log_restore_skip,
                    padded_numel=self.ts.ef_numel)
        except (FileNotFoundError, RuntimeError) as e:
            raise RuntimeError(
                f"rollback ({reason}) has no restorable checkpoint under "
                f"{self.ckpt_dir!r} — enable save_every_steps so a "
                f"rollback target exists (docs/RESILIENCE.md)") from e
        to_step = int(jax.device_get(state.step))
        self.bus.publish({"event": "rollback", "reason": reason,
                          "rollback": n, "to_step": to_step,
                          "lr_scale": self._lr_scale, "checkpoint": path})
        # the rewound steps' timings describe the abandoned trajectory;
        # post-rollback throughput must not average them in
        self.tracker.reset()
        self.logger.warning(
            "rollback #%d (%s): restored %s (step %d), lr_scale=%g",
            n, reason, path, to_step, self._lr_scale)
        self._build_steps()
        self.state = state      # setter: drops data iter + step cache

    # ------------------------------------------------------------------
    # adaptive policy plumbing (docs/ADAPTIVE.md)
    def _policy_knobs(self) -> Dict[str, str]:
        """Current knob values in the string form PolicyDecisions carry."""
        from ..policy import (KNOB_BUCKET, KNOB_COMPRESSOR, KNOB_DENSITY,
                              KNOB_OVERLAP, KNOB_WIRE)
        cfg = self.cfg
        size = "" if cfg.bucket_size is None else str(cfg.bucket_size)
        return {KNOB_COMPRESSOR: self._comp.name,
                KNOB_DENSITY: f"{cfg.density:g}",
                KNOB_WIRE: cfg.wire,
                KNOB_BUCKET: f"{cfg.bucket_policy}:{size}",
                KNOB_OVERLAP: cfg.overlap}

    def _apply_policy(self, decision) -> None:
        """Apply one PolicyDecision at the recompile-safe boundary: mutate
        the knob, rebuild compressor/plan as needed, rebuild the jitted
        programs, and re-shape the live TrainState for the new program
        layout (:meth:`_rebuild_for_policy`)."""
        from ..policy import (KNOB_BUCKET, KNOB_COMPRESSOR, KNOB_DENSITY,
                              KNOB_OVERLAP, KNOB_WIRE)
        cfg = self.cfg
        knob, value = decision.knob, decision.new
        if knob == KNOB_COMPRESSOR:
            self._comp = get_compressor(value, density=cfg.density,
                                        sigma_scale=cfg.sigma_scale)
            cfg.compressor = value
        elif knob == KNOB_DENSITY:
            cfg.density = float(value)
            self._comp = get_compressor(cfg.compressor, density=cfg.density,
                                        sigma_scale=cfg.sigma_scale)
            # per-bucket k is derived from density: the plan must re-derive
            self.plan = plan_for_params(self._state.params, cfg.density,
                                        cfg.bucket_size,
                                        policy=cfg.bucket_policy)
        elif knob == KNOB_WIRE:
            cfg.wire = value
        elif knob == KNOB_OVERLAP:
            # a program-layout change like density/bucket-plan: the engine's
            # note_applied/note_reverted non-compressor branch resets every
            # arm's step-time records and charges the recompile budget —
            # timings measured under the other schedule are not comparable
            cfg.overlap = value
        elif knob == KNOB_BUCKET:
            pol, _, size = value.partition(":")
            cfg.bucket_policy = pol
            cfg.bucket_size = int(size) if size else None
            self.plan = plan_for_params(self._state.params, cfg.density,
                                        cfg.bucket_size,
                                        policy=cfg.bucket_policy)
        else:
            raise ValueError(f"unknown policy knob {knob!r}")
        with self._span("policy_rebuild", knob=knob):
            self._rebuild_for_policy()

    def _rebuild_for_policy(self) -> None:
        """Rebuild the step programs for retuned knobs and migrate the
        live TrainState across the layout change. Params/opt/step/rng are
        layout-invariant; the EF residual follows the checkpoint-edge
        contract (strip the fused-EF block pad to the canonical
        [P, total_numel], re-pad for the new program — one bounded host
        round-trip, never in the jitted path); a stateful compressor's
        warm-threshold carry is re-initialized fresh (its old thresholds
        priced a different selector/plan)."""
        old_ef = self.ts.ef_numel
        state = self._state
        self._build_steps()
        new_ef = self.ts.ef_numel
        ef = state.ef_residual
        nworkers = self.mesh.size
        if new_ef != old_ef:
            n = self.plan.total_numel
            mat = np.asarray(jax.device_get(ef)).reshape(
                nworkers, old_ef)[:, :n]
            pad = np.zeros((nworkers, new_ef), mat.dtype)
            pad[:, :n] = mat
            ef = pad.reshape(-1)
        # init_state re-shards EF and builds a right-shaped comp_state for
        # the new program; everything trajectory-carrying is copied over
        fresh = self.ts.init_state(state.params, state.rng,
                                   model_state=state.model_state,
                                   carry=state.carry)
        fresh = fresh._replace(
            step=state.step, opt_state=state.opt_state,
            ef_residual=jnp.asarray(ef))
        self.state = fresh      # setter: drops data iter + step cache

    def _policy_tick(self, rollback_pending: bool) -> None:
        """One boundary tick of the closed loop: probation watchdog first
        (a bad decision reverts BEFORE any rollback executes, so the
        restored checkpoint meets the pre-decision program layout), then —
        quiet intervals only — the next decision. Every apply/revert seals
        a checkpoint so a later rollback always has a target matching the
        current layout."""
        eng = self.engine
        revert = eng.check_revert(rollback_pending=rollback_pending)
        if revert is not None:
            with self._span("policy_apply", knob=revert.knob,
                            reason=revert.reason):
                self._apply_policy(revert)
            eng.note_reverted(revert)
            self.logger.warning("policy revert %s: %s -> %s (%s)",
                                revert.knob, revert.old, revert.new,
                                revert.reason)
            if not rollback_pending:
                self._save_checkpoint()
            return
        if rollback_pending:
            return
        decision = eng.decide()
        if decision is not None:
            with self._span("policy_apply", knob=decision.knob,
                            reason=decision.reason):
                self._apply_policy(decision)
            eng.note_applied(decision)
            self.logger.info("policy decision [%s] %s: %s -> %s (%s)",
                             decision.rule, decision.knob, decision.old,
                             decision.new, decision.reason)
            self._save_checkpoint()

    # ------------------------------------------------------------------
    def _dummy_inputs(self):
        shape = (2,) + self.spec.input_shape
        if self.spec.task == "seq2seq":
            return (jnp.ones(shape, jnp.int32), jnp.ones(shape, jnp.int32))
        return (jnp.zeros(shape, self.spec.input_dtype),)

    @property
    def step(self) -> int:
        return int(jax.device_get(self.state.step))

    @property
    def epoch(self) -> int:
        return self.step // self.steps_per_epoch

    def _in_warmup(self, step: int) -> bool:
        return self.is_dense_only or step < self.cfg.compress_warmup_steps

    # ------------------------------------------------------------------
    def train(self, num_iters: int, data_iter=None) -> Dict[str, float]:
        """Run ``num_iters`` optimizer steps (reference ``trainer.train(n)``,
        SURVEY.md §1.1 L4->L3 interface). Returns the last log record.

        The loop keeps ONE step in flight: iteration t pulls and places
        batch t and dispatches step t on the state that step t-1 returns,
        not yet ready, and only then waits for step t-1, reads its scalars
        and logs it. The device has its next program and input queued while
        it runs the last one; the programs, their arguments and their order
        are those of a loop that waits for every step. The call pulls
        exactly ``num_iters`` batches and returns when all its steps have
        ended. It does not run ahead of a step after which the host acts on
        the finished state (:meth:`_host_acts_after`, and the call's last).

        With tracing on every iteration is one ``iteration`` span whose
        leaf children each hold exactly one thing (docs/OBSERVABILITY.md):
        ``data_wait``, ``h2d``, ``step_dispatch`` for the step it
        dispatches; ``step_sync``, ``step_readback`` and, at a log step,
        ``log_step`` and ``trace_drain`` for each step it waits for (the
        one before; its own too where the loop does not run ahead); what
        is left of the iteration beside them is the loop's own
        bookkeeping."""
        last: Dict[str, float] = {}
        ended: Optional[_Flight] = None
        # a call that raised may have left a step in flight: its state is
        # the trainer's, its scalars are lost
        self._flight = None
        for i in range(num_iters):
            # cached step — no device sync
            step = self.step if not hasattr(self, "_step_cache") else \
                self._step_cache
            with self._span("iteration", step_num=step + 1):
                behind = self._flight
                self._flight = self._dispatch(step, data_iter)
                if behind is not None:
                    last = self._finish(behind, ahead=1) or last
                    ended = behind
                # None where a rollback at that log step dropped it with
                # the state it restored over (the state setter)
                flight = self._flight
                if flight is not None and (
                        i + 1 == num_iters or self.shutdown.requested
                        or self._host_acts_after(flight.done)):
                    self._flight = None
                    last = self._finish(flight, ahead=0) or last
                    ended = flight
        if ended is not None and not last:
            with self._span("log_step"):
                last = self._log_train(ended.done, ended.metrics, quiet=True)
            self._drain_spans()
        return last

    def _host_acts_after(self, done: int) -> bool:
        """Whether the host reads or replaces the finished state once
        global step ``done`` has ended, so that step ``done + 1`` must not
        be dispatched before: a cadence save; a log step at which the
        policy engine may rebuild the programs; a profiler window that
        opens or closes there. Known from the step number and the
        configuration alone."""
        cfg = self.cfg
        if cfg.save_every_steps and done % cfg.save_every_steps == 0:
            return True
        if done % cfg.log_every == 0 and self.engine is not None:
            return True
        return (self.profiler is not None
                and self.profiler.transition_due(done))

    def _input_fields(self) -> Dict[str, Any]:
        """What the ``data_wait`` span records of the input path as the
        loop asks for a batch: ``ready``, the batches waiting in the
        trainer's own prefetch queue (whoever pulls from it);
        ``assemble_ms``, what its producer thread took for the newest
        batch it pulled; ``fresh``, the batch buffers the data package has
        allocated so far. The first two only while that queue runs."""
        fields: Dict[str, Any] = {"fresh": data_lib.batch_buffers.fresh}
        it = getattr(self, "_iter", None)
        if it is not None:
            fields["ready"] = it.ready()
            if it.assemble_s is not None:
                fields["assemble_ms"] = round(it.assemble_s * 1e3, 3)
        return fields

    def _dispatch(self, step: int, data_iter) -> _Flight:
        """The first half of the optimizer step from global step ``step``:
        wait for the batch, place it, dispatch the program on the state as
        it stands (ready or not). ``_state`` and ``_step_cache`` move here,
        together."""
        cfg = self.cfg
        # resolved per iteration: a rollback mid-run invalidates the
        # cached iterator, and the rebuilt one must be picked up here
        it = data_iter if data_iter is not None else self._train_iter()
        t_io = time.perf_counter()
        with (self.trace.span("data_wait", **self._input_fields())
              if self.trace is not None else _NO_SPAN):
            batch = next(it)
        with self._span("h2d"):
            batch = shard_batch(self.mesh, batch, spec=self._batch_spec)
        self._probe_batch = batch      # for _maybe_probe_mfu at log time
        io_s = time.perf_counter() - t_io
        if self.profiler is not None:
            # jax.profiler trace window (SURVEY.md §5 Tracing rebuild
            # note: real fwd/bwd/comm breakdown comes from device
            # traces, not host timers)
            self.profiler.maybe_transition(step)
        if (self.recurrent and step % self.steps_per_epoch == 0
                and step > 0):
            # fresh text stream at each epoch wrap -> fresh carry
            # (direct _state write: the loop's own advances must not
            # trip the external-assignment invalidation in the setter)
            self._state = self._state._replace(carry=jax.tree.map(
                jnp.zeros_like, self._state.carry))
        fn = (self.ts.dense_step if self._in_warmup(step)
              else self.ts.sparse_step)
        t0 = time.perf_counter()
        with self._span("step_dispatch"):
            self._state, m = fn(self._state, batch)
        self._step_cache = step + 1
        return _Flight(step + 1, m, t0, io_s, time.perf_counter() - t0)

    def _finish(self, flight: _Flight, ahead: int) -> Dict[str, float]:
        """The second half, for a step that was dispatched: wait for it,
        read its scalars back, do what its boundary owes (cadence save,
        preemption) and, at a log step, log it. ``ahead`` is the number of
        step programs dispatched behind it (0 or 1). Returns the log
        record, empty where it is no log step."""
        cfg, done, m = self.cfg, flight.done, flight.metrics
        t_sync = time.perf_counter()
        with self._span("step_sync", ahead=ahead):
            # jit dispatch is async: the step has ended when its loss is
            # ready
            jax.block_until_ready(m.loss)
        now = time.perf_counter()
        # one step's end to the next; from its own dispatch for a step
        # dispatched after the last one had ended. So ex/s is the loop's
        # rate, run ahead or not
        step_wall = now - max(flight.t0, self._t_synced)
        self._t_synced = now
        # io_s + step_s: what the host spent on the step, on its input and
        # on dispatching and awaiting its program
        self.timers.add("io", flight.io_s)
        self.timers.add("step", flight.dispatch_s + now - t_sync)
        with self._span("step_readback"):
            # m.loss is already synced above, so these per-step host reads
            # cost a device_get of ready scalars, not a sync. Guard-off
            # runs skip the read: skipped is a structural zero there.
            sk = (float(jax.device_get(m.skipped))
                  if cfg.nonfinite_guard else 0.0)
            # skipped steps burn wall-clock but train on nothing — they
            # must not inflate ex/s (telemetry/throughput.py)
            self.tracker.update(cfg.global_batch_size, step_wall,
                                skipped=bool(sk))
            if sk:
                nf = float(jax.device_get(m.nonfinite))
                self.bus.publish({"event": "skip", "step": done,
                                  "nonfinite": nf})
                self.logger.warning(
                    "step %d skipped by in-step guard (%g non-finite "
                    "grad entries); state unchanged", done, nf)
            if self.monitor is not None:
                self.monitor.observe(done, float(jax.device_get(m.loss)),
                                     sk)
        pending = (self.monitor.should_rollback()
                   if self.monitor is not None else None)
        # the loop does not run ahead of a cadence save's step, so the
        # state here is the one after ``done``
        if cfg.save_every_steps and done % cfg.save_every_steps == 0:
            if pending is None:
                path = self._save_checkpoint()
                self.logger.info("checkpoint -> %s", path)
            else:
                # sealing the live state while a rollback is pending
                # would make the suspect/diverged state the newest —
                # and therefore the rollback target — checkpoint
                self.logger.warning(
                    "cadence save at step %d suppressed: rollback "
                    "pending (%s)", done, pending)
        if self.shutdown.requested and not ahead:
            # preemption contract (docs/RESILIENCE.md): seal a
            # checkpoint at the step boundary, then exit cleanly. With a
            # step in flight that boundary is the end of that step: the
            # loop waits for it next
            path = self._save_checkpoint()
            self.bus.publish({"event": "preempt", "step": done,
                              "checkpoint": path})
            self.logger.warning(
                "shutdown requested: checkpointed %s at step %d",
                path, done)
            raise TrainingPreempted(done, path)
        if done % cfg.log_every == 0:
            return self._log_step(done, m)
        return {}

    def _log_step(self, done: int, m) -> Dict[str, float]:
        """What every ``log_every``-th iteration adds: the train record,
        the finished spans' way onto the bus, and the acts that wait for
        an interval's end (policy tick, rollback)."""
        with self._span("log_step"):
            last = self._log_train(done, m)
        self._drain_spans()
        # policy/resilience ACT only at log intervals (ISSUE
        # contract); between intervals they only accumulate
        # observations. Order matters: the engine's probation
        # watchdog runs BEFORE a pending rollback executes, so a
        # bad decision's knobs are reverted first and the restored
        # checkpoint meets the pre-decision program layout.
        reason = (self.monitor.should_rollback()
                  if self.monitor is not None else None)
        # no ticks during dense warm-up: every signal gathered so
        # far describes the dense program (ef_norm is structurally
        # 0, no wire/density in play), so a decision here could
        # only misfire — and nothing can need reverting, since no
        # decision has ever applied
        if self.engine is not None and not self._in_warmup(done):
            self._policy_tick(rollback_pending=reason is not None)
        if reason:
            # the rollback span closes inside the OLD trajectory
            # (it is that trajectory's terminal act); only then is
            # the root rotated for the restored one
            with self._span("rollback", reason=reason):
                self._rollback(reason)
            self._rotate_trajectory(reason)
        return last

    def _train_iter(self):
        if getattr(self, "_iter", None) is None:
            self._iter = iter(data_lib.prefetch(
                self._stream(), depth=2,
                max_retries=self.cfg.io_retries,
                backoff_s=self.cfg.io_backoff_s,
                on_event=self._io_event))
        return self._iter

    def _io_event(self, rec: Dict[str, Any]) -> None:
        # runs on the prefetch thread; EventBus.publish is lock-serialized
        self.bus.publish(rec)
        self.logger.warning(
            "data io retry %s/%s after %s (backoff %.3gs)",
            rec.get("attempt"), rec.get("max_retries"), rec.get("error"),
            rec.get("backoff_s", 0.0))

    def _stream(self):
        """Epoch stream aligned to the current step — a resumed run
        continues with the SAME epoch shuffle order and position an
        uninterrupted run would see (exact data-iterator resume,
        SURVEY.md §5 checkpoint rebuild note). Class-based/resumable
        (data_lib.EpochStream), NOT a generator: prefetch's transient-IO
        retry must be able to re-pull after a raise — a generator dies on
        its first raise and would turn io_retries into a silent
        end-of-stream."""
        return data_lib.EpochStream(self.train_ds, self.cfg.seed, self.step)

    def _maybe_probe_mfu(self, fn) -> None:
        """Resolve flops/step + device peak once (lazily, off the first
        logged interval) so the tracker can report MFU. Runs only where
        the mesh's chip has a peak on record (TPU; on CPU the field is
        absent). There a failed cost analysis is an error, not a quietly
        missing field. ``program_flops`` lowers and compiles ``fn`` a
        second time — a cache hit, because the caller passes the program
        that just ran; the log line says how long it took."""
        if self._mfu_probed or getattr(self, "_probe_batch", None) is None:
            return
        self._mfu_probed = True
        self._peak_flops = throughput.device_peak_flops(
            self.mesh.devices.flat[0])
        if self._peak_flops is None:
            return
        t0 = time.perf_counter()
        self._flops_per_step = throughput.program_flops(
            fn, self._state, self._probe_batch)
        if self._flops_per_step is None:
            raise RuntimeError(
                "XLA cost analysis reported no FLOPs for the step program "
                "on a TPU mesh; mfu cannot be computed")
        self.logger.info(
            "mfu probe: %.4g flop/step per chip, peak %.4g flop/s (%.1fs)",
            self._flops_per_step, self._peak_flops,
            time.perf_counter() - t0)

    def _log_train(self, step: int, m, quiet: bool = False):
        loss = float(jax.device_get(m.loss))
        means = self.timers.means()
        lr = float(self.schedule(step))
        # by the step's number: the state may be a later step's by now
        epoch = step // self.steps_per_epoch
        rec = {
            "event": "train", "step": step, "epoch": epoch,
            "loss": loss, "lr": lr,
            "grad_norm": float(jax.device_get(m.grad_norm)),
            "num_selected": float(jax.device_get(m.num_selected)),
            "bytes_sent": int(jax.device_get(m.bytes_sent)),
            "density": self.cfg.density,
            "density_achieved": float(jax.device_get(m.achieved_density)),
            "ef_norm": float(jax.device_get(m.ef_norm)),
            "io_s": means.get("io", 0.0), "step_s": means.get("step", 0.0),
            "skipped": float(jax.device_get(m.skipped)),
            "nonfinite": float(jax.device_get(m.nonfinite)),
        }
        # ``m`` came from the step whose pre-step index is step-1, so the
        # warm-up test must use step-1: _in_warmup(step) flips one
        # interval early and would stamp the last all-dense interval
        # (ef_norm structurally 0, dense allreduce bytes) as sparse —
        # feeding the policy engine a dense sample under a sparse marker
        if not self._in_warmup(step - 1):
            # the payload's wire format travels with every sparse bytes
            # claim (ISSUE 5 protocol: "u16bf16" packed / "i32f32"
            # legacy); warm-up steps move a dense f32 allreduce instead,
            # so the field would be a lie there — omitted
            rec["wire_format"] = self.ts.wire_format
            # which step schedule moved those bytes ("pipelined" | "off")
            # — same sparse-interval gating as wire_format
            rec["overlap"] = self.ts.overlap
            ovl = float(jax.device_get(m.overlapped_bytes_sent))
            if ovl:
                rec["overlapped_bytes_sent"] = int(ovl)
        if len(self.plan.buckets) > 1:
            # per-bucket selection counts (dp-mean); single-bucket plans
            # skip the column — it would duplicate num_selected
            rec["sel_per_bucket"] = [
                round(float(v), 2)
                for v in np.asarray(jax.device_get(m.sel_per_bucket))]
        # the program that produced ``m`` (pre-step index step-1, as above):
        # it has been compiled, so the probe's compile is a cache hit
        self._maybe_probe_mfu(self.ts.dense_step if self._in_warmup(step - 1)
                              else self.ts.sparse_step)
        # ONE canonical tracker snapshot per interval (ISSUE 6 satellite):
        # the log line, the bus record, and the policy engine all read the
        # same consistent numbers instead of racing per-field properties
        sig = self.tracker.signals(self._flops_per_step, self._peak_flops)
        if sig.examples_per_s is not None:
            rec["ex_per_s"] = round(sig.examples_per_s, 3)
        if sig.mfu is not None:
            rec["mfu"] = round(sig.mfu, 5)
        if self.monitor is not None:
            rec["consecutive_skips"] = self.monitor.consecutive_skips
            rec["lr_scale"] = self._lr_scale
        aux = jax.device_get(m.aux)
        rec.update({k: float(v) for k, v in aux.items()})
        self.bus.publish(rec)
        if self.health is not None:
            # one verdict per published train record — the exact cadence
            # replay_health reproduces offline, so the live endpoint, the
            # CLI and the report section agree verdict-for-verdict. The
            # tick reads only host state already synced above: zero extra
            # device syncs
            hrec = self.health.tick(step)
            self.bus.publish(hrec)
            if self.monitor is not None \
                    and hrec["state_code"] >= CRITICAL:
                for cause in hrec["causes"]:
                    if cause in PRE_ARM_CAUSES:
                        # arm the normal rollback path; the boundary
                        # check right after this log call executes it
                        self.monitor.pre_arm(f"health:{cause}", step)
                        break
        if not quiet:
            imgs = self.cfg.global_batch_size / max(rec["step_s"], 1e-9)
            self.logger.info(
                "step %d (ep %d) loss=%.4f lr=%.4g io=%.1fms step=%.1fms "
                "(%.0f ex/s) sent=%dB %s", step, epoch, loss, lr,
                1e3 * rec["io_s"], 1e3 * rec["step_s"], imgs,
                rec["bytes_sent"],
                " ".join(f"{k}={float(v):.4f}" for k, v in aux.items()))
        self.timers.reset()
        return rec

    # ------------------------------------------------------------------
    def test(self, epoch: Optional[int] = None) -> Dict[str, float]:
        """Full eval pass (reference ``trainer.test(epoch)``)."""
        totals: Dict[str, float] = {}
        # LM eval threads hidden state across the contiguous test windows
        # (same repackaging as training; fresh carry per eval pass)
        carry = (self.spec.module.initial_carry(self._eval_bs)
                 if self.recurrent else None)
        for i, batch in enumerate(self.test_ds.epoch()):
            if (self.cfg.eval_max_batches is not None
                    and i >= self.cfg.eval_max_batches):
                break
            batch = shard_batch(self.mesh, batch, spec=self._batch_spec)
            if self.recurrent:
                sums, carry = self.eval_step(
                    self.state.params, self.state.model_state, batch, carry)
                sums = jax.device_get(sums)
            else:
                sums = jax.device_get(self.eval_step(
                    self.state.params, self.state.model_state, batch))
            for k, v in sums.items():
                totals[k] = totals.get(k, 0.0) + float(v)
        n = max(totals.get("n", 1.0), 1.0)
        out = {"val_loss": totals.get("loss_sum", 0.0) / n}
        if "top1" in totals:
            out["top1"] = totals["top1"] / n
        if "top5" in totals:
            out["top5"] = totals["top5"] / n
        if "cer_edit_sum" in totals:
            # character error rate from the greedy CTC decode (VERDICT r3
            # item 5): total edit distance / total reference characters
            out["cer"] = (totals["cer_edit_sum"]
                          / max(totals.get("cer_ref_sum", 1.0), 1.0))
        if self.spec.task == "lm":
            out["perplexity"] = math.exp(min(out["val_loss"], 30.0))
        rec = {"event": "eval", "step": self.step,
               "epoch": epoch if epoch is not None else self.epoch, **out}
        self.bus.publish(rec)
        self.logger.info("eval %s", " ".join(
            f"{k}={v:.4f}" for k, v in out.items()))
        return out

    # ------------------------------------------------------------------
    def fit(self) -> Dict[str, float]:
        """The reference's outer epoch loop (SURVEY.md §3.1), wrapped in
        the resilience runtime: SIGTERM/SIGINT checkpoint-then-exit, and
        step-budgeted saves/rollbacks inside :meth:`train`."""
        cfg = self.cfg
        result: Dict[str, float] = {}
        # signal.signal is a main-thread-only API (CPython); fits driven
        # from worker threads (tests, notebooks) skip the handlers but
        # keep the programmatic shutdown.request() path
        install = (cfg.handle_signals
                   and threading.current_thread() is threading.main_thread())
        if install:
            self.shutdown.install()
        try:
            while self.step < self.total_steps:
                n = min(self.steps_per_epoch, self.total_steps - self.step)
                self.train(n)
                ep = self.epoch
                if cfg.eval_every_epochs and ep % cfg.eval_every_epochs == 0:
                    result = self.test(ep)
                if (cfg.save_every_epochs
                        and ep % cfg.save_every_epochs == 0
                        and (self.monitor is None
                             or self.monitor.should_rollback() is None)):
                    # same suppression as the step-cadence save: a pending
                    # rollback (detected after the last log interval of the
                    # epoch) must not seal the suspect state
                    path = self._save_checkpoint()
                    self.logger.info("checkpoint -> %s", path)
            self._save_checkpoint()
        except TrainingPreempted as e:
            # clean exit: the checkpoint is sealed, the caller decides
            # whether to reschedule (train.py just returns)
            self.logger.warning("training preempted at step %d "
                                "(checkpoint: %s)", e.step, e.ckpt_path)
            result = {**result, "preempted_at": float(e.step)}
        finally:
            if install:
                self.shutdown.uninstall()
        return result

    def close(self):
        if self.profiler is not None:
            self.profiler.close()      # stop a still-live trace first
        if self.trace is not None:
            # seal the trajectory root, then detach the stamp hook so a
            # reused bus never inherits a dead trace context
            self.trace.drain()
            if self._traj_span is not None:
                self.trace.end(self._traj_span)
                self._traj_span = None
            self.trace.uninstall()
        if self._health_server is not None:
            self._health_server.close()
            self._health_server = None
        self.bus.close()
