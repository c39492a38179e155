"""Run configuration — one dataclass, CLI-overridable.

Reference parity: the argparse surface of ``horovod_trainer.py``
(SURVEY.md §2 C6: ``--dnn --dataset --batch-size --lr --nworkers
--nwpernode --nsteps-update --compressor --density --sigma-scale ...``) plus
the hardcoded constants scattered through ``settings.py`` (SURVEY.md §2 C10),
consolidated into a single typed config (SURVEY.md §5 "Config / flag
system" rebuild note).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
from dataclasses import dataclass, field
from typing import List, Optional, Tuple


@dataclass
class TrainConfig:
    # model / data (reference --dnn / --dataset / --data-dir)
    dnn: str = "resnet20"
    dataset: str = "cifar10"
    data_dir: Optional[str] = None          # None/'synthetic' -> synthetic
    num_classes: Optional[int] = None

    # batch geometry (reference --batch-size is PER WORKER; global = bs * P)
    batch_size: int = 32                    # per worker
    nsteps_update: int = 1                  # gradient accumulation factor
    nworkers: int = 1                       # dp size; 0 -> all devices
    ici_size: int = 0                       # >0 with dcn_size: hierarchical
    dcn_size: int = 0                       #   (dcn_dp, ici_dp) mesh
    sp_size: int = 0                        # >1: ring-attention sequence
                                            # parallelism over a (dp, sp)
                                            # mesh (transformer_lm only)

    # optimization (reference SGD defaults)
    lr: float = 0.1
    momentum: float = 0.9
    weight_decay: float = 5e-4
    nesterov: bool = False
    epochs: int = 90
    max_steps: Optional[int] = None         # hard cap (overrides epochs)
    lr_milestones: Tuple[float, ...] = (0.5, 0.75)  # fractions of total steps
    lr_decay: float = 0.1
    warmup_epochs: float = 5.0              # LR warmup (multi-worker scaling)
    clip_norm: Optional[float] = None       # grad clipping (LSTM: 0.25)
    label_smoothing: float = 0.0            # transformer: 0.1
    carry_hidden: bool = True               # LSTM: carry hidden state across
                                            # bptt windows (the reference's
                                            # "repackaging"); False = fresh
                                            # zero carry per window

    # compression (reference --compressor/--density/--sigma-scale)
    compressor: str = "none"
    density: float = 0.001
    sigma_scale: Optional[float] = None
    bucket_size: Optional[int] = None       # None=whole-model, 0=per-tensor
    bucket_policy: str = "greedy"           # 'greedy' (tensor-boundary merge)
                                            # | 'uniform' (equal flat chunks,
                                            # vectorized compress — scalable)
    compress_warmup_steps: int = 0          # dense allreduce for first N steps
    fold_lr: bool = False                   # EF on lr-scaled grads (§2.3 note)
    exchange: str = "allgather"             # sparse exchange: 'allgather'
                                            # (C2 path) | 'gtopk' (C3 tree)
    decorrelate_comp_rng: bool = False      # per-worker compressor RNG (the
                                            # randomkec shared-vs-decorrelated
                                            # seed ablation, VERDICT r5 #6;
                                            # analysis/randomkec_decorrelated)
    wire: str = "auto"                      # sparse-exchange wire format
                                            # (parallel/wire.py): 'auto' =
                                            # packed u16+bf16 when eligible,
                                            # 'off' = always legacy i32+f32
                                            # (the bf16-vs-f32 parity arm)
    overlap: str = "auto"                   # bucket-pipelined step schedule
                                            # (parallel/trainstep.py): 'auto'
                                            # = per-bucket exchange issued
                                            # while the next bucket
                                            # compresses when the plan is
                                            # eligible (uniform, >=2
                                            # buckets); 'off' = sequential
                                            # program, bit-identical to
                                            # pre-overlap builds
    policy: str = "static"                  # 'adaptive' = telemetry-driven
                                            # policy engine retunes selector/
                                            # density/wire/bucket-plan at
                                            # recompile-safe boundaries
                                            # (gaussiank_sgd_tpu/policy/,
                                            # docs/ADAPTIVE.md); 'static' =
                                            # knobs stay exactly as
                                            # configured (bit-identical to
                                            # pre-policy behavior)
    trace: str = "off"                      # 'on' = span-based step tracing
                                            # (telemetry/tracing.py): host
                                            # phase spans + trace_id/span_id
                                            # stamped on every bus record;
                                            # 'off' = event stream identical
                                            # byte-for-byte to pre-tracing
                                            # builds. Render with
                                            # `python -m gaussiank_sgd_tpu.
                                            # telemetry trace`
    health: str = "off"                     # 'on' = run-health monitor
                                            # (telemetry/health.py): rolling
                                            # SLO windows over the event
                                            # stream, one ok/degraded/
                                            # critical health_status verdict
                                            # per log interval with
                                            # attributed causes; 'off' =
                                            # stream byte-identical to
                                            # pre-health builds
    health_port: Optional[int] = None       # serve live health JSON at
                                            # http://127.0.0.1:PORT/healthz
                                            # (+ /metrics); implies
                                            # health='on'. 0 = ephemeral
                                            # port (tests)

    # numerics
    compute_dtype: str = "bfloat16"         # MXU-native compute
    seed: int = 42

    # resilience (docs/RESILIENCE.md; training/resilience.py)
    nonfinite_guard: bool = True            # fused in-step anomaly guard:
                                            # a non-finite loss/grad step
                                            # commits nothing (params/opt/
                                            # EF unchanged) and is flagged
                                            # in metrics
    max_consecutive_skips: int = 10         # rollback after N back-to-back
                                            # guard-skipped steps (0 = off)
    loss_spike_factor: float = 0.0          # rollback when loss > f * EMA
                                            # (0 = off)
    loss_ema_beta: float = 0.9              # spike-detector EMA decay
    lr_backoff: float = 0.5                 # LR scale per rollback
                                            # (compounds)
    max_rollbacks: int = 3                  # then fail loud
    save_every_steps: int = 0               # mid-epoch checkpoint cadence
                                            # (0 = epoch saves only); the
                                            # rollback target is the last
                                            # such checkpoint
    keep_checkpoints: int = 0               # keep-last-k retention GC
                                            # (0 = keep all)
    handle_signals: bool = True             # fit(): SIGTERM/SIGINT ->
                                            # checkpoint at next step
                                            # boundary, clean exit
    io_retries: int = 3                     # transient data-loader errors
                                            # retried per batch (0 = off)
    io_backoff_s: float = 0.05              # initial retry backoff
                                            # (exponential, capped at 2 s)

    # escape hatches for tests/experiments: extra ctor kwargs threaded
    # through to models.get_model / data.make_dataset (e.g. a toy LSTM:
    # model_kwargs={'hidden_dim': 64}, dataset_kwargs={'vocab_size': 256})
    model_kwargs: dict = field(default_factory=dict)
    dataset_kwargs: dict = field(default_factory=dict)
    eval_max_batches: Optional[int] = None  # cap test() batches (None = all)

    # io / logging / checkpoints (reference settings.py + torch.save path)
    run_id: str = "run"
    output_dir: str = "./runs"
    log_every: int = 10                     # reference display-freq
    eval_every_epochs: int = 1
    save_every_epochs: int = 10
    resume: Optional[str] = None            # checkpoint dir to resume from
    profile_steps: Optional[Tuple[int, int]] = None  # jax.profiler window
    prom_textfile: Optional[str] = None     # Prometheus textfile-collector
                                            # path (telemetry exporter);
                                            # None = JSONL only
    telemetry_window: int = 50              # rolling window (steps) for the
                                            # throughput/MFU tracker

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), default=str, indent=2)

    @property
    def global_batch_size(self) -> int:
        return self.batch_size * max(1, self.nworkers) * self.nsteps_update


def add_args(p: argparse.ArgumentParser, suppress_defaults: bool = False) -> None:
    """CLI flags named as in the reference entrypoint (SURVEY.md §2 C6).

    ``suppress_defaults``: every flag defaults to ``argparse.SUPPRESS`` so a
    parse reveals exactly which flags the user typed (used for --config file
    precedence in from_args).
    """
    if suppress_defaults:
        real_add = p.add_argument

        def add_argument(*a, **kw):
            kw["default"] = argparse.SUPPRESS
            return real_add(*a, **kw)
        p.add_argument = add_argument
    d = TrainConfig()
    p.add_argument("--dnn", default=d.dnn)
    p.add_argument("--dataset", default=d.dataset)
    p.add_argument("--data-dir", dest="data_dir", default=d.data_dir)
    p.add_argument("--batch-size", dest="batch_size", type=int,
                   default=d.batch_size, help="per-worker batch size")
    p.add_argument("--nsteps-update", dest="nsteps_update", type=int,
                   default=d.nsteps_update)
    p.add_argument("--nworkers", type=int, default=d.nworkers,
                   help="dp width; 0 = all visible devices")
    p.add_argument("--ici-size", dest="ici_size", type=int, default=d.ici_size)
    p.add_argument("--dcn-size", dest="dcn_size", type=int, default=d.dcn_size)
    p.add_argument("--sp-size", dest="sp_size", type=int, default=d.sp_size,
                   help="ring-attention sequence-parallel width "
                        "(transformer_lm); mesh = nworkers x sp_size")
    p.add_argument("--lr", type=float, default=d.lr)
    p.add_argument("--momentum", type=float, default=d.momentum)
    p.add_argument("--weight-decay", dest="weight_decay", type=float,
                   default=d.weight_decay)
    p.add_argument("--nesterov", action=argparse.BooleanOptionalAction,
                   default=d.nesterov)
    p.add_argument("--epochs", type=int, default=d.epochs)
    p.add_argument("--max-steps", dest="max_steps", type=int, default=None)
    p.add_argument("--warmup-epochs", dest="warmup_epochs", type=float,
                   default=d.warmup_epochs)
    p.add_argument("--clip-norm", dest="clip_norm", type=float, default=None)
    p.add_argument("--label-smoothing", dest="label_smoothing", type=float,
                   default=d.label_smoothing)
    p.add_argument("--carry-hidden", dest="carry_hidden",
                   action=argparse.BooleanOptionalAction,
                   default=d.carry_hidden,
                   help="LSTM: carry hidden state across bptt windows "
                        "(reference repackaging); --no-carry-hidden = fresh "
                        "zero carry per window")
    p.add_argument("--compressor", default=d.compressor,
                   help="none|topk|approxtopk[16]|gaussian|gaussian_warm|"
                        "gaussian_fused|randomk|randomkec|dgcsampling|"
                        "redsync|redsynctrim — or 'auto' for the codified "
                        "framework default (registry.DEFAULT_SELECTOR)")
    p.add_argument("--density", type=float, default=d.density)
    p.add_argument("--sigma-scale", dest="sigma_scale", type=float,
                   default=None)
    p.add_argument("--bucket-size", dest="bucket_size", type=int, default=None)
    p.add_argument("--bucket-policy", dest="bucket_policy",
                   choices=("greedy", "uniform"), default=d.bucket_policy)
    p.add_argument("--exchange", choices=("allgather", "gtopk"),
                   default=d.exchange,
                   help="sparse exchange: allgather (reference C2) or the "
                        "gTop-k ppermute butterfly (reference C3)")
    p.add_argument("--decorrelate-comp-rng", dest="decorrelate_comp_rng",
                   action=argparse.BooleanOptionalAction,
                   default=d.decorrelate_comp_rng,
                   help="fold the worker index into the compressor RNG "
                        "(randomkec seed ablation; see "
                        "analysis/randomkec_decorrelated.py)")
    p.add_argument("--wire", choices=("auto", "off"), default=d.wire,
                   help="sparse-exchange wire format (parallel/wire.py): "
                        "auto = packed u16+bf16 when the plan is eligible, "
                        "off = always the legacy i32+f32 format")
    p.add_argument("--overlap", choices=("auto", "off"), default=d.overlap,
                   help="bucket-pipelined step (parallel/trainstep.py): "
                        "auto = overlap each bucket's exchange with the "
                        "next bucket's compress when the plan is eligible "
                        "(uniform, >=2 buckets), off = the sequential "
                        "program (bit-identical to pre-overlap builds)")
    p.add_argument("--policy", choices=("static", "adaptive"),
                   default=d.policy,
                   help="adaptive = close the loop from telemetry to "
                        "selector/density/wire/bucket retuning at "
                        "recompile-safe boundaries (docs/ADAPTIVE.md); "
                        "static = knobs stay as configured")
    p.add_argument("--trace", choices=("off", "on"), default=d.trace,
                   help="span-based step tracing (telemetry/tracing.py): "
                        "on = emit host-phase span records and stamp "
                        "trace_id/span_id on every event; off = stream "
                        "byte-identical to pre-tracing builds")
    p.add_argument("--health", choices=("off", "on"), default=d.health,
                   help="run-health monitor (telemetry/health.py): on = "
                        "one ok/degraded/critical health_status verdict "
                        "per log interval with attributed causes; off = "
                        "stream byte-identical to pre-health builds")
    p.add_argument("--health-port", dest="health_port", type=int,
                   default=d.health_port,
                   help="serve live health JSON at /healthz (+ /metrics) "
                        "on this port; implies --health on; 0 = ephemeral")
    p.add_argument("--compress-warmup-steps", dest="compress_warmup_steps",
                   type=int, default=d.compress_warmup_steps)
    p.add_argument("--fold-lr", dest="fold_lr",
                   action=argparse.BooleanOptionalAction, default=d.fold_lr)
    p.add_argument("--compute-dtype", dest="compute_dtype",
                   default=d.compute_dtype)
    p.add_argument("--seed", type=int, default=d.seed)
    p.add_argument("--nonfinite-guard", dest="nonfinite_guard",
                   action=argparse.BooleanOptionalAction,
                   default=d.nonfinite_guard,
                   help="fused in-step anomaly guard: non-finite steps "
                        "commit nothing (docs/RESILIENCE.md)")
    p.add_argument("--max-consecutive-skips", dest="max_consecutive_skips",
                   type=int, default=d.max_consecutive_skips,
                   help="rollback after N back-to-back skipped steps; 0=off")
    p.add_argument("--loss-spike-factor", dest="loss_spike_factor",
                   type=float, default=d.loss_spike_factor,
                   help="rollback when loss > factor * EMA(loss); 0=off")
    p.add_argument("--loss-ema-beta", dest="loss_ema_beta", type=float,
                   default=d.loss_ema_beta)
    p.add_argument("--lr-backoff", dest="lr_backoff", type=float,
                   default=d.lr_backoff,
                   help="LR scale applied per rollback (compounds)")
    p.add_argument("--max-rollbacks", dest="max_rollbacks", type=int,
                   default=d.max_rollbacks)
    p.add_argument("--save-every-steps", dest="save_every_steps", type=int,
                   default=d.save_every_steps,
                   help="mid-epoch checkpoint cadence (rollback target); "
                        "0 = epoch saves only")
    p.add_argument("--keep-checkpoints", dest="keep_checkpoints", type=int,
                   default=d.keep_checkpoints,
                   help="keep-last-k checkpoint retention; 0 = keep all")
    p.add_argument("--handle-signals", dest="handle_signals",
                   action=argparse.BooleanOptionalAction,
                   default=d.handle_signals,
                   help="SIGTERM/SIGINT -> checkpoint at next step "
                        "boundary, then clean exit")
    p.add_argument("--io-retries", dest="io_retries", type=int,
                   default=d.io_retries,
                   help="transient data-loader error retries per batch")
    p.add_argument("--io-backoff-s", dest="io_backoff_s", type=float,
                   default=d.io_backoff_s)
    p.add_argument("--run-id", dest="run_id", default=d.run_id)
    p.add_argument("--output-dir", dest="output_dir", default=d.output_dir)
    p.add_argument("--log-every", dest="log_every", type=int,
                   default=d.log_every)
    p.add_argument("--save-every-epochs", dest="save_every_epochs", type=int,
                   default=d.save_every_epochs)
    p.add_argument("--resume", default=None)
    p.add_argument("--profile-steps", dest="profile_steps", type=int,
                   nargs=2, metavar=("START", "STOP"), default=None,
                   help="arm a jax.profiler trace for global steps "
                        "[START, STOP) (docs/OBSERVABILITY.md)")
    p.add_argument("--prom-textfile", dest="prom_textfile", default=None,
                   help="write latest metrics as a Prometheus "
                        "node-exporter textfile at this path")
    p.add_argument("--telemetry-window", dest="telemetry_window", type=int,
                   default=d.telemetry_window,
                   help="rolling window (steps) for the throughput/MFU "
                        "tracker")
    p.add_argument("--model-kwargs", dest="model_kwargs", type=json.loads,
                   default={}, help='JSON, e.g. \'{"hidden_dim": 64}\'')
    p.add_argument("--dataset-kwargs", dest="dataset_kwargs", type=json.loads,
                   default={}, help='JSON, e.g. \'{"vocab_size": 256}\'')
    p.add_argument("--eval-max-batches", dest="eval_max_batches", type=int,
                   default=None)
    p.add_argument("--config", dest="config", default=None,
                   help="JSON config file (exp_configs/*.json); CLI flags "
                        "explicitly given on the command line override it")


def from_args(args: argparse.Namespace,
              argv: Optional[List[str]] = None) -> TrainConfig:
    """Build a TrainConfig from parsed args, optionally layered on a JSON
    config file (reference ``exp_configs`` role, SURVEY.md §2 C12).

    Precedence: dataclass defaults < ``--config`` file < flags explicitly
    present on the command line. Explicitness is detected by re-parsing
    ``argv`` with all defaults suppressed, so passing a flag at its default
    value still overrides the file.
    """
    fields = {f.name for f in dataclasses.fields(TrainConfig)}

    def _detuple(d: dict) -> dict:
        # argparse nargs and JSON both deliver lists; tuple-typed fields
        # (profile_steps, lr_milestones) normalize here
        return {k: tuple(v) if isinstance(v, list) else v
                for k, v in d.items()}

    base = _detuple({k: v for k, v in vars(args).items() if k in fields})
    cfg_path = getattr(args, "config", None)
    if not cfg_path:
        return TrainConfig(**base)
    if argv is None:
        # Defaulting to sys.argv here would let a programmatic caller's
        # process argv masquerade as explicit overrides of the config file
        # (ADVICE r2). CLI callers pass the same argv they gave parse_args
        # (train.py normalizes None -> sys.argv[1:] before parsing).
        raise ValueError(
            "--config precedence needs the original argv to tell explicit "
            "flags from defaults; pass from_args(args, argv) the same list "
            "parse_args saw (sys.argv[1:] for a CLI)")
    with open(cfg_path) as f:
        file_vals = json.load(f)
    # "_comment"-style annotation keys are documentation, not config
    file_vals = {k: v for k, v in file_vals.items() if not k.startswith("_")}
    unknown = set(file_vals) - fields
    if unknown:
        raise ValueError(f"unknown keys in {cfg_path}: {sorted(unknown)}")
    # tuples arrive as JSON lists
    for k, v in file_vals.items():
        if isinstance(v, list):
            file_vals[k] = tuple(v)
    explicit_p = argparse.ArgumentParser()
    add_args(explicit_p, suppress_defaults=True)
    explicit, _ = explicit_p.parse_known_args(argv)
    merged = dict(base)
    merged.update(file_vals)
    merged.update(_detuple(
        {k: v for k, v in vars(explicit).items() if k in fields}))
    return TrainConfig(**merged)
