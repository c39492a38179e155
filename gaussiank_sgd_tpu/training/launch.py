"""Multi-process pod rig — the ``jax.distributed`` launcher (ROADMAP
item 3 / ISSUE 17).

Every number in this repo used to come from a single-process mesh over
virtual CPU devices, where a "dying worker" could only be simulated.
This module makes process death a *real*, injectable, recoverable
failure mode: ``python -m gaussiank_sgd_tpu.training.launch --nprocs N``
spawns N OS processes on the CPU backend of one machine (the CI-able
stand-in for multi-host TPU), each running the UNMODIFIED
:class:`~gaussiank_sgd_tpu.training.trainer.Trainer` against a global
``jax.distributed`` mesh (one device per process, gloo collectives).

Supervisor state machine (docs/RESILIENCE.md "Multi-process failure
model")::

    SPAWN(gen) ──> WATCH ──────────── all workers exit 0 ──> DONE
       ^             │ worker lost (exit code / stale heartbeat)
       │             v
       │          TEARDOWN (SIGTERM all -> grace -> SIGKILL stragglers)
       │             │ relaunch budget left?
       └── RELAUNCH(gen+1, resume=last sealed checkpoint) ── else FAIL

* **bootstrap** — :func:`bootstrap_distributed` wraps
  ``jax.distributed.initialize`` with a bounded timeout and bounded
  exponential backoff + deterministic jitter; every retry is recorded as
  a ``bootstrap_retry`` telemetry event (the ``io_retry`` shape), and
  exhaustion fails LOUD with the coordinator address and the full
  attempt log — never a silent hang.
* **death detection** — the supervisor polls child exit codes (a real
  ``SIGKILL`` surfaces as ``rc = -9`` immediately) and per-worker
  heartbeat files (written by a bus exporter on every train/checkpoint
  record) for staleness; either marks the worker lost.
* **teardown** — survivors of a killed peer hang inside the next gloo
  collective, so SIGTERM alone cannot stop them: the supervisor forwards
  SIGTERM to every child first (so :class:`GracefulShutdown` seals a
  checkpoint wherever a step boundary is still reachable), waits a
  grace period, then SIGKILLs stragglers.
* **relaunch** — a fresh generation (new coordinator port) resumes from
  the last sealed checkpoint in the SHARED checkpoint dir through the
  existing elastic-restore path (``TrainConfig.resume``); with no sealed
  checkpoint yet the generation cold-starts.
* **telemetry** — each worker writes its own JSONL stream stamped with
  ``process_index``; the supervisor writes ``supervisor.jsonl``
  (``worker_lost`` / ``worker_relaunch``); ``python -m
  gaussiank_sgd_tpu.telemetry merge`` joins them into one
  strictly-validating stream for the report/health CLIs.

The launcher is strictly OPT-IN: nothing here is imported by the
single-process entrypoints, whose behavior stays byte-identical.
The supervisor itself never imports jax (pure stdlib): the backend
only exists inside worker processes.
"""

from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

# env plumbing between supervisor and workers
SPEC_ENV = "GKSGD_LAUNCH_SPEC"
KILL_STEP_ENV = "GKSGD_CHAOS_KILL_STEP"
KILL_PROC_ENV = "GKSGD_CHAOS_KILL_PROC"
PREEMPT_STEP_ENV = "GKSGD_CHAOS_PREEMPT_STEP"
PREEMPT_PROC_ENV = "GKSGD_CHAOS_PREEMPT_PROC"

# manifest name duplicated from training/checkpoint.py so the supervisor
# never imports jax/orbax (checked against it in tests/test_launch.py)
_MANIFEST = "commit_manifest.json"


# ---------------------------------------------------------------------------
# coordinator bootstrap (worker side, but unit-testable without jax)
# ---------------------------------------------------------------------------

def _deterministic_jitter(process_id: int, attempt: int) -> float:
    """Jitter fraction in [0, 1) — hashed from (process, attempt), never
    random: the chaos harness contract is that every replay is
    bit-identical, and spreading processes apart only needs per-process
    DIFFERENT delays, not unpredictable ones."""
    h = hashlib.sha256(f"{process_id}:{attempt}".encode()).digest()
    return int.from_bytes(h[:4], "big") / 2 ** 32


def bootstrap_distributed(coordinator: str, num_processes: int,
                          process_id: int, *,
                          timeout_s: float = 60.0,
                          max_retries: int = 4,
                          backoff_s: float = 0.5,
                          backoff_cap_s: float = 8.0,
                          jitter: float = 0.25,
                          initialize: Optional[Callable[[], None]] = None,
                          on_retry: Optional[Callable[[Dict[str, Any]],
                                                      None]] = None,
                          sleep: Callable[[float], None] = time.sleep,
                          ) -> int:
    """``jax.distributed.initialize`` with bounded timeout + retries.

    Coordinator bootstrap hardening (ISSUE 17 satellite): each attempt is
    bounded by ``timeout_s`` (passed as jax's ``initialization_timeout``),
    a failed attempt backs off exponentially (``backoff_s * 2**attempt``,
    capped at ``backoff_cap_s``, plus up to ``jitter`` deterministic
    per-process spread), and after ``max_retries`` retries the failure is
    re-raised LOUDLY with the coordinator address and the full attempt
    log in the message — a worker must never hang silently on a dead
    coordinator. Each retry calls ``on_retry`` with a ``bootstrap_retry``
    event record (``io_retry`` shape; the caller owns the publish site —
    the bus usually does not exist yet during bootstrap).

    ``initialize`` is injectable (:class:`~gaussiank_sgd_tpu.training.
    chaos.FlakyCoordinator` in tests); the default builds the real jax
    call. Returns the number of attempts that ran (1 = first try worked).
    """
    if initialize is None:
        def initialize() -> None:
            import jax
            jax.distributed.initialize(
                coordinator_address=coordinator,
                num_processes=num_processes, process_id=process_id,
                initialization_timeout=max(int(timeout_s), 1))
    attempts: List[str] = []
    for attempt in range(1, max_retries + 2):     # 1 first try + retries
        try:
            initialize()
            return attempt
        except Exception as e:  # noqa: BLE001 — every failure kind retries
            attempts.append(f"attempt {attempt}: {type(e).__name__}: {e}")
            if attempt > max_retries:
                raise RuntimeError(
                    f"jax.distributed bootstrap failed for process "
                    f"{process_id}/{num_processes} against coordinator "
                    f"{coordinator} after {attempt} attempt(s) "
                    f"(timeout {timeout_s:g}s each):\n  "
                    + "\n  ".join(attempts)) from e
            delay = min(backoff_s * 2 ** (attempt - 1), backoff_cap_s)
            delay *= 1.0 + jitter * _deterministic_jitter(process_id,
                                                          attempt)
            if on_retry is not None:
                on_retry({"event": "bootstrap_retry", "attempt": attempt,
                          "max_retries": max_retries,
                          "backoff_s": round(delay, 6),
                          "coordinator": coordinator,
                          "error": f"{type(e).__name__}: {e}",
                          "ts": round(time.time(), 6)})
            sleep(delay)
    raise AssertionError("unreachable")  # pragma: no cover


def provision_worker_backend() -> None:
    """Prepare THIS process for a 1-device slot of a multi-process CPU
    mesh. Must run before any jax API that initializes the backend
    (``jax.distributed.initialize`` comes first, then the backend).

    Every worker contributes exactly one CPU device to the global mesh,
    like one chip of a pod slice; the env pin keeps the worker (and
    anything it spawns) off an accelerator the supervisor's host may hold.
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 1)
    # cross-process CPU collectives need a real backend; gloo ships with
    # jax's CPU client and works over localhost TCP
    jax.config.update("jax_cpu_collectives_implementation", "gloo")


# ---------------------------------------------------------------------------
# heartbeats (worker writes, supervisor reads)
# ---------------------------------------------------------------------------

_HEARTBEAT_EVENTS = ("config", "train", "eval", "checkpoint", "preempt")


class HeartbeatExporter:
    """Bus exporter that records liveness+progress in a tiny JSON file.

    Every ``train``/``checkpoint``/... record atomically replaces the
    file with ``{"step", "ts", "process_index"}``; the supervisor reads
    ``ts`` staleness as the hang detector (exit codes catch real death
    first — a heartbeat only times out when the process is alive but
    stuck, e.g. blocked in a collective whose peer silently vanished).
    Lock-free: the bus's delivery turnstile already serializes emit().
    """

    def __init__(self, path: str, process_index: int,
                 clock: Callable[[], float] = time.time):
        self.path = path
        self.process_index = int(process_index)
        self._clock = clock
        self._step = 0
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)

    def beat(self, step: Optional[int] = None) -> None:
        if step is not None:
            self._step = int(step)
        tmp = f"{self.path}.tmp"
        with open(tmp, "w", encoding="utf-8") as fh:
            json.dump({"step": self._step, "ts": round(self._clock(), 6),
                       "process_index": self.process_index}, fh)
        os.replace(tmp, self.path)

    def emit(self, record: Dict[str, Any]) -> None:
        if record.get("event") in _HEARTBEAT_EVENTS:
            step = record.get("step")
            self.beat(int(step) if isinstance(step, (int, float)) else None)

    def flush(self) -> None:
        return None

    def close(self) -> None:
        return None


def read_heartbeat(path: str) -> Optional[Dict[str, Any]]:
    """Parse a heartbeat file; None when absent or mid-replace garbage
    (the write is atomic, but a supervisor poll can race the very first
    create)."""
    try:
        with open(path, "r", encoding="utf-8") as fh:
            rec = json.load(fh)
        return rec if isinstance(rec, dict) else None
    except (OSError, ValueError):
        return None


# ---------------------------------------------------------------------------
# worker main
# ---------------------------------------------------------------------------

def _spec_to_config(spec: Dict[str, Any], process_id: int):
    """Rebuild the per-worker TrainConfig from the launch spec: shared
    pod dir, per-process run dir (telemetry streams must not interleave),
    and resume pointed at the shared checkpoint dir when the supervisor
    found a sealed checkpoint to restore from."""
    from .config import TrainConfig
    d = dict(spec["config"])
    # json round-trip turns tuples into lists; restore the tuple fields
    for key in ("lr_milestones", "profile_steps"):
        if d.get(key) is not None:
            d[key] = tuple(d[key])
    d["output_dir"] = spec["pod_dir"]
    d["run_id"] = f"proc{process_id:03d}"
    d["nworkers"] = int(spec["nprocs"])
    d["resume"] = spec.get("resume") or None
    if process_id != 0:
        # checkpoint GC walks+deletes shared dirs; racing P copies of it
        # against each other (and against a save) can tear a sealed dir,
        # so retention runs on process 0 only
        d["keep_checkpoints"] = 0
    return TrainConfig(**d)


def worker_main(spec: Dict[str, Any], process_id: int) -> int:
    """One pod worker: provision a 1-device CPU slot, join the
    ``jax.distributed`` mesh (bounded-retry bootstrap), then run the
    unmodified Trainer with (a) the SHARED checkpoint dir so orbax
    coordinates sealed saves across the pod, (b) ``process_index``
    stamped on every telemetry record, and (c) a heartbeat file for the
    supervisor. SIGTERM lands on this process's main thread, so
    ``GracefulShutdown`` seals a per-pod checkpoint and fit() returns
    cleanly — exit code 0 either way."""
    provision_worker_backend()
    pending_events: List[Dict[str, Any]] = []
    bootstrap_distributed(
        spec["coordinator"], int(spec["nprocs"]), process_id,
        timeout_s=float(spec.get("bootstrap_timeout_s", 60.0)),
        max_retries=int(spec.get("bootstrap_retries", 4)),
        backoff_s=float(spec.get("bootstrap_backoff_s", 0.5)),
        on_retry=pending_events.append)

    from .trainer import Trainer
    from . import chaos

    cfg = _spec_to_config(spec, process_id)
    trainer = Trainer(cfg)
    # every record this process publishes carries its pod coordinates —
    # the merge CLI and cross-process validate_stream key on these
    trainer.bus.add_stamp(lambda: {"process_index": process_id})
    trainer.ckpt_dir = spec["ckpt_dir"]      # shared across the pod
    hb = HeartbeatExporter(spec["heartbeats"][process_id], process_id)
    trainer.bus.attach(hb)
    for rec in pending_events:               # bootstrap predates the bus
        trainer.bus.publish(rec)
    hb.beat(trainer.step)                    # arm the staleness clock

    kill_step = os.environ.get(KILL_STEP_ENV)
    if kill_step is not None \
            and int(os.environ.get(KILL_PROC_ENV, "0")) == process_id:
        chaos.inject_process_death(trainer, int(kill_step))
    preempt_step = os.environ.get(PREEMPT_STEP_ENV)
    if preempt_step is not None \
            and int(os.environ.get(PREEMPT_PROC_ENV, "0")) == process_id:
        chaos.inject_preemption(trainer, int(preempt_step))

    try:
        trainer.fit()
    finally:
        trainer.close()
    return 0


# ---------------------------------------------------------------------------
# supervisor
# ---------------------------------------------------------------------------

def free_port(host: str = "127.0.0.1") -> int:
    """An OS-assigned free TCP port (each generation gets a fresh
    coordinator address — the previous generation's coordinator socket
    may still be in TIME_WAIT)."""
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind((host, 0))
        return int(s.getsockname()[1])


def has_sealed_checkpoint(ckpt_dir: str) -> Optional[str]:
    """Newest ``step_*`` dir carrying a commit manifest, or None.

    Deliberately a cheap stdlib scan, not ``checkpoint.list_checkpoints``
    — the supervisor never imports jax/orbax; full inventory validation
    (and corrupt-dir fallback) happens in the workers' own
    ``restore_latest_good`` at relaunch."""
    if not os.path.isdir(ckpt_dir):
        return None
    best: Optional[str] = None
    for d in sorted(os.listdir(ckpt_dir)):
        if d.startswith("step_") \
                and os.path.isfile(os.path.join(ckpt_dir, d, _MANIFEST)):
            best = os.path.join(ckpt_dir, d)
    return best


@dataclasses.dataclass
class LaunchConfig:
    """Supervisor knobs (defaults documented in docs/RESILIENCE.md)."""

    nprocs: int = 2
    heartbeat_timeout_s: float = 300.0   # hang backstop; exit codes are
                                         # the primary death signal
    grace_s: float = 20.0                # SIGTERM -> SIGKILL escalation
    poll_s: float = 0.2
    max_relaunches: int = 2
    bootstrap_timeout_s: float = 60.0
    bootstrap_retries: int = 4
    bootstrap_backoff_s: float = 0.5
    kill_step: Optional[int] = None      # chaos: SIGKILL one worker when
    kill_proc: int = 0                   # it pulls the batch for this step
                                         # (generation 0 only)
    preempt_step: Optional[int] = None   # chaos: SIGTERM one worker at a
    preempt_proc: int = 0                # step (graceful twin; gen 0 only)


class Supervisor:
    """Spawn/watch/teardown/relaunch loop over N worker processes.

    The loop is a TARGET-N RECONCILER, not a fixed-N relauncher: the
    width to spawn at is supervisor state (``target_nprocs``), every
    generation's spec is built from it, and :meth:`request_resize` moves
    it from any thread — the watch loop notices at its next poll and
    executes teardown -> re-spec -> spawn at the new width, resuming
    from the last sealed checkpoint through the elastic-restore path.
    The base class accepts any width >= 1 with no ceremony; budgets,
    bounds and ``resize_*`` telemetry live in
    :class:`~gaussiank_sgd_tpu.service.ElasticSupervisor`, which
    overrides the ``_poll_tick``/``_post_spawn``/``_on_worker_lost``/
    ``_apply_resize`` hooks.

    Single-threaded by design: the watch loop polls, and the SIGTERM/
    SIGINT handlers only set an Event (async-signal-safe), mirroring
    ``GracefulShutdown``. Publishes its own telemetry stream
    (``supervisor.jsonl``, strict-validated) so ``worker_lost`` /
    ``worker_relaunch`` incidents are first-class stream records the
    health CLI can attribute.
    """

    def __init__(self, cfg, launch: LaunchConfig, pod_dir: str):
        from ..telemetry import EventBus, JSONLExporter
        from .metrics import make_logger
        self.cfg = cfg
        self.launch = launch
        self.pod_dir = pod_dir
        self.ckpt_dir = os.path.join(pod_dir, "ckpt")
        os.makedirs(pod_dir, exist_ok=True)
        self.bus = EventBus(
            [JSONLExporter(os.path.join(pod_dir, "supervisor.jsonl"))],
            validate=True)
        self.bus.add_stamp(lambda: {"process_index": -1})
        self._shutdown = threading.Event()
        self._old_handlers: Dict[int, Any] = {}
        self._logs: List[Any] = []
        self.log = make_logger("gaussiank_sgd_tpu.launch")
        self.generation = 0
        self.relaunches = 0
        self._lock = threading.Lock()
        self._target_nprocs = int(launch.nprocs)
        self._resize: Optional[Tuple[int, str]] = None

    # -- target-N reconciliation ---------------------------------------
    @property
    def target_nprocs(self) -> int:
        with self._lock:
            return self._target_nprocs

    def request_resize(self, nprocs: int, reason: str = "operator") -> None:
        """Thread-safe: ask the reconcile loop to re-mesh at ``nprocs``.
        Takes effect at the next watch poll; a later request before the
        loop consumed the previous one supersedes it."""
        with self._lock:
            self._resize = (max(1, int(nprocs)), str(reason))

    def _resize_pending(self) -> bool:
        with self._lock:
            return self._resize is not None

    def _take_resize(self) -> Optional[Tuple[int, str]]:
        with self._lock:
            out, self._resize = self._resize, None
            return out

    def _commit_target(self, nprocs: int) -> None:
        with self._lock:
            self._target_nprocs = max(1, int(nprocs))

    # -- service hooks (no-ops here; service/ overrides) ----------------
    def _poll_tick(self, procs: Sequence[subprocess.Popen],
                   spec: Dict[str, Any]) -> None:
        """Once per watch poll, before death checks."""

    def _post_spawn(self, procs: Sequence[subprocess.Popen],
                    spec: Dict[str, Any]) -> None:
        """Right after a generation is spawned, before watching it."""

    def _on_worker_lost(self, lost: List[Dict[str, Any]],
                        spec: Dict[str, Any]) -> None:
        """After ``worker_lost`` is published, before the relaunch
        budget is charged."""

    def _apply_resize(self, directive: Tuple[int, str],
                      progress_step: int) -> bool:
        """Commit a directive taken after teardown; False refuses it (the
        loop then relaunches at the old width). The base accepts all."""
        self._commit_target(directive[0])
        return True

    def _progress_step(self, spec: Dict[str, Any]) -> int:
        """Highest step any worker's heartbeat reached this generation."""
        best = 0
        for path in spec["heartbeats"]:
            hb = read_heartbeat(path)
            if hb is not None:
                best = max(best, int(hb.get("step", 0)))
        return best

    # -- lifecycle ------------------------------------------------------
    def _install_signals(self) -> None:
        if threading.current_thread() is not threading.main_thread():
            return                       # tests driving from threads
        for sig in (signal.SIGTERM, signal.SIGINT):
            self._old_handlers[sig] = signal.signal(
                sig, lambda _s, _f: self._shutdown.set())

    def stop(self) -> None:
        """Request a graceful end of the run (what SIGTERM does); safe
        from any thread — the watch loop notices at its next poll."""
        self._shutdown.set()

    def _uninstall_signals(self) -> None:
        for sig, old in self._old_handlers.items():
            signal.signal(sig, old)
        self._old_handlers.clear()

    def _worker_spec(self, resume: Optional[str],
                     nprocs: Optional[int] = None) -> Dict[str, Any]:
        n = int(nprocs) if nprocs is not None else self.target_nprocs
        hb_dir = os.path.join(self.pod_dir, "heartbeats")
        return {
            "coordinator": f"127.0.0.1:{free_port()}",
            "nprocs": n,
            "pod_dir": self.pod_dir,
            "ckpt_dir": self.ckpt_dir,
            "heartbeats": [os.path.join(hb_dir, f"proc{i:03d}.json")
                           for i in range(n)],
            "resume": resume,
            "bootstrap_timeout_s": self.launch.bootstrap_timeout_s,
            "bootstrap_retries": self.launch.bootstrap_retries,
            "bootstrap_backoff_s": self.launch.bootstrap_backoff_s,
            "config": dataclasses.asdict(self.cfg),
        }

    def _spawn(self, spec: Dict[str, Any]) -> List[subprocess.Popen]:
        # stale heartbeats from the previous generation must not trip
        # the staleness detector before the new workers' first beat —
        # glob the whole dir: after a shrink, the dropped workers' files
        # are not in this spec but would still look live to _progress_step
        hb_dir = os.path.dirname(spec["heartbeats"][0])
        if os.path.isdir(hb_dir):
            for name in os.listdir(hb_dir):
                if name.startswith("proc") and name.endswith(".json"):
                    os.remove(os.path.join(hb_dir, name))
        n = int(spec["nprocs"])
        self.log.info(
            "SPAWN gen %d: nprocs=%d heartbeat_timeout=%.1fs "
            "poll_interval=%.2fs grace=%.1fs coordinator=%s",
            self.generation, n, self.launch.heartbeat_timeout_s,
            self.launch.poll_s, self.launch.grace_s, spec["coordinator"])
        procs = []
        pkg_root = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        for i in range(n):
            env = dict(os.environ)
            env[SPEC_ENV] = json.dumps(spec)
            env["PYTHONPATH"] = pkg_root + os.pathsep \
                + env.get("PYTHONPATH", "")
            if self.generation == 0 and self.launch.kill_step is not None:
                env[KILL_STEP_ENV] = str(self.launch.kill_step)
                env[KILL_PROC_ENV] = str(self.launch.kill_proc)
            else:
                env.pop(KILL_STEP_ENV, None)
                env.pop(KILL_PROC_ENV, None)
            if self.generation == 0 \
                    and self.launch.preempt_step is not None:
                env[PREEMPT_STEP_ENV] = str(self.launch.preempt_step)
                env[PREEMPT_PROC_ENV] = str(self.launch.preempt_proc)
            else:
                env.pop(PREEMPT_STEP_ENV, None)
                env.pop(PREEMPT_PROC_ENV, None)
            log = open(os.path.join(
                self.pod_dir,
                f"gen{self.generation:02d}_proc{i:03d}.log"), "w")
            self._logs.append(log)
            procs.append(subprocess.Popen(
                [sys.executable, "-m",
                 "gaussiank_sgd_tpu.training.launch", "--worker", str(i)],
                env=env, stdout=log, stderr=subprocess.STDOUT))
        return procs

    # -- watch / teardown ----------------------------------------------
    def _lost_workers(self, procs: Sequence[subprocess.Popen],
                      spec: Dict[str, Any],
                      now: float) -> List[Dict[str, Any]]:
        lost = []
        for i, p in enumerate(procs):
            rc = p.poll()
            if rc is not None and rc != 0:
                lost.append({"worker": i, "reason": "exit", "exit_code": rc})
                continue
            if rc is None:
                hb = read_heartbeat(spec["heartbeats"][i])
                if hb is not None:
                    age = now - float(hb.get("ts", now))
                    if age > self.launch.heartbeat_timeout_s:
                        lost.append({"worker": i,
                                     "reason": "heartbeat_timeout",
                                     "heartbeat_age_s": round(age, 3),
                                     "heartbeat_step":
                                         int(hb.get("step", 0))})
        return lost

    def _watch(self, procs: List[subprocess.Popen],
               spec: Dict[str, Any]) -> Tuple[str, List[Dict[str, Any]]]:
        while True:
            if self._shutdown.is_set():
                return "shutdown", []
            self._poll_tick(procs, spec)
            if self._resize_pending():
                return "resize", []
            lost = self._lost_workers(procs, spec, time.time())
            if lost:
                return "lost", lost
            if all(p.poll() == 0 for p in procs):
                return "ok", []
            time.sleep(self.launch.poll_s)

    def _teardown(self, procs: Sequence[subprocess.Popen]) -> None:
        """SIGTERM every live child FIRST (GracefulShutdown seals where a
        step boundary is still reachable), wait out the grace window,
        then SIGKILL stragglers — a peer-less gloo collective never
        returns, so escalation is mandatory, and the supervisor must
        never exit leaving orphans holding unsealed checkpoints."""
        for p in procs:
            if p.poll() is None:
                p.terminate()
        deadline = time.time() + self.launch.grace_s
        while time.time() < deadline \
                and any(p.poll() is None for p in procs):
            time.sleep(self.launch.poll_s)
        for p in procs:
            if p.poll() is None:
                p.kill()
        for p in procs:
            p.wait()

    # -- main loop ------------------------------------------------------
    def run(self) -> int:
        self._install_signals()
        try:
            while True:
                resume = has_sealed_checkpoint(self.ckpt_dir)
                spec = self._worker_spec(
                    resume=self.ckpt_dir if resume else None)
                procs = self._spawn(spec)
                self._post_spawn(procs, spec)
                outcome, lost = self._watch(procs, spec)
                if outcome == "ok":
                    return 0
                progress = self._progress_step(spec)
                self._teardown(procs)
                if outcome == "shutdown":
                    return 143           # 128 + SIGTERM, shell convention
                for rec in lost:
                    self.bus.publish({"event": "worker_lost",
                                      "generation": self.generation,
                                      **rec})
                if outcome == "lost":
                    self._on_worker_lost(lost, spec)
                    self.relaunches += 1
                    if self.relaunches > self.launch.max_relaunches:
                        raise RuntimeError(
                            f"relaunch budget exhausted "
                            f"({self.launch.max_relaunches}): workers keep "
                            f"dying — see {self.pod_dir}/gen*_proc*.log and "
                            f"supervisor.jsonl (docs/RESILIENCE.md)")
                # a directive may have arrived via the watch interrupt OR
                # from _on_worker_lost (loss-driven shrink): either way it
                # is applied exactly once, after teardown, so the next
                # spawn reconciles straight to the new width
                directive = self._take_resize()
                if directive is not None:
                    self._apply_resize(directive, progress)
                self.generation += 1
                sealed = has_sealed_checkpoint(self.ckpt_dir)
                self.bus.publish({"event": "worker_relaunch",
                                  "generation": self.generation,
                                  "nprocs": self.target_nprocs,
                                  "checkpoint": sealed or ""})
        finally:
            self._uninstall_signals()
            self.bus.close()
            for log in self._logs:
                log.close()


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def main(argv: Optional[List[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] == "--worker":
        spec = json.loads(os.environ[SPEC_ENV])
        return worker_main(spec, int(argv[1]))

    from . import config as config_mod
    ap = argparse.ArgumentParser(
        prog="python -m gaussiank_sgd_tpu.training.launch",
        description="multi-process pod rig: N-process jax.distributed "
                    "training with supervised kill/restore")
    ap.add_argument("--nprocs", type=int, default=2)
    ap.add_argument("--heartbeat-timeout", type=float, default=300.0,
                    dest="heartbeat_timeout_s",
                    help="seconds of heartbeat silence before a live "
                         "worker counts as lost (hang backstop)")
    ap.add_argument("--poll-interval", type=float, default=0.2,
                    dest="poll_s",
                    help="supervisor watch-loop poll period (s); also "
                         "the teardown escalation poll")
    ap.add_argument("--grace", type=float, default=20.0, dest="grace_s",
                    help="SIGTERM->SIGKILL escalation window (s)")
    ap.add_argument("--max-relaunches", type=int, default=2)
    ap.add_argument("--bootstrap-timeout", type=float, default=60.0,
                    dest="bootstrap_timeout_s")
    ap.add_argument("--bootstrap-retries", type=int, default=4)
    ap.add_argument("--kill-step", type=int, default=None,
                    help="chaos: SIGKILL --kill-proc when it pulls the "
                         "batch feeding this global step (gen 0 only)")
    ap.add_argument("--kill-proc", type=int, default=0)
    ap.add_argument("--preempt-step", type=int, default=None,
                    help="chaos: SIGTERM --preempt-proc (graceful "
                         "preemption) at this global step (gen 0 only)")
    ap.add_argument("--preempt-proc", type=int, default=0)
    config_mod.add_args(ap)
    args = ap.parse_args(argv)
    cfg = config_mod.from_args(args, argv)

    launch = LaunchConfig(
        nprocs=args.nprocs,
        heartbeat_timeout_s=args.heartbeat_timeout_s,
        grace_s=args.grace_s, poll_s=args.poll_s,
        max_relaunches=args.max_relaunches,
        bootstrap_timeout_s=args.bootstrap_timeout_s,
        bootstrap_retries=args.bootstrap_retries,
        kill_step=args.kill_step, kill_proc=args.kill_proc,
        preempt_step=args.preempt_step, preempt_proc=args.preempt_proc)
    pod_dir = os.path.join(cfg.output_dir, cfg.run_id)
    return Supervisor(cfg, launch, pod_dir).run()


if __name__ == "__main__":
    sys.exit(main())
