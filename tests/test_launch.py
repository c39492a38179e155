"""Multi-process pod rig (training/launch.py, ISSUE 17).

Tier-1 part: pure-unit coverage of every launcher building block that
does not need a real pod — bootstrap retry/backoff (FlakyCoordinator),
deterministic process-death injection, heartbeats, sealed-checkpoint
scanning, supervisor loss detection against fake child handles, the
telemetry merge CLI, and the health monitor's worker_lost /
coordinator_stall attribution.

Slow part (``-m slow`` + ``GKSGD_RUN_SLOW=1``): the real thing — an
N-process ``jax.distributed`` pod where one worker takes a real SIGKILL
mid-training, the supervisor detects/tears down/relaunches from the last
sealed checkpoint, and the merged per-process telemetry strict-validates
with the incident attributed; plus process-vs-process bitwise agreement
of the packed-wire gTop-k exchange.
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time

import pytest

from gaussiank_sgd_tpu.telemetry import EventBus, JSONLExporter, MemoryExporter
from gaussiank_sgd_tpu.telemetry.__main__ import infer_process_index
from gaussiank_sgd_tpu.telemetry.__main__ import main as telemetry_cli
from gaussiank_sgd_tpu.telemetry.health import (CAUSE_COORDINATOR_STALL,
                                                CAUSE_WORKER_LOST,
                                                HealthMonitor)
from gaussiank_sgd_tpu.training import chaos, launch
from gaussiank_sgd_tpu.training.config import TrainConfig
from gaussiank_sgd_tpu.training.resilience import GracefulShutdown

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

slow = pytest.mark.slow
run_slow = pytest.mark.skipif(
    os.environ.get("GKSGD_RUN_SLOW") != "1",
    reason="multi-minute multi-process pod run (set GKSGD_RUN_SLOW=1)")


# ------------------------------------------------------------- bootstrap

def _bootstrap(refusals, **kw):
    fc = chaos.FlakyCoordinator(refusals)
    sleeps, events = [], []
    attempts = launch.bootstrap_distributed(
        "10.0.0.1:1234", 4, 3, timeout_s=1.0, initialize=fc,
        on_retry=events.append, sleep=sleeps.append, **kw)
    return attempts, sleeps, events, fc


def test_bootstrap_retries_to_success_and_replays_identically():
    a1, s1, e1, fc1 = _bootstrap(2, max_retries=3)
    a2, s2, e2, fc2 = _bootstrap(2, max_retries=3)
    assert a1 == a2 == 3 and fc1.calls == 3          # 2 refusals + success
    assert s1 == s2 and len(s1) == 2                 # deterministic jitter
    assert s1[0] < s1[1]                             # exponential growth
    assert [e["attempt"] for e in e1] == [1, 2]
    assert all(e["event"] == "bootstrap_retry"
               and e["max_retries"] == 3
               and e["coordinator"] == "10.0.0.1:1234"
               and "ConnectionRefusedError" in e["error"] for e in e1)
    # the recorded backoff is the slept backoff
    assert [e["backoff_s"] for e in e1] == [round(s, 6) for s in s1]


def test_bootstrap_backoff_is_capped():
    _a, sleeps, _e, _fc = _bootstrap(6, max_retries=6, backoff_s=0.5,
                                     backoff_cap_s=2.0, jitter=0.0)
    assert sleeps == [min(0.5 * 2 ** i, 2.0) for i in range(6)]


def test_bootstrap_exhaustion_fails_loud_with_attempt_log():
    with pytest.raises(RuntimeError) as ei:
        _bootstrap(-1, max_retries=2)
    msg = str(ei.value)
    assert "10.0.0.1:1234" in msg                    # coordinator address
    assert "process 3/4" in msg
    assert "attempt 1:" in msg and "attempt 3:" in msg
    assert "ConnectionRefusedError" in msg


def test_bootstrap_exhaustion_attempt_log_is_complete_and_ordered():
    # "full attempt log": every attempt appears, in order, each with its
    # own error — not just the first and last (ISSUE 18 satellite)
    with pytest.raises(RuntimeError) as ei:
        _bootstrap(-1, max_retries=3)
    lines = [ln.strip() for ln in str(ei.value).splitlines()
             if ln.strip().startswith("attempt ")]
    assert len(lines) == 4                           # max_retries + 1
    assert [int(ln.split()[1].rstrip(":")) for ln in lines] == [1, 2, 3, 4]
    assert all("ConnectionRefusedError" in ln for ln in lines)


def test_bootstrap_retry_event_validates_on_a_strict_bus():
    _a, _s, events, _fc = _bootstrap(1, max_retries=2)
    mem = MemoryExporter()
    bus = EventBus([mem], validate=True)
    for rec in events:
        bus.publish(dict(rec))
    bus.close()
    assert mem.records[0]["event"] == "bootstrap_retry"


def test_deterministic_jitter_range_and_stability():
    vals = {launch._deterministic_jitter(p, a)
            for p in range(8) for a in range(1, 5)}
    assert all(0.0 <= v < 1.0 for v in vals)
    assert len(vals) == 32                            # spread, no collision
    assert launch._deterministic_jitter(3, 2) \
        == launch._deterministic_jitter(3, 2)


# ------------------------------------------------------- process death

class _FakeTrainer:
    """The three attributes the stream injectors touch — no jax."""

    def __init__(self, step=0, n=64):
        self.step = step
        self._stream = lambda: iter(range(n))
        self.invalidated = 0

    def _invalidate_data_iter(self):
        self.invalidated += 1


def _pulls_until_signal(start_step, target):
    hits = []
    old = signal.signal(signal.SIGUSR1, lambda _s, _f: hits.append(True))
    try:
        t = _FakeTrainer(step=start_step)
        chaos.inject_process_death(t, target, signum=signal.SIGUSR1)
        assert t.invalidated == 1
        it = t._stream()
        pulls = 0
        while not hits:
            next(it)
            pulls += 1
        return pulls
    finally:
        signal.signal(signal.SIGUSR1, old)


def test_process_death_fires_on_exact_stream_position_twice():
    # keyed on the global step counter: from step 3, the batch feeding
    # step 5 is the 3rd pull — and a second run dies at the same pull
    assert _pulls_until_signal(3, 5) == 3
    assert _pulls_until_signal(3, 5) == 3
    assert _pulls_until_signal(0, 7) == 8


def _pulls_until_preempt(start_step, target):
    hits = []
    old = signal.signal(signal.SIGUSR1, lambda _s, _f: hits.append(True))
    try:
        t = _FakeTrainer(step=start_step)
        chaos.inject_preemption(t, target, signum=signal.SIGUSR1)
        assert t.invalidated == 1
        it = t._stream()
        pulls = 0
        while not hits:
            next(it)
            pulls += 1
        return pulls
    finally:
        signal.signal(signal.SIGUSR1, old)


def test_inject_preemption_fires_on_exact_stream_position_twice():
    # the graceful twin of inject_process_death: same step keying, same
    # determinism — only the delivered signal differs (SIGTERM, so the
    # worker's GracefulShutdown seals and exits 0)
    assert _pulls_until_preempt(3, 5) == 3
    assert _pulls_until_preempt(3, 5) == 3
    assert _pulls_until_preempt(0, 7) == 8
    # and it lands on the same pull as the SIGKILL twin would
    assert _pulls_until_preempt(2, 9) == _pulls_until_signal(2, 9)


_DEATH_CODE = r"""
import sys
sys.path.insert(0, %(repo)r)
from gaussiank_sgd_tpu.training import chaos

class T:
    def __init__(self):
        self.step = 0
        self._stream = lambda: iter(range(100))
    def _invalidate_data_iter(self):
        pass

t = T()
chaos.inject_process_death(t, 7)
for _ in t._stream():
    t.step += 1
    print("PULL", t.step, flush=True)
print("SURVIVED", flush=True)
"""


def test_process_death_real_sigkill_replays_identically():
    def run():
        return subprocess.run(
            [sys.executable, "-c", _DEATH_CODE % {"repo": REPO}],
            capture_output=True, text=True, timeout=300, cwd=REPO)
    r1, r2 = run(), run()
    # a real SIGKILL: rc is -9, no cleanup line ever prints
    assert r1.returncode == -9, (r1.returncode, r1.stderr[-2000:])
    assert "SURVIVED" not in r1.stdout
    # bit-for-bit replay: identical pull trace across two runs
    assert r1.stdout == r2.stdout and r1.stdout.strip().endswith("PULL 7")
    assert r2.returncode == -9


# ----------------------------------------------------------- heartbeats

def test_heartbeat_exporter_beats_on_progress_events(tmp_path):
    path = str(tmp_path / "hb" / "proc001.json")
    clock = [100.0]
    hb = launch.HeartbeatExporter(path, 1, clock=lambda: clock[0])
    hb.beat(0)
    assert launch.read_heartbeat(path) \
        == {"step": 0, "ts": 100.0, "process_index": 1}
    clock[0] = 101.5
    hb.emit({"event": "train", "step": 7})
    assert launch.read_heartbeat(path) \
        == {"step": 7, "ts": 101.5, "process_index": 1}
    clock[0] = 103.0
    hb.emit({"event": "policy_decision", "step": 9})   # not a liveness event
    assert launch.read_heartbeat(path)["ts"] == 101.5
    hb.emit({"event": "checkpoint", "step": 8})
    assert launch.read_heartbeat(path) \
        == {"step": 8, "ts": 103.0, "process_index": 1}


def test_read_heartbeat_tolerates_garbage(tmp_path):
    assert launch.read_heartbeat(str(tmp_path / "nope.json")) is None
    bad = tmp_path / "bad.json"
    bad.write_text('{"step": 3, "ts"')
    assert launch.read_heartbeat(str(bad)) is None
    bad.write_text('[1, 2]')
    assert launch.read_heartbeat(str(bad)) is None


# ------------------------------------------------- sealed-checkpoint scan

def test_has_sealed_checkpoint_picks_newest_sealed(tmp_path):
    ckpt = tmp_path / "ckpt"
    assert launch.has_sealed_checkpoint(str(ckpt)) is None
    for step, sealed in [(2, True), (4, True), (6, False)]:
        d = ckpt / f"step_{step:08d}"
        d.mkdir(parents=True)
        if sealed:
            (d / launch._MANIFEST).write_text("{}")
    # step_6 has no commit manifest (save died mid-write): skipped
    assert launch.has_sealed_checkpoint(str(ckpt)) \
        == str(ckpt / "step_00000004")


def test_manifest_name_matches_checkpoint_module():
    # the supervisor duplicates the name to stay jax-free; keep in sync
    from gaussiank_sgd_tpu.training.checkpoint import MANIFEST
    assert launch._MANIFEST == MANIFEST


# --------------------------------------------------- supervisor (no pod)

class _FakeProc:
    def __init__(self, rc):
        self._rc = rc

    def poll(self):
        return self._rc


def _supervisor(tmp_path, **kw):
    cfg = TrainConfig(output_dir=str(tmp_path), run_id="pod")
    return launch.Supervisor(cfg, launch.LaunchConfig(**kw),
                             str(tmp_path / "pod"))


def test_lost_workers_exit_code_and_heartbeat_staleness(tmp_path):
    sup = _supervisor(tmp_path, nprocs=3, heartbeat_timeout_s=10.0)
    try:
        hb_dir = tmp_path / "pod" / "heartbeats"
        hb_dir.mkdir(parents=True)
        spec = {"heartbeats": [str(hb_dir / f"proc{i:03d}.json")
                               for i in range(3)]}
        (hb_dir / "proc002.json").write_text(
            json.dumps({"step": 5, "ts": 50.0, "process_index": 2}))
        procs = [_FakeProc(0), _FakeProc(-9), _FakeProc(None)]
        lost = sup._lost_workers(procs, spec, now=100.0)
        assert {"worker": 1, "reason": "exit", "exit_code": -9} in lost
        assert {"worker": 2, "reason": "heartbeat_timeout",
                "heartbeat_age_s": 50.0, "heartbeat_step": 5} in lost
        assert len(lost) == 2                        # rc=0 is not lost
        # a live worker with no heartbeat yet (still bootstrapping) is
        # NOT lost — the staleness clock arms on the first beat
        os.remove(hb_dir / "proc002.json")
        assert sup._lost_workers(procs, spec, now=1e9) \
            == [{"worker": 1, "reason": "exit", "exit_code": -9}]
    finally:
        sup.bus.close()


def test_worker_spec_fresh_coordinator_and_resume(tmp_path):
    sup = _supervisor(tmp_path, nprocs=2)
    try:
        s1 = sup._worker_spec(resume=None)
        s2 = sup._worker_spec(resume=str(tmp_path / "pod" / "ckpt"))
        assert s1["coordinator"].startswith("127.0.0.1:")
        assert s1["coordinator"] != s2["coordinator"]   # fresh port per gen
        assert s1["resume"] is None
        assert s2["resume"] == str(tmp_path / "pod" / "ckpt")
        assert len(s1["heartbeats"]) == 2
        assert s1["config"]["run_id"] == "pod"
        # survives the env-var JSON round-trip the workers read (tuple
        # config fields arrive as lists; _spec_to_config restores them)
        rt = json.loads(json.dumps(s2))
        assert rt["config"]["lr_milestones"] \
            == list(s2["config"]["lr_milestones"])
        rt["config"] = s2["config"] = None
        assert rt == s2
    finally:
        sup.bus.close()


def test_spec_to_config_per_process_layout(tmp_path):
    sup = _supervisor(tmp_path, nprocs=4)
    try:
        spec = sup._worker_spec(resume=str(sup.ckpt_dir))
    finally:
        sup.bus.close()
    cfg1 = launch._spec_to_config(spec, 1)
    assert cfg1.output_dir == str(tmp_path / "pod")
    assert cfg1.run_id == "proc001" and cfg1.nworkers == 4
    assert cfg1.resume == str(tmp_path / "pod" / "ckpt")
    assert cfg1.keep_checkpoints == 0          # retention on process 0 only
    cfg0 = launch._spec_to_config(spec, 0)
    assert cfg0.keep_checkpoints == TrainConfig().keep_checkpoints
    assert isinstance(cfg0.lr_milestones, tuple)


def test_supervisor_publishes_strictly_valid_incident_records(tmp_path):
    sup = _supervisor(tmp_path, nprocs=2)
    sup.bus.publish({"event": "worker_lost", "generation": 0, "worker": 1,
                     "reason": "exit", "exit_code": -9})
    sup.bus.publish({"event": "worker_relaunch", "generation": 1,
                     "nprocs": 2, "checkpoint": ""})
    sup.bus.close()
    lines = [json.loads(l) for l in
             open(tmp_path / "pod" / "supervisor.jsonl")]
    assert [r["event"] for r in lines] == ["worker_lost", "worker_relaunch"]
    assert all(r["process_index"] == -1 for r in lines)   # provenance stamp
    assert telemetry_cli(["validate",
                          str(tmp_path / "pod" / "supervisor.jsonl"),
                          "--strict"]) == 0


# ------------------------------------------------------- merge CLI + infer

def test_infer_process_index_from_paths():
    assert infer_process_index("pod/proc007/metrics.jsonl", None) == 7
    assert infer_process_index("gen01_proc012.log", None) == 12
    assert infer_process_index("proc3.jsonl", None) == 3
    assert infer_process_index("pod/supervisor.jsonl", -1) == -1
    assert infer_process_index("reprocess.jsonl", None) is None  # no sep


def _write_stream(path, pidx, events, t0=0.0):
    with open(path, "w") as fh:
        for i, ev in enumerate(events):
            rec = {"schema_version": 1, "seq": i, "ts": t0 + i,
                   "process_index": pidx, **ev}
            fh.write(json.dumps(rec) + "\n")


def test_cli_merge_interleaves_and_strict_validates(tmp_path, capsys):
    a = str(tmp_path / "proc000.jsonl")
    b = str(tmp_path / "proc001.jsonl")
    sup = str(tmp_path / "supervisor.jsonl")
    _write_stream(a, 0, [{"event": "skip", "step": s, "nonfinite": 0.0}
                         for s in (1, 2, 3)], t0=0.0)
    _write_stream(b, 1, [{"event": "skip", "step": s, "nonfinite": 0.0}
                         for s in (1, 2, 3)], t0=0.5)
    _write_stream(sup, -1, [{"event": "worker_lost", "generation": 0,
                             "worker": 1, "reason": "exit"}], t0=1.25)
    out = str(tmp_path / "merged.jsonl")
    assert telemetry_cli(["merge", a, b, sup, "-o", out, "--strict"]) == 0
    merged = [json.loads(l) for l in open(out)]
    assert len(merged) == 7
    assert [r["ts"] for r in merged] == sorted(r["ts"] for r in merged)
    assert merged[3]["event"] == "worker_lost"       # ts-ordered insert
    assert sorted({r["process_index"] for r in merged}) == [-1, 0, 1]
    text = capsys.readouterr().out
    assert "7 record(s) from 3 stream(s)" in text
    assert "3 process(es)" in text


def test_merge_streams_timestamp_ties_across_three_streams():
    # ISSUE 18 satellite: at equal ts across >= 3 streams the merge is
    # deterministic — ties break by process_index, and records from the
    # same stream never reorder relative to each other
    from gaussiank_sgd_tpu.telemetry.events import merge_streams

    def stream(pidx, specs):
        return [json.dumps({"schema_version": 1, "seq": i,
                            "process_index": pidx, **spec})
                for i, spec in enumerate(specs)]

    s2 = stream(2, [{"ts": 1.0, "event": "skip", "step": 1,
                     "nonfinite": 0.0},
                    {"ts": 2.0, "event": "skip", "step": 2,
                     "nonfinite": 0.0}])
    s0 = stream(0, [{"ts": 1.0, "event": "skip", "step": 1,
                     "nonfinite": 0.0},
                    {"ts": 1.0, "event": "skip", "step": 2,
                     "nonfinite": 0.0}])
    s1 = stream(1, [{"ts": 1.0, "event": "skip", "step": 1,
                     "nonfinite": 0.0},
                    # ts-less record: inherits 1.0 from its own stream,
                    # stays behind its predecessor
                    {"event": "skip", "step": 2, "nonfinite": 0.0}])
    merged, rep = merge_streams([s2, s0, s1], [2, 0, 1])
    key = [(r["process_index"], r["seq"]) for r in merged]
    # the five ts=1.0 records first (pidx asc, in-stream order kept),
    # then the lone ts=2.0 record
    assert key == [(0, 0), (0, 1), (1, 0), (1, 1), (2, 0), (2, 1)]
    assert rep.n_records == 6 and rep.dropped_lines == 0
    # input order of the streams argument must not matter
    merged2, _rep2 = merge_streams([s1, s2, s0], [1, 2, 0])
    assert [(r["process_index"], r["seq"]) for r in merged2] == key


def test_cli_merge_usage_errors(tmp_path):
    a = str(tmp_path / "a.jsonl")
    _write_stream(a, 0, [{"event": "skip", "step": 1, "nonfinite": 0.0}])
    out = str(tmp_path / "m.jsonl")
    # --index count must match the inputs
    assert telemetry_cli(["merge", a, "-o", out,
                          "--index", "0", "--index", "1"]) == 2
    assert telemetry_cli(["merge", str(tmp_path / "nope.jsonl"),
                          "-o", out]) == 2


def test_cli_merge_strict_reports_cross_process_duplicates(tmp_path,
                                                          capsys):
    a = str(tmp_path / "proc000.jsonl")
    with open(a, "w") as fh:
        for seq in (0, 1, 1):                        # duplicate seq
            fh.write(json.dumps({"schema_version": 1, "seq": seq,
                                 "ts": float(seq), "process_index": 0,
                                 "event": "skip", "step": seq,
                                 "nonfinite": 0.0}) + "\n")
    out = str(tmp_path / "m.jsonl")
    # duplicates are detection warnings (like gaps/resets), not fatal
    assert telemetry_cli(["merge", a, "-o", out, "--strict"]) == 0
    text = capsys.readouterr().out
    assert "duplicate seq 1 [process 0]" in text
    assert "1 duplicate(s)" in text


# --------------------------------------------------- health attribution

def _train(step):
    return {"event": "train", "step": step, "epoch": 0, "loss": 1.0,
            "lr": 0.1, "grad_norm": 1.0, "num_selected": 10.0,
            "bytes_sent": 100, "density": 0.01, "io_s": 0.0,
            "step_s": 0.1, "skipped": 0.0, "nonfinite": 0.0,
            "density_achieved": 0.01, "ef_norm": 1.0}


def test_health_worker_lost_is_critical():
    mon = HealthMonitor()
    mon.emit(_train(2))
    mon.tick(2)
    mon.emit({"event": "worker_lost", "generation": 0, "worker": 1,
              "reason": "exit", "exit_code": -9})
    v = mon.tick(4)
    assert v["state"] == "critical" and CAUSE_WORKER_LOST in v["causes"]
    assert v["evidence"][CAUSE_WORKER_LOST]["workers_lost"] == 1
    # ages out of the window once quiet intervals pass
    for step in range(6, 30, 2):
        v = mon.tick(step)
    assert v["state"] == "ok"
    assert mon.summary()["worst_state"] == "critical"


def test_health_bootstrap_retries_degrade_then_exhaustion_criticals():
    mon = HealthMonitor()
    for attempt in (1, 2):
        mon.emit({"event": "bootstrap_retry", "attempt": attempt,
                  "max_retries": 4, "backoff_s": 0.5,
                  "coordinator": "c:1", "error": "refused"})
    v = mon.tick(2)
    assert v["state"] == "degraded"
    assert CAUSE_COORDINATOR_STALL in v["causes"]
    # an attempt that reaches max_retries means exhaustion: sticky critical
    mon2 = HealthMonitor()
    mon2.emit({"event": "bootstrap_retry", "attempt": 4, "max_retries": 4,
               "backoff_s": 0.5, "coordinator": "c:1", "error": "refused"})
    v2 = mon2.tick(2)
    assert v2["state"] == "critical"
    assert v2["evidence"][CAUSE_COORDINATOR_STALL]["retries_exhausted"]


def test_replay_health_ticks_after_worker_lost(tmp_path):
    from gaussiank_sgd_tpu.telemetry import replay_health
    stream = [_train(2),
              {"event": "worker_lost", "generation": 0, "worker": 0,
               "reason": "heartbeat_timeout"}]
    replayed, mon = replay_health(stream)
    assert any(CAUSE_WORKER_LOST in r["causes"] for r in replayed)
    assert mon.summary()["worst_state"] == "critical"


# ------------------------------------------------------ graceful shutdown

def test_graceful_shutdown_install_rejects_non_main_thread():
    box = []

    def run():
        try:
            GracefulShutdown().install()
        except RuntimeError as e:
            box.append(str(e))

    t = threading.Thread(target=run)
    t.start()
    t.join()
    assert box and "main thread" in box[0]
    # handler table untouched: installing on the main thread still works
    gs = GracefulShutdown().install()
    try:
        assert not gs.requested
    finally:
        gs.uninstall()


# ===================================================== slow: the real pod

def _pod_cmd(out_dir, run_id, **over):
    flags = {"nprocs": 2, "kill-step": None, "kill-proc": 1, "grace": 15,
             "max-relaunches": 2, "heartbeat-timeout": 300,
             "dnn": "mnistnet", "dataset": "mnist", "batch-size": 8,
             "nworkers": 2, "lr": 0.05, "epochs": 1, "max-steps": 10,
             "compressor": "gaussian", "density": 0.01,
             "compress-warmup-steps": 2, "warmup-epochs": 0,
             "save-every-steps": 2, "save-every-epochs": 0,
             "log-every": 2, "eval-max-batches": 2,
             "output-dir": out_dir, "run-id": run_id, "seed": 0}
    flags.update(over)
    cmd = [sys.executable, "-m", "gaussiank_sgd_tpu.training.launch"]
    for k, v in flags.items():
        if v is not None:
            cmd += [f"--{k}", str(v)]
    return cmd


def _run_pod(tmp_path, run_id, timeout=1500, **over):
    env = dict(os.environ)
    env.pop("GKSGD_FORCE_VIRTUAL_CPU", None)
    proc = subprocess.run(_pod_cmd(str(tmp_path), run_id, **over),
                          env=env, cwd=REPO, capture_output=True,
                          text=True, timeout=timeout)
    return proc, os.path.join(str(tmp_path), run_id)


def _final_losses(pod_dir, nprocs):
    out = {}
    for i in range(nprocs):
        path = os.path.join(pod_dir, f"proc{i:03d}", "metrics.jsonl")
        trains = [json.loads(l) for l in open(path)
                  if '"event": "train"' in l]
        out[i] = trains[-1]["loss"]
    return out


@slow
@run_slow
def test_pod_n2_kill_restore_smoke(tmp_path):
    """ISSUE 17 acceptance (N=2 shape): real SIGKILL mid-training ->
    supervisor detects -> relaunch from last sealed checkpoint -> exit 0;
    merged stream strict-validates; health CLI attributes worker_lost."""
    proc, pod = _run_pod(tmp_path, "smoke", **{"kill-step": 5})
    assert proc.returncode == 0, proc.stderr[-4000:] + proc.stdout[-2000:]

    sup = [json.loads(l) for l in open(os.path.join(pod,
                                                    "supervisor.jsonl"))]
    lost = [r for r in sup if r["event"] == "worker_lost"]
    rel = [r for r in sup if r["event"] == "worker_relaunch"]
    assert lost and lost[0]["worker"] == 1 and lost[0]["exit_code"] == -9
    assert rel and rel[0]["checkpoint"].startswith(
        os.path.join(pod, "ckpt", "step_"))

    merged = os.path.join(pod, "merged.jsonl")
    assert telemetry_cli([
        "merge", os.path.join(pod, "proc000", "metrics.jsonl"),
        os.path.join(pod, "proc001", "metrics.jsonl"),
        os.path.join(pod, "supervisor.jsonl"),
        "-o", merged, "--strict"]) == 0
    assert telemetry_cli(["health", merged]) == 2     # critical: worker_lost


@slow
@run_slow
def test_pod_n4_kill_restore_loss_parity(tmp_path):
    """ISSUE 17 acceptance (N>=4): the killed+restored pod ends within
    the unkilled run's parity band."""
    n = int(os.environ.get("GKSGD_POD_PROCS", "4"))
    base = {"nprocs": n, "nworkers": n, "batch-size": 2 * n}
    clean, pod_c = _run_pod(tmp_path, "clean", **base)
    assert clean.returncode == 0, clean.stderr[-4000:]
    killed, pod_k = _run_pod(tmp_path, "killed",
                             **{**base, "kill-step": 5, "kill-proc": 1})
    assert killed.returncode == 0, killed.stderr[-4000:]

    sup = [json.loads(l) for l in
           open(os.path.join(pod_k, "supervisor.jsonl"))]
    assert any(r["event"] == "worker_lost" for r in sup)
    loss_c = _final_losses(pod_c, n)[0]
    loss_k = _final_losses(pod_k, n)[0]
    # every process logs the same global loss; killed-run's final loss
    # sits in the unkilled run's band (restore replays the lost steps)
    assert _final_losses(pod_k, n) == {i: loss_k for i in range(n)}
    assert abs(loss_k - loss_c) <= max(0.25 * abs(loss_c), 0.5), \
        (loss_c, loss_k)


_AGREE_CODE = r"""
import hashlib, sys
sys.path.insert(0, %(repo)r)
pid, nprocs, coord, out = (int(sys.argv[1]), int(sys.argv[2]),
                           sys.argv[3], sys.argv[4])
from gaussiank_sgd_tpu.training import launch
launch.provision_worker_backend()
launch.bootstrap_distributed(coord, nprocs, pid, timeout_s=120)

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map
from gaussiank_sgd_tpu.compressors import get_compressor
from gaussiank_sgd_tpu.parallel.bucketing import make_bucket_plan
from gaussiank_sgd_tpu.parallel.gtopk import gtopk_allreduce
from gaussiank_sgd_tpu.parallel.mesh import data_parallel_mesh
from gaussiank_sgd_tpu.parallel.wire import plan_wire_format

n = 65536
plan = make_bucket_plan([n], 0.001, bucket_size=65536, policy="uniform")
wf = plan_wire_format(plan, jnp.float32)
assert wf is not None
k = max(1, -(-n // 1000))
mesh = data_parallel_mesh(nprocs)
topk = get_compressor("topk").fn

# same full matrix on every process (same key); each holds one row
accs = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (nprocs, n)))
sharding = NamedSharding(mesh, P("dp"))
local = jax.device_put(accs[pid:pid + 1], jax.local_devices()[0])
garr = jax.make_array_from_single_device_arrays(
    (nprocs, n), sharding, [local])

def worker(acc_shard):
    r = topk(acc_shard[0], k)
    g, _bytes = gtopk_allreduce(r.compressed, nprocs, "dp", wire=wf)
    return g.indices[None], g.values[None]

f = jax.jit(shard_map(worker, mesh=mesh, in_specs=P("dp"),
                      out_specs=P("dp"), check_vma=False))
gi, gv = f(garr)
mine_i = np.asarray(gi.addressable_data(0))
mine_v = np.asarray(gv.addressable_data(0))
h = hashlib.sha256(mine_i.tobytes() + mine_v.tobytes()).hexdigest()
with open(out, "w") as fh:
    fh.write(h)
print("AGREE_OK", pid, h, flush=True)
"""


@slow
@run_slow
def test_pod_bitwise_wire_agreement_across_processes(tmp_path):
    """ISSUE 17 acceptance: process-vs-process BITWISE agreement of the
    packed-wire gTop-k exchange (the bf16 pre-merge re-quantization runs
    on every rank independently — any divergence shows up as a hash
    mismatch). GKSGD_AGREE_PROCS sets the width (target 32; default 4
    keeps single-core CI sane)."""
    n = int(os.environ.get("GKSGD_AGREE_PROCS", "4"))
    coord = f"127.0.0.1:{launch.free_port()}"
    env = dict(os.environ)
    env.pop("GKSGD_FORCE_VIRTUAL_CPU", None)
    outs = [str(tmp_path / f"hash{i:03d}") for i in range(n)]
    procs = [subprocess.Popen(
        [sys.executable, "-c", _AGREE_CODE % {"repo": REPO},
         str(i), str(n), coord, outs[i]],
        env=env, cwd=REPO, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True) for i in range(n)]
    deadline = time.time() + 1200
    for p in procs:
        p.wait(timeout=max(1.0, deadline - time.time()))
    logs = [p.stdout.read() for p in procs]
    assert all(p.returncode == 0 for p in procs), \
        "\n".join(log[-2000:] for log in logs)
    hashes = {open(o).read() for o in outs}
    assert len(hashes) == 1, hashes                  # bitwise identical
