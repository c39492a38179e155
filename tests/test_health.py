"""Run-health monitor (docs/OBSERVABILITY.md "Run health"): cause
detectors over synthetic windows, the replay/live cadence contract, the
Prometheus health gauges, the policy-gating and rollback pre-arm hookups,
the HTTP surface, the offline CLI exit codes, and the ISSUE acceptance
scenarios — chaos-driven runs whose data_wait / instability verdicts are
visible identically via the live endpoint, the CLI exit code, and the
report section.
"""

import json
import os
import urllib.error
import urllib.request

import pytest

from gaussiank_sgd_tpu.policy.engine import PolicyEngine
from gaussiank_sgd_tpu.policy.rules import PolicyDecision, Rule
from gaussiank_sgd_tpu.policy.signals import PolicySignals
from gaussiank_sgd_tpu.telemetry import (
    EventBus, HealthMonitor, HealthPolicy, HealthServer, MemoryExporter,
    PrometheusTextfileExporter, replay_health,
)
from gaussiank_sgd_tpu.telemetry.health import (
    CRITICAL, DEGRADED, OK, PRE_ARM_CAUSES, format_health,
)
from gaussiank_sgd_tpu.telemetry.events import validate_file
from gaussiank_sgd_tpu.telemetry.report import (format_report, load_events,
                                                summarize)
from gaussiank_sgd_tpu.telemetry.__main__ import main as telemetry_cli
from gaussiank_sgd_tpu.training import chaos
from gaussiank_sgd_tpu.training.config import TrainConfig
from gaussiank_sgd_tpu.training.resilience import (ResilienceMonitor,
                                                   ResiliencePolicy)
from gaussiank_sgd_tpu.training.trainer import Trainer


def train_rec(step, *, step_s=0.1, io_s=0.0, sparse=True, **kw):
    rec = {"event": "train", "step": step, "epoch": 0, "loss": 1.0,
           "lr": 0.1, "grad_norm": 1.0, "num_selected": 10.0,
           "bytes_sent": 100, "density": 0.01, "io_s": io_s,
           "step_s": step_s, "skipped": 0.0, "nonfinite": 0.0,
           "density_achieved": 0.01, "ef_norm": 1.0}
    if sparse:
        rec["wire_format"] = "u16bf16"
    rec.update(kw)
    return rec


def feed(mon, records, tick_every_train=True):
    out = []
    for r in records:
        mon.emit(r)
        if tick_every_train and r.get("event") == "train":
            out.append(mon.tick(int(r["step"])))
    return out


# ------------------------------------------------------------- detectors

def test_clean_window_is_ok():
    mon = HealthMonitor(density_target=0.01)
    verdicts = feed(mon, [train_rec((i + 1) * 2) for i in range(8)])
    assert all(v["state"] == "ok" and v["state_code"] == OK
               and v["causes"] == [] for v in verdicts)
    assert verdicts[-1]["step_s_p50"] == pytest.approx(0.1)
    assert verdicts[-1]["step_s_p99"] == pytest.approx(0.1)
    s = mon.summary()
    assert s["worst_state"] == "ok" and s["incidents"] == []


def test_data_wait_fraction_degraded_and_critical():
    mon = HealthMonitor()
    v = feed(mon, [train_rec((i + 1) * 2, io_s=0.06) for i in range(4)])
    assert v[-1]["causes"] == ["data_wait"]
    assert v[-1]["state"] == "degraded"
    assert v[-1]["evidence"]["data_wait"]["data_wait_frac"] \
        == pytest.approx(0.375)
    mon2 = HealthMonitor()
    v2 = feed(mon2, [train_rec((i + 1) * 2, io_s=0.3) for i in range(4)])
    assert v2[-1]["state"] == "critical"
    assert v2[-1]["causes"] == ["data_wait"]


def test_data_wait_io_retry_burst_without_train_records():
    # the FlakyIterator shape: the loader retries before a single train
    # interval lands — the burst alone must attribute data_wait
    mon = HealthMonitor()
    for _ in range(2):
        mon.emit({"event": "io_retry", "attempt": 1, "max_retries": 3,
                  "backoff_s": 0.01, "error": "ChaosError"})
    v = mon.tick(2)
    assert v["state"] == "degraded" and v["causes"] == ["data_wait"]
    assert v["evidence"]["data_wait"]["io_retries"] == 2
    # retries age out of the window once quiet intervals pass
    for step in range(4, 22, 2):
        v = mon.tick(step)
    assert v["state"] == "ok"


def test_ef_pressure_critical_and_pre_arm_vocabulary():
    mon = HealthMonitor()
    v = feed(mon, [train_rec((i + 1) * 2, ef_norm=200.0 + i)
                   for i in range(4)])
    assert v[-1]["state"] == "critical"
    assert v[-1]["causes"] == ["ef_pressure"]
    assert v[-1]["state_code"] == CRITICAL
    assert "ef_pressure" in PRE_ARM_CAUSES
    # high but flat/falling ratio below critical: not flagged
    mon2 = HealthMonitor()
    v2 = feed(mon2, [train_rec((i + 1) * 2, ef_norm=20.0 - i)
                     for i in range(4)])
    assert v2[-1]["state"] == "ok"
    # dense warm-up intervals (no wire_format) must not feed the gauge
    mon3 = HealthMonitor()
    v3 = feed(mon3, [train_rec((i + 1) * 2, sparse=False, ef_norm=0.0)
                     for i in range(4)])
    assert v3[-1]["state"] == "ok"


def test_density_drift_needs_persistence():
    mon = HealthMonitor(density_target=0.01)
    recs = [train_rec((i + 1) * 2, density_achieved=0.05)
            for i in range(3)]
    v = feed(mon, recs)
    assert v[1]["state"] == "ok"          # 2 drifted intervals: not yet
    assert v[2]["causes"] == ["density_drift"]
    assert v[2]["evidence"]["density_drift"]["drifted_intervals"] == 3


def test_instability_skip_then_rollback_escalates():
    mon = HealthMonitor()
    mon.emit({"event": "skip", "step": 7, "nonfinite": 1.0})
    v = mon.tick(8)
    assert v["state"] == "degraded" and v["causes"] == ["instability"]
    mon.emit({"event": "rollback", "reason": "skip_budget", "rollback": 1,
              "to_step": 4, "lr_scale": 0.5, "checkpoint": "c"})
    v = mon.tick(10)
    assert v["state"] == "critical"
    assert v["evidence"]["instability"]["rollbacks"] == 1


def test_step_time_regression_compares_windows():
    pol = HealthPolicy(window=4)
    mon = HealthMonitor(policy=pol)
    recs = [train_rec((i + 1) * 2, step_s=0.05) for i in range(4)]
    recs += [train_rec((i + 5) * 2, step_s=0.2) for i in range(4)]
    v = feed(mon, recs)
    assert v[-1]["causes"] == ["step_time_regression"]
    assert v[-1]["step_s_trend"] == pytest.approx(4.0)
    # the reverse (a slow compile-polluted start) must NOT flag
    mon2 = HealthMonitor(policy=pol)
    rev = [train_rec((i + 1) * 2, step_s=0.2) for i in range(4)]
    rev += [train_rec((i + 5) * 2, step_s=0.05) for i in range(4)]
    assert feed(mon2, rev)[-1]["state"] == "ok"


def test_policy_thrash_ages_out_of_the_window():
    mon = HealthMonitor()
    for step in (2, 4):
        mon.emit({"event": "policy_revert", "step": step, "rule": "r",
                  "knob": "density", "old": "0.005", "new": "0.01",
                  "reason": "loss spike", "quarantined": True})
    v = mon.tick(4)
    assert "policy_thrash" in v["causes"]
    assert v["evidence"]["policy_thrash"]["quarantined"] == 2
    # not sticky: the reverts leave the window once quiet intervals pass
    for step in range(6, 30, 2):
        v = mon.tick(step)
    assert v["state"] == "ok" and v["causes"] == []


# ---------------------------------------------- record contract & replay

def test_health_record_validates_on_a_strict_bus():
    mon = HealthMonitor()
    mon.emit({"event": "skip", "step": 3, "nonfinite": 1.0})
    rec = mon.tick(4)
    mem = MemoryExporter()
    bus = EventBus([mem], validate=True)     # fail-loud CI mode
    bus.publish(dict(rec))
    bus.close()
    out = mem.records[0]
    assert out["event"] == "health_status" and out["seq"] == 0


def test_replay_matches_live_verdicts_and_skips_recorded_ones(tmp_path):
    # a live-monitored stream: interleave the monitor's own verdicts the
    # way the trainer writes them, then replay the file — the replayed
    # verdicts must equal the recorded ones exactly
    live = HealthMonitor()
    stream = []
    for i in range(6):
        io = 0.2 if i >= 3 else 0.0
        r = train_rec((i + 1) * 2, io_s=io)
        stream.append(r)
        live.emit(r)
        h = live.tick(r["step"])
        stream.append(h)
        live.emit(h)        # the bus fans published verdicts back too
    recorded = [r for r in stream if r["event"] == "health_status"]
    replayed, mon = replay_health(stream)
    assert [r["state"] for r in replayed] == [r["state"] for r in recorded]
    assert [r["causes"] for r in replayed] \
        == [r["causes"] for r in recorded]
    assert replayed[-1]["causes"] == ["data_wait"]
    assert mon.summary()["worst_state"] == live.summary()["worst_state"]


def test_incident_bookkeeping_and_format():
    mon = HealthMonitor()
    mon.emit({"event": "skip", "step": 5, "nonfinite": 1.0})
    mon.tick(6)                                 # degraded opens
    mon.emit({"event": "rollback", "reason": "skip_budget", "rollback": 1,
              "to_step": 4, "lr_scale": 0.5, "checkpoint": "c"})
    mon.tick(8)                                 # escalates: new incident
    for step in range(10, 28, 2):
        mon.tick(step)                          # decays back to ok
    s = mon.summary()
    assert s["worst_state"] == "critical" and s["last_state"] == "ok"
    assert [i["state"] for i in s["incidents"]] == ["degraded", "critical"]
    assert s["incidents"][0]["causes"] == ["instability"]
    assert s["cause_steps"]["instability"] > 0
    text = format_health(s)
    assert "worst state: critical" in text and "instability" in text


# --------------------------------------------------- prometheus exporter

def test_prometheus_health_gauges_set_and_clear(tmp_path):
    path = str(tmp_path / "gksgd.prom")
    exp = PrometheusTextfileExporter(path)
    exp.emit({"event": "health_status", "step": 4, "state": "degraded",
              "state_code": 1, "causes": ["data_wait"]})
    text = open(path).read()
    assert "gksgd_health_state 1\n" in text
    assert 'gksgd_health_cause_active{cause="data_wait"} 1\n' in text
    exp.emit({"event": "health_status", "step": 6, "state": "ok",
              "state_code": 0, "causes": []})
    exp.close()
    text = open(path).read()
    assert "gksgd_health_state 0\n" in text
    # once seen, a cause stays exported at 0 so dashboards see it clear
    assert 'gksgd_health_cause_active{cause="data_wait"} 0\n' in text
    assert not [p for p in os.listdir(tmp_path)
                if p.startswith("gksgd.prom.tmp")]


# -------------------------------------------- policy / resilience hookup

class _AlwaysPropose(Rule):
    name = "always"

    def propose(self, snap, ctx):
        return PolicyDecision(step=snap.step, rule=self.name,
                              knob="density", old="0.01", new="0.005",
                              reason="test")


def test_signals_ingest_health_and_engine_gates_exploration():
    sig = PolicySignals()
    eng = PolicyEngine([_AlwaysPropose()], signals=sig, hysteresis=1,
                       cooldown=0)
    sig.update({"event": "health_status", "step": 4, "state": "degraded",
                "state_code": 1, "causes": ["data_wait"]})
    snap = sig.snapshot()
    assert snap.health_state == DEGRADED
    assert snap.health_causes == ("data_wait",)
    assert eng.decide() is None            # non-ok verdict holds the loop
    sig.update({"event": "health_status", "step": 6, "state": "ok",
                "state_code": 0, "causes": []})
    assert sig.snapshot().health_state == OK
    assert eng.decide() is not None        # recovered: exploration resumes


def test_resilience_pre_arm_fires_hooks_once():
    mon = ResilienceMonitor(ResiliencePolicy(max_consecutive_skips=3))
    fired = []
    mon.add_anomaly_hook(lambda reason, step: fired.append((reason, step)))
    mon.pre_arm("health:ef_pressure", 40)
    mon.pre_arm("health:ef_pressure", 42)      # already pending: no-op
    assert mon.should_rollback() == "health:ef_pressure"
    assert mon.pending_since == 40
    assert fired == [("health:ef_pressure", 40)]


# ----------------------------------------------------------- HTTP surface

def test_health_server_endpoints(tmp_path):
    mon = HealthMonitor()
    feed(mon, [train_rec(2)])
    prom = tmp_path / "gksgd.prom"
    prom.write_text("gksgd_events_total{event=\"train\"} 1\n")
    srv = HealthServer(mon, port=0, prom_path=str(prom)).start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        d = json.loads(urllib.request.urlopen(f"{base}/healthz").read())
        assert d["state"] == "ok" and d["worst_state"] == "ok"
        assert d["verdicts"] == 1
        met = urllib.request.urlopen(f"{base}/metrics").read().decode()
        assert "gksgd_events_total" in met     # serves the textfile
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{base}/nope")
        assert ei.value.code == 404
        # a critical verdict flips /healthz to 503 (still JSON)
        mon.emit({"event": "rollback", "reason": "x", "rollback": 1,
                  "to_step": 0, "lr_scale": 0.5, "checkpoint": "c"})
        mon.tick(4)
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{base}/healthz")
        assert ei.value.code == 503
        assert json.loads(ei.value.read())["state"] == "critical"
    finally:
        srv.close()


# ------------------------------------------------------------ offline CLI

def _write_stream(path, records):
    with open(path, "w") as fh:
        for r in records:
            fh.write(json.dumps(r) + "\n")


def test_cli_health_exit_codes(tmp_path, capsys):
    clean = tmp_path / "clean.jsonl"
    _write_stream(clean, [train_rec((i + 1) * 2) for i in range(4)])
    assert telemetry_cli(["health", str(clean)]) == 0
    out = capsys.readouterr().out
    assert "worst state: ok" in out

    degraded = tmp_path / "degraded.jsonl"
    _write_stream(degraded, [train_rec((i + 1) * 2, io_s=0.06)
                             for i in range(4)])
    assert telemetry_cli(["health", str(degraded)]) == 1
    capsys.readouterr()                        # drain the text rendering

    critical = tmp_path / "critical.jsonl"
    _write_stream(critical, [
        train_rec(2),
        {"event": "rollback", "reason": "x", "rollback": 1, "to_step": 0,
         "lr_scale": 0.5, "checkpoint": "c"},
        train_rec(4),
    ])
    assert telemetry_cli(["health", str(critical), "--json"]) == 2
    out = capsys.readouterr().out
    assert json.loads(out)["worst_state"] == "critical"

    # missing / empty files exit 3, never aliasing a critical verdict
    assert telemetry_cli(["health", str(tmp_path / "nope.jsonl")]) == 3
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    assert telemetry_cli(["health", str(empty)]) == 3


def test_report_gains_run_health_section(tmp_path):
    path = tmp_path / "run.jsonl"
    _write_stream(path, [
        train_rec(2), train_rec(4),
        {"event": "skip", "step": 5, "nonfinite": 1.0},
        train_rec(6, skipped=1.0),
        train_rec(8),
    ])
    summary = summarize(load_events(str(path)))
    h = summary["health"]
    assert h["worst_state"] == "degraded"
    assert h["incidents"][0]["causes"] == ["instability"]
    text = format_report(summary)
    assert "== run health (worst: degraded" in text
    assert "instability" in text


# ------------------------------------------------- trainer e2e (chaos)

def make_cfg(tmp_path, **kw):
    base = dict(
        dnn="mnistnet", dataset="mnist", batch_size=8, nworkers=8,
        lr=0.05, momentum=0.9, weight_decay=0.0, epochs=1, max_steps=12,
        compressor="gaussian", density=0.01, compress_warmup_steps=4,
        warmup_epochs=0.0, compute_dtype="float32",
        output_dir=str(tmp_path), log_every=5, eval_every_epochs=0,
        save_every_epochs=0, seed=0,
    )
    base.update(kw)
    return TrainConfig(**base)


def read_events(t, kind=None):
    recs = [json.loads(line) for line in
            open(os.path.join(t.run_dir, "metrics.jsonl"))]
    return [r for r in recs if kind is None or r.get("event") == kind]


def test_default_run_attaches_no_monitor_and_emits_no_health(tmp_path):
    # the byte-identity gate: --health off (the default) builds no
    # monitor, no server, and publishes no health_status records
    t = Trainer(make_cfg(tmp_path, max_steps=4, log_every=2))
    assert t.health is None and t._health_server is None
    t.fit()
    t.close()
    assert read_events(t, "health_status") == []


def test_clean_health_run_is_ok_everywhere(tmp_path):
    # lr=0.005, not make_cfg's 0.05: that one is x8 workers = 0.4 with no
    # LR warm-up, and mnistnet DIVERGES under it (loss 3 -> 75 by step 4).
    # The dead network's gradient has ~700 non-zeros of 1.66M, no threshold
    # can select k=16634 of them, and density_drift says so — correctly.
    # A clean run has to be one: the loss falls and selection tracks k.
    t = Trainer(make_cfg(tmp_path, max_steps=10, log_every=2, lr=0.005,
                         health="on", health_port=0))
    port = t._health_server.port
    t.fit()
    train = read_events(t, "train")
    assert train[-1]["loss"] < train[0]["loss"]
    live = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{port}/healthz").read())
    t.close()
    verdicts = read_events(t, "health_status")
    assert len(verdicts) == 5                  # one per train interval
    assert all(v["state"] == "ok" for v in verdicts)
    assert live["worst_state"] == "ok"
    path = os.path.join(t.run_dir, "metrics.jsonl")
    assert validate_file(path, strict=True).ok
    assert telemetry_cli(["health", path]) == 0
    assert summarize(load_events(path))["health"]["worst_state"] == "ok"


def test_nan_chaos_attributes_instability_everywhere(tmp_path, capsys):
    # ISSUE acceptance: injected NaN -> skip -> rollback must yield an
    # instability-attributed verdict within a bounded number of steps,
    # visible identically via live endpoint JSON, offline CLI exit code,
    # and the report section — on a strictly-valid stream
    t = Trainer(make_cfg(tmp_path, max_steps=12, log_every=2,
                         save_every_steps=4, max_consecutive_skips=1,
                         health="on", health_port=0))
    chaos.inject_nan_batches(t, {6})           # poisons step 7
    port = t._health_server.port
    while t.step < t.total_steps:
        t.train(t.total_steps - t.step)
    # the rollback is still inside the rolling window at run end, so the
    # probe contract says 503 — the JSON body still carries the status
    try:
        live = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz").read())
    except urllib.error.HTTPError as e:
        assert e.code == 503
        live = json.loads(e.read())
    t.close()

    verdicts = read_events(t, "health_status")
    flagged = [v for v in verdicts if "instability" in v["causes"]]
    assert flagged, "no instability verdict after NaN injection"
    # bounded detection: first attribution within 2 intervals of the hit
    assert flagged[0]["step"] <= 7 + 2 * t.cfg.log_every
    assert max(v["state_code"] for v in verdicts) == CRITICAL
    assert read_events(t, "rollback")          # the rewind really ran

    path = os.path.join(t.run_dir, "metrics.jsonl")
    assert validate_file(path, strict=True).ok
    # the three surfaces agree on the worst state and its cause
    assert live["worst_state"] == "critical"
    assert telemetry_cli(["health", path]) == 2
    assert "instability" in capsys.readouterr().out
    h = summarize(load_events(path))["health"]
    assert h["worst_state"] == "critical"
    assert any("instability" in i["causes"] for i in h["incidents"])


def test_data_stall_chaos_attributes_data_wait(tmp_path):
    # ISSUE acceptance: loader stalls (transient read failures, retried
    # with backoff) must yield a data_wait-attributed degraded verdict
    t = Trainer(make_cfg(tmp_path, max_steps=10, log_every=2,
                         io_backoff_s=0.001, health="on"))
    t.train_ds = chaos.FlakyEpochSource(t.train_ds, fail_batches=[1, 2],
                                        times=1)
    t.fit()
    t.close()
    verdicts = read_events(t, "health_status")
    flagged = [v for v in verdicts if "data_wait" in v["causes"]]
    assert flagged, "no data_wait verdict after loader stalls"
    assert flagged[0]["state_code"] >= DEGRADED
    assert flagged[0]["evidence"]["data_wait"]["io_retries"] >= 2
    path = os.path.join(t.run_dir, "metrics.jsonl")
    assert validate_file(path, strict=True).ok
    assert telemetry_cli(["health", path]) >= 1
