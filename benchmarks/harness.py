"""The benchmark's harness: everything `run.py` does after it has found its
chips, as functions that the tests under `benchmarks/tests/` call on the CPU.

Driven by data. A cell is an entry of `BENCHMARK.json`'s `workloads`; its
configuration is `configs/<config>.json` with its plain reference
`reference/<reference>.py`; its traffic mix is `traffic/<traffic>.json`; every
per-layer metric is `layer_metrics/<name>.py`. Adding any of them adds files
and entries and edits nothing here.

From the program (`gaussiank_sgd_tpu`) it takes the system under test, built
exactly as the CLI builds it (`train.make_trainer(argv)`), and drives it
through `Trainer.train(n, data_iter=...)`. Weights are the benchmark's own,
made from the seed by the configuration's reference file and handed to every
trainer of the run; inputs are the trainer's own seeded stream, timed and copied on the
way through.
"""

from __future__ import annotations

import contextlib
import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_SAVED_EVENT = "/jax/compilation_cache/compile_time_saved_sec"

# Steps an arm takes, uncounted, whenever the other arm had the turn before
# it: while it sat out its input stream stood still (GatedStream), so at most
# the one batch that was in the producer's hand is ready, and after that
# many steps its loop is back in the stride of a run with one trainer.
LEAD_IN_STEPS = 2

# The arms a mix's `round` may name, in the order they are built, and the arm
# each end-to-end metric is read from. The sparse trainer is the system under
# test and every round names it. The dense baseline is the control arm: it
# costs the harness 12 bytes a parameter at rest for the whole run (float32
# parameters, momentum and the residual the program allocates for it too),
# so whether a cell carries it is the mix's choice. It has to be a mix's and
# not the harness's, because a later PR that adds a configuration may add
# files only: a mix whose round is ["sparse"] runs one trainer.
ARM_ORDER = ("dense", "sparse")
METRIC_ARM = {"examples_per_s": "sparse", "step_ms_p95": "sparse",
              "dense_examples_per_s": "dense"}


def say(*parts) -> None:
    print(*parts, flush=True)


# ------------------------------------------------------------------ data files

def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT) -> dict:
    """The cell's entry with its configuration, traffic mix and the metrics
    it reports, each read from the file its name points to."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; it has "
                         f"{sorted(cells)}")
    cell = dict(cells[name])
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(root, cfg_entry["file"])) as f:
        cell["config_data"] = json.load(f)
    cell["config_data"]["reference_dir"] = os.path.join(
        root, bench["paths"][0], "reference")
    mix_path = os.path.join(root, bench["paths"][0], "traffic",
                            cell["traffic"] + ".json")
    with open(mix_path) as f:
        cell["mix"] = json.load(f)

    def mine(metric):
        return "workloads" not in metric or name in metric["workloads"]

    cell["arms"] = arm_names(cell["mix"])
    cell["end_to_end"] = [m for m in bench["end_to_end"] if mine(m)]
    absent = [m["name"] for m in cell["end_to_end"]
              if METRIC_ARM.get(m["name"]) not in (None, *cell["arms"])]
    if absent:
        raise SystemExit(
            f"cell {name!r} lists {absent}, read from an arm that its mix "
            f"{cell['traffic']!r} does not run (round {cell['mix']['round']})"
            f": take the cell out of those metrics' `workloads`")
    cell["per_layer"] = [m for m in bench["per_layer"] if mine(m)]
    cell["metrics_dir"] = os.path.join(root, bench["paths"][0],
                                       "layer_metrics")
    return cell


def arm_names(mix: dict) -> List[str]:
    """The arms the mix's `round` names, in the order they are built."""
    rnd = list(mix["round"])
    if "sparse" not in rnd or set(rnd) - set(ARM_ORDER):
        raise SystemExit(
            f"mix {mix.get('name')!r}: `round` is {rnd}; it names the sparse "
            f"trainer (the system under test) and may name the dense "
            f"baseline, nothing else")
    return [a for a in ARM_ORDER if a in rnd]


def load_peaks(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)
    if device_kind not in peaks or device_kind.startswith("_"):
        raise KeyError(f"no peaks on record for device_kind {device_kind!r} "
                       f"in benchmarks/peaks.json; add it with its source")
    return peaks[device_kind]


def load_reference(config: dict):
    """The configuration's plain reference, `reference/<reference>.py`
    beside the cell's other files (`load_cell` says where), as a module of
    the package `benchmarks.reference`, whose `common` it imports."""
    name = f"benchmarks.reference.{config['reference']}"
    path = os.path.join(config.get("reference_dir", ""),
                        config["reference"] + ".py")
    if name not in sys.modules and os.path.exists(path):
        spec = importlib.util.spec_from_file_location(name, path)
        sys.modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[name])
    return importlib.import_module(name)


def load_layer_metric(metrics_dir: str, name: str):
    """A per-layer metric's reader: `layer_metrics/<name>.py`, found by the
    metric's name, with `read(run) -> number or None`."""
    path = os.path.join(metrics_dir, name + ".py")
    if not os.path.exists(path):
        path = os.path.join(HERE, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------- compile bookkeeping

class CompileLog:
    """`jax.monitoring` listeners (as chip_smoke.py's): seconds spent in the
    backend compiler, seconds the persistent cache saved, cache hits and
    misses, and how many compilations fell inside the measured window."""

    def __init__(self):
        import jax
        self.spent = 0.0
        self.saved = 0.0
        self.events: Dict[str, int] = {}
        self.in_window = 0
        self.window_open = False
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == _COMPILE_EVENT:
            self.spent += duration
            if self.window_open:
                self.in_window += 1
        elif event == _SAVED_EVENT:
            self.saved += duration

    def _event(self, event, **kw):
        if event.startswith("/jax/compilation_cache/cache_"):
            key = event.rsplit("/", 1)[1]
            self.events[key] = self.events.get(key, 0) + 1


def host_peak_gib() -> float:
    """The process's peak resident size so far, GiB."""
    import resource
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20


class Parts:
    """Seconds by part: named stretches of ONE thread's wall clock, in the
    order they first ran, so that they add up to the total they are printed
    beside; with a `CompileLog`, the backend-compile seconds that fell
    inside each part stand apart; and where the host's peak resident size
    grew by a quarter GiB or more inside a part, what it grew to.
    `with parts("name"):` times a stretch (the same name again adds to
    it); `add` takes seconds measured elsewhere, with `host_peak_gib()`
    before and after them where it was read."""

    def __init__(self, compile_log: Optional[CompileLog] = None):
        self.seconds: Dict[str, float] = {}
        self.compile: Dict[str, float] = {}
        self.host_peak: Dict[str, float] = {}
        self._log = compile_log

    def add(self, name: str, seconds: float, compile_s: float = 0.0,
            host_peak: Optional[tuple] = None) -> None:
        self.seconds[name] = self.seconds.get(name, 0.0) + seconds
        if compile_s:
            self.compile[name] = self.compile.get(name, 0.0) + compile_s
        if host_peak is not None and host_peak[1] >= host_peak[0] + 0.25:
            self.host_peak[name] = host_peak[1]

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0, peak0 = time.perf_counter(), host_peak_gib()
        c0 = self._log.spent if self._log is not None else 0.0
        try:
            yield
        finally:
            self.add(name, time.perf_counter() - t0,
                     (self._log.spent - c0) if self._log is not None else 0.0,
                     (peak0, host_peak_gib()))

    def record(self, total: float) -> dict:
        """{"total_s", "parts": {name: seconds}, "compile": {name: seconds},
        "host_peak_gib": {name: GiB}, "unnamed_s"}: what the line says, for
        the result object."""
        return {"total_s": total, "parts": dict(self.seconds),
                "compile": dict(self.compile),
                "host_peak_gib": dict(self.host_peak),
                "unnamed_s": total - sum(self.seconds.values())}

    def line(self, title: str, total: float) -> str:
        named = sum(self.seconds.values())
        parts = ", ".join(
            f"{name} {secs:.2f}"
            + (f" (compile {self.compile[name]:.2f})"
               if name in self.compile else "")
            + (f" [host peak {self.host_peak[name]:.1f} GiB]"
               if name in self.host_peak else "")
            for name, secs in self.seconds.items())
        return (f"{title} {total:.2f}s = {parts}; named {named:.2f}s, "
                f"unnamed {total - named:.2f}s "
                f"({100.0 * (total - named) / max(total, 1e-9):.1f} %)")


# Elements a thread takes at a time where the harness or the check goes over
# a full-length vector on the host, and the threads the blocks are shared
# among (numpy releases the interpreter's lock inside an operation). The
# arithmetic is elementwise, so neither changes a bit, and no temporary is
# longer than a block whatever the configuration's size.
BLOCK = 1 << 20
THREADS = 8


def blocks(n: int) -> List[slice]:
    return [slice(lo, min(n, lo + BLOCK)) for lo in range(0, n, BLOCK)]


def on_blocks(fn, tasks) -> list:
    """`fn(task)` for every task on `THREADS` threads; the results in the
    tasks' order, whichever thread made them (an exception is raised
    here)."""
    from concurrent.futures import ThreadPoolExecutor
    with ThreadPoolExecutor(max_workers=THREADS) as pool:
        return list(pool.map(fn, tasks))


# ------------------------------------------------------------------ the feed

class TimedFeed:
    """The trainer's own prefetching iterator, timed from outside: the time
    between two successive `next()` calls is one whole loop iteration, the
    wait for data and the loop's host work included. Keeps host copies of
    the first `keep` batches for the reference."""

    def __init__(self, it, keep: int = 0):
        self._it = it
        self.keep = keep
        self.kept: List[Any] = []
        self.enter: List[float] = []
        self.leave: List[float] = []

    def __iter__(self):
        return self

    def __next__(self):
        t0 = time.perf_counter()
        batch = next(self._it)
        self.enter.append(t0)
        self.leave.append(time.perf_counter())
        if len(self.kept) < self.keep:
            self.kept.append(tuple(np.array(a) for a in batch))
        return batch

    def mark(self) -> int:
        return len(self.enter)


class GatedStream:
    """The trainer's own input stream (`Trainer._stream()`) behind a gate.

    A run with two trainers drives one at a time. The one sitting out
    must not go on producing batches: its producer thread would take the
    host from the other one's loop, and its queue would be full at its next
    turn, which no step of a run with one trainer finds once the host is
    the slower side. The gate is open while the arm is driven and shut
    otherwise; a shut gate holds the producer at its next pull, whatever
    the depth of the queue or the number of producers behind it. The
    batches and their order are the stream's own."""

    def __init__(self, source, gate: threading.Event):
        self._source = iter(source)
        self._gate = gate
        self.pulls = 0

    def __iter__(self):
        return self

    def __next__(self):
        self._gate.wait()
        self.pulls += 1
        return next(self._source)


class BusTap:
    """Rides the trainer's event bus and counts the steps that the in-step
    guard skipped."""

    def __init__(self):
        self.skips = 0

    def emit(self, record) -> None:
        if record.get("event") == "skip":
            self.skips += 1

    def flush(self) -> None:
        return None

    def close(self) -> None:
        return None


# ------------------------------------------------------------- the trainers

def trainer_argv(config: dict, mix: dict, seed: int, arm: str, out_dir: str,
                 trace: bool) -> List[str]:
    """The argument list the CLI would get: `--config <file>` with the
    benchmark's own copy of the shipped configuration's fields. Everything
    but the seed and the output directory is fixed by the configuration and
    the mix, so every run compiles the same programs.

    The data set is the program's synthetic one, sized by the worker: the
    configuration's `dataset_kwargs` as they stand, and each entry of its
    `dataset_kwargs_per_worker` times the mix's workers. A configuration
    that gives neither is an image one: `synthetic_examples` is its
    `examples_per_worker` a worker."""
    nworkers = int(mix["nworkers"])
    fields = dict(config["trainer"])
    per_worker = config.get(
        "dataset_kwargs_per_worker",
        {"synthetic_examples": config["examples_per_worker"]})
    dataset_kwargs = dict(config.get("dataset_kwargs", {}))
    dataset_kwargs.update({key: int(v) * nworkers
                           for key, v in per_worker.items()})
    fields.update(
        nworkers=nworkers, log_every=int(mix["log_every"]), seed=int(seed),
        compressor=(config["sparse_compressor"] if arm == "sparse"
                    else "none"),
        output_dir=out_dir, run_id=arm, trace="on" if trace else "off",
        dataset_kwargs=dataset_kwargs)
    path = os.path.join(out_dir, f"{arm}.json")
    with open(path, "w") as f:
        json.dump(fields, f)
    return ["--config", path]


def path_of(key_path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "name", k)))
                    for k in key_path)


def leaves_by_path(tree) -> Dict[str, Any]:
    """{path: leaf} in the order jax flattens the tree, which is the order
    of the program's flat gradient, momentum and residual."""
    import jax
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {path_of(p): leaf for p, leaf in flat}


def give_weights(trainer, weights: Dict[str, Any]) -> None:
    """Hand the benchmark's weights to the trainer, leaf by leaf, under the
    shardings its own parameters have (the way a restore replaces them)."""
    import jax
    state = trainer.state
    mine = leaves_by_path(state.params)
    if set(mine) != set(weights):
        raise ValueError(
            f"the reference's parameters and the program's differ: only in "
            f"the program {sorted(set(mine) - set(weights))[:5]}, only in "
            f"the reference {sorted(set(weights) - set(mine))[:5]}")
    for p, leaf in mine.items():
        if tuple(leaf.shape) != tuple(weights[p].shape):
            raise ValueError(f"{p}: program {leaf.shape}, reference "
                             f"{weights[p].shape}")
    flat, treedef = jax.tree_util.tree_flatten_with_path(state.params)
    # from the host: a copy of its own for each trainer (the steps donate
    # their state), and no program to compile
    new = [jax.device_put(np.asarray(weights[path_of(p)], leaf.dtype),
                          leaf.sharding) for p, leaf in flat]
    trainer.state = state._replace(
        params=jax.tree_util.tree_unflatten(treedef, new))


def split_flat(flat: np.ndarray, like: Dict[str, Any]) -> Dict[str, np.ndarray]:
    """A flat vector in the program's layout as {path: array}."""
    out, off = {}, 0
    for p, leaf in like.items():
        n = int(np.prod(leaf.shape))
        out[p] = np.asarray(flat[off:off + n]).reshape(leaf.shape)
        off += n
    return out


class Arm:
    """One of the run's trainers ("sparse" or "dense") with its timed feed,
    its bus tap and what was read from its first steps."""

    def __init__(self, name: str, trainer, keep: int):
        self.name = name
        self.trainer = trainer
        self.tap = trainer.bus.attach(BusTap())
        self.gate = threading.Event()
        self.streams: List[GatedStream] = []
        make_stream = trainer._stream

        def gated_stream():
            self.streams.append(GatedStream(make_stream(), self.gate))
            return self.streams[-1]

        trainer._stream = gated_stream
        trainer._invalidate_data_iter()
        self.feed = TimedFeed(trainer._train_iter(), keep=keep)
        self.global_batch = trainer.cfg.global_batch_size
        self.blocks: List[dict] = []
        self.first: Dict[str, Any] = {}
        self.steps_per_block = 1

    def train(self, n: int) -> dict:
        """`Trainer.train(n)` on the timed feed, the arm's input stream
        running for as long as the call lasts."""
        self.gate.set()
        try:
            rec = self.trainer.train(n, data_iter=self.feed)
        finally:
            self.gate.clear()
        if sum(s.pulls for s in self.streams) < self.feed.mark():
            raise RuntimeError(
                "the trainer's batches no longer come through "
                "Trainer._stream(): an arm that sits out would go on "
                "producing, and the rates would be the harness's own")
        return rec


def build_arms(cell: dict, seed: int, out_dir: str, trace: bool,
               first_steps: int = 3, parts: Optional[Parts] = None):
    """The trainers that the mix's round names, built as the CLI builds
    them, with the benchmark's weights in place of their own. Returns
    (arms, the weights on the host in the program's order); the seconds of
    each part go to `parts`."""
    import jax
    parts = parts if parts is not None else Parts()
    with parts("program import"):
        from gaussiank_sgd_tpu import train as program
    config, mix = cell["config_data"], cell["mix"]
    ref = load_reference(config)
    t0 = time.perf_counter()
    with parts("weights from seed"):
        weights = {p: _host(v) for p, v in jax.jit(
            lambda k: ref.init_params(k, config))(
                jax.random.PRNGKey(seed)).items()}
    say(f"weights from seed {seed}: {len(weights)} leaves, "
        f"{sum(int(v.size) for v in weights.values())} parameters, "
        f"{time.perf_counter() - t0:.1f}s")
    arms = {}
    for name in cell["arms"]:
        t0 = time.perf_counter()
        with parts(f"{name} trainer"):
            trainer = program.make_trainer(
                trainer_argv(config, mix, seed, name, out_dir, trace))
            give_weights(trainer, weights)
            arms[name] = Arm(name, trainer, keep=first_steps)
        say(f"{name} trainer built in {time.perf_counter() - t0:.1f}s: "
            f"kernel={trainer.ts.kernel_mode} wire={trainer.ts.wire_format} "
            f"ef_numel={trainer.ts.ef_numel} k={trainer.plan.total_k} "
            f"global_batch={trainer.cfg.global_batch_size}; in use on the "
            f"fullest chip {_in_use()} bytes")
    order = leaves_by_path(arms[cell["arms"][0]].trainer.state.params)
    return arms, {p: weights[p] for p in order}


# ------------------------------------------------------------- first steps

def _host(x, out: Optional[np.ndarray] = None) -> np.ndarray:
    """A device array on the host (into `out` where one is given), in
    slices on threads where it is long (`reference/common.py` `fetch`)."""
    from .reference.common import fetch
    return fetch(x, out)


def _in_use() -> int:
    """Bytes in use on the fullest device, by its allocator (arrays only)."""
    import jax
    return max(int((d.memory_stats() or {}).get("bytes_in_use", 0))
               for d in jax.local_devices())


def first_steps(arm: Arm, config: dict, steps: int = 3) -> None:
    """Drive the trainer through its first steps by the window's own call
    and feed, one step at a time, and read from its state what the check
    compares: each step's loss, the momentum buffer and the residual after
    the first step, which entries each worker sent at each step, and the
    parameters after the last.

    An entry counts as sent by worker w at a step when it is zero in w's
    residual after the step AND something arrived at it in the momentum
    buffer, i.e. the buffer differs there from `mu * m + wd * p` of the
    state before. (Zero in the residual alone also holds where a worker's
    gradient is exactly zero, a dead unit.)

    Nothing is kept on the device beside the trainer's state while a step
    runs (`arm.first["step_held_bytes"]` is what was in use then). After
    a step the momentum and the parameters cross to the host in slices on
    threads (`_host`), and the residual's zero test as one bool array made
    by one fused pass on the device; the arrival test is the host's, in
    float32 as it is written here, a block at a time on threads."""
    import jax
    tr = arm.trainer
    n = tr.plan.total_numel
    nworkers = tr.mesh.size
    mu = np.float32(config["trainer"]["momentum"])
    wd = np.float32(config["trainer"]["weight_decay"])
    sparse = arm.name == "sparse"
    losses, masks, held = [], [], 0
    inner = Parts()
    arm.first["k"] = int(tr.plan.total_k)
    arm.first["built"] = {"wire_format": tr.ts.wire_format,
                          "kernel_mode": tr.ts.kernel_mode,
                          "buckets": len(tr.plan.buckets)}

    @jax.jit
    def zero_in(residual):
        return residual.reshape(nworkers, -1)[:, :n] == 0

    def state_to_host(with_zero_test: bool):
        """(momentum, parameters flat in the program's order, the zero
        test or None)."""
        state = tr._state
        leaves = list(leaves_by_path(state.params).values())
        flat, off = np.empty((n,), leaves[0].dtype), 0
        for leaf in leaves:
            _host(leaf, flat[off:off + leaf.size].reshape(leaf.shape))
            off += leaf.size
        return (_host(state.opt_state["m"])[:n], flat,
                _host(zero_in(state.ef_residual)) if with_zero_test
                else None)

    prev_m = np.zeros((n,), np.float32)
    with inner("state to the host"):
        _, prev_p, _ = state_to_host(False)
    for s in range(steps):
        held = max(held, _in_use())
        with inner("the steps"):
            rec = arm.train(1)
        losses.append(float(rec["loss"]))
        state = tr._state
        with inner("state to the host"):
            m, p, zero = state_to_host(sparse)
        if sparse:
            mask = np.empty(zero.shape, bool)

            def sent(b):
                quiet = mu * prev_m[b] + wd * prev_p[b]
                arrived = (np.abs(m[b] - quiet)
                           > 1e-5 * np.abs(quiet) + 1e-12)
                np.logical_and(zero[:, b], arrived[None, :], out=mask[:, b])

            with inner("masks on the host"):
                on_blocks(sent, blocks(n))
            masks.append(mask)
        if s == 0:
            arm.first["momentum1"] = m
            # the dense baseline's residual is allocated and never read
            with inner("state to the host"):
                arm.first["residual1"] = (
                    _host(state.ef_residual).reshape(nworkers, -1)
                    if sparse else None)
            arm.first["dtypes"] = {
                "residual_dtype": str(state.ef_residual.dtype),
                "momentum_dtype": str(state.opt_state["m"].dtype)}
            arm.first["residual_devices"] = len(
                {d.id for d in state.ef_residual.sharding.device_set})
        prev_m, prev_p = m, p
    arm.first["losses"] = losses
    arm.first["masks"] = masks
    arm.first["params"] = split_flat(
        prev_p, leaves_by_path(tr._state.params))
    arm.first["batches"] = list(arm.feed.kept)
    arm.first["step_held_bytes"] = held
    say(inner.line(f"{arm.name} first steps by part:",
                   sum(inner.seconds.values())))


def warm_up(arm: Arm, mix: dict, max_intervals: int = 12) -> dict:
    """Dense: one log interval (its program has run three times in the
    first steps). Sparse: at least two log intervals, and on until
    `num_selected` has been inside [0.5, 2] k for one (the carried threshold
    starts cold). Sets the number of steps to a block from the last
    interval's own step time."""
    tr = arm.trainer
    every = int(mix["log_every"])
    k = tr.plan.total_k
    rec, seen = {}, 0
    arm.train(LEAD_IN_STEPS)
    for _ in range(max_intervals):
        t0 = time.perf_counter()
        rec = arm.train(every)
        step_s = (time.perf_counter() - t0) / every
        seen += 1
        if arm.name == "dense":
            break
        if seen >= 2 and 0.5 * k <= rec["num_selected"] <= 2.0 * k:
            break
    arm.steps_per_block = max(1, round(float(mix["block_seconds"]) / step_s))
    arm.first["warm_selected"] = float(rec.get("num_selected", 0.0))
    arm.first["warm_intervals"] = seen
    arm.first["warm_step_ms"] = step_s * 1e3
    return rec


# ----------------------------------------------------------------- the window

def block_order(mix: dict):
    """The mix's round of blocks, turned round every other round, so that
    neither arm always runs first."""
    rnd, i = list(mix["round"]), 0
    while True:
        yield from (rnd[::-1] if i % 2 else rnd)
        i += 1


def run_block(arm: Arm, profile_dir: Optional[str] = None) -> dict:
    """One block: `Trainer.train(n)`, ended by the loop's own
    `block_until_ready` on its last step. With `profile_dir` the block runs
    under the profiler (the traced run's steady window)."""
    import jax
    n = arm.steps_per_block
    skips0, mark = arm.tap.skips, arm.feed.mark()
    if profile_dir:
        from . import trace_reduce
        jax.profiler.start_trace(
            profile_dir, profiler_options=trace_reduce.profiler_options())
    t0 = time.perf_counter()
    arm.train(n)
    t1 = time.perf_counter()
    if profile_dir:
        jax.profiler.stop_trace()
    enter = arm.feed.enter[mark:mark + n]
    leave = arm.feed.leave[mark:mark + n]
    block = {"arm": arm.name, "t0": t0, "t1": t1, "steps": n,
             "skipped": arm.tap.skips - skips0,
             "iter_s": [b - a for a, b in zip(enter, enter[1:] + [t1])],
             "wait_s": [b - a for a, b in zip(enter, leave)],
             "first_step": arm.trainer._step_cache - n,
             "traced": bool(profile_dir)}
    arm.blocks.append(block)
    return block


def plan_blocks(mix: dict, seconds: float, trace: bool) -> List[dict]:
    """The window's blocks, fixed before it opens: as many as fit
    `seconds` at `block_seconds` each (at least one round), in the mix's
    order. The same seconds give the same layout in every run. In a traced
    run one block of each arm runs under the profiler: its second where it
    has two, so that the loop is in its stride."""
    count = max(len(mix["round"]),
                int(round(seconds / float(mix["block_seconds"]))))
    order = block_order(mix)
    blocks = [{"arm": next(order), "traced": False} for _ in range(count)]
    if trace:
        for arm in set(mix["round"]):
            mine = [b for b in blocks if b["arm"] == arm]
            for b in (mine[1:] + mine[:1])[:1]:
                b["traced"] = True
    return blocks


def measure(arms: Dict[str, Arm], mix: dict, seconds: float,
            compile_log: Optional[CompileLog] = None,
            trace_dir: Optional[str] = None) -> dict:
    """The measured window: the planned blocks, one after the other. An arm
    that takes over from the other first takes `LEAD_IN_STEPS` steps that
    count for nothing."""
    traced: Dict[str, List[str]] = {a: [] for a in arms}
    if compile_log is not None:
        compile_log.in_window, compile_log.window_open = 0, True
    t_open = time.perf_counter()
    before = None
    for i, plan in enumerate(plan_blocks(mix, seconds, bool(trace_dir))):
        name, pdir = plan["arm"], None
        if name != before:
            arms[name].train(LEAD_IN_STEPS)
        before = name
        if plan["traced"]:
            pdir = os.path.join(trace_dir, f"{name}_{len(traced[name])}")
            traced[name].append(pdir)
        b = run_block(arms[name], pdir)
        rate = arms[name].global_batch * (b["steps"] - b["skipped"]) / (
            b["t1"] - b["t0"])
        slowest = max(range(b["steps"]), key=b["iter_s"].__getitem__)
        say(f"block {i:3d} {name:6s} {b['steps']:4d} steps "
            f"{b['t1'] - b['t0']:.3f}s {rate:10.1f} examples/s, first wait "
            f"{1e3 * b['wait_s'][0]:.1f} ms, mean wait "
            f"{1e3 * sum(b['wait_s']) / len(b['wait_s']):.1f} ms, longest "
            f"iteration {1e3 * b['iter_s'][slowest]:.1f} ms (its "
            f"{slowest + 1}.)" + (" traced" if pdir else ""))
    window_s = time.perf_counter() - t_open
    if compile_log is not None:
        compile_log.window_open = False
    return {"window_s": window_s, "traced": traced}


def arm_totals(arm: Arm) -> dict:
    """Rates over all the arm's blocks of the window, per-iteration times
    over all its iterations."""
    blocks = arm.blocks
    wall = sum(b["t1"] - b["t0"] for b in blocks)
    steps = sum(b["steps"] for b in blocks)
    skipped = sum(b["skipped"] for b in blocks)
    iters = [t for b in blocks for t in b["iter_s"]]
    waits = [t for b in blocks for t in b["wait_s"]]
    return {"wall_s": wall, "steps": steps, "skipped": skipped,
            "examples_per_s": (arm.global_batch * (steps - skipped) / wall
                               if wall else 0.0),
            "iter_s": iters, "wait_s": waits}


def percentile(values: List[float], q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def end_to_end(arms: Dict[str, Arm], setup_s: float) -> Dict[str, float]:
    """The end-to-end metrics that the run's arms give (`METRIC_ARM`): one
    that needs an arm the mix does not run is not there."""
    sp = arm_totals(arms["sparse"])
    out = {"examples_per_s": sp["examples_per_s"],
           "step_ms_p95": 1e3 * percentile(sp["iter_s"], 95),
           "setup_s": setup_s}
    if "dense" in arms:
        out["dense_examples_per_s"] = arm_totals(
            arms["dense"])["examples_per_s"]
    return out


def program_memory(arm: Arm) -> dict:
    """XLA's own account of the arm's step program on one device: its
    temporaries and the outputs that do not alias an argument. Lowering and
    compiling the program that just ran is a cache hit."""
    tr = arm.trainer
    fn = tr.ts.dense_step if tr.is_dense_only else tr.ts.sparse_step
    m = fn.lower(tr._state, tr._probe_batch).compile().memory_analysis()
    return {"temp": int(m.temp_size_in_bytes),
            "argument": int(m.argument_size_in_bytes),
            "fresh_output": int(m.output_size_in_bytes
                                - m.alias_size_in_bytes)}


def state_bytes(arm: Arm) -> int:
    """Bytes of the arm's training state at rest on its fullest chip:
    parameters, optimizer state, residual, whatever else `Trainer._state`
    holds there."""
    import jax
    per: Dict[int, int] = {}
    for leaf in jax.tree_util.tree_leaves(arm.trainer._state):
        for shard in getattr(leaf, "addressable_shards", ()):
            per[shard.device.id] = (per.get(shard.device.id, 0)
                                    + int(shard.data.nbytes))
    return max(per.values(), default=0)


def device_report(chips: int, arms: Optional[Dict[str, Arm]] = None) -> dict:
    """The device as JAX reports it, the peak bytes on the fullest chip, and
    under `memory` what the run holds there.

    On this runtime the allocator's `peak_bytes_in_use` counts the arrays
    that live on the device and NOT the temporaries of a running XLA program
    (a step whose activations alone are gigabytes leaves it at the size of
    the state). The peak is therefore what is at rest after the window (the
    trainers' state, the batches in flight) plus the largest of the step
    programs' temporaries and fresh outputs, by XLA's memory analysis of
    the compiled program; never less than the allocator's own peak."""
    import jax
    devs = jax.devices()[:chips] if chips else jax.devices()
    peak = rest = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        rest = max(rest, int(stats.get("bytes_in_use", 0)))
    report = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    if arms:
        progs = {n: program_memory(a) for n, a in arms.items()}
        need = max(p["temp"] + p["fresh_output"] for p in progs.values())
        report["memory_peak_bytes"] = max(peak, rest + need)
        n = next(iter(arms.values())).trainer.plan.total_numel
        report["memory"] = {
            "parameters": int(n),
            "state_bytes": {a: state_bytes(arm) for a, arm in arms.items()},
            "at_rest_bytes": rest, "allocator_peak_bytes": peak,
            "step_program_bytes": progs,
            # what was in use as a first step began, and with its program
            "first_step_bytes": {
                a: {"in_use": held, "with_program": (
                    held + progs[a]["temp"] + progs[a]["fresh_output"])}
                for a, held in ((a, int(arm.first.get("step_held_bytes", 0)))
                                for a, arm in arms.items())},
            "window_peak_bytes": report["memory_peak_bytes"]}
    return report


def _bytes(x: int, n: float) -> str:
    return f"{x} ({x / n:.2f} B/param)"


def say_memory(device: dict) -> None:
    """The run's line on what it holds on the fullest chip, in bytes and in
    bytes a parameter: each arm's state at rest, each step program's
    temporaries and fresh outputs by XLA's analysis, and the window's peak
    (the allocator counts arrays only, so: at rest plus the largest
    program)."""
    m = device["memory"]
    n = float(m["parameters"])
    say(f"device memory, fullest chip, {m['parameters']} parameters: "
        + ", ".join(f"{a} state at rest {_bytes(v, n)}"
                    for a, v in m["state_bytes"].items())
        + f"; all at rest {_bytes(m['at_rest_bytes'], n)}, allocator "
        f"peak_bytes_in_use {_bytes(m['allocator_peak_bytes'], n)} (arrays "
        f"only); step programs by XLA's analysis "
        + ", ".join(f"{a} temp {_bytes(p['temp'], n)} fresh output "
                    f"{_bytes(p['fresh_output'], n)}"
                    for a, p in m["step_program_bytes"].items())
        + "; as a first step began "
        + ", ".join(f"{a} in use {_bytes(v['in_use'], n)} with its program "
                    f"{_bytes(v['with_program'], n)}"
                    for a, v in m["first_step_bytes"].items())
        + f"; window peak {_bytes(m['window_peak_bytes'], n)}")


def say_check_memory(device: dict) -> None:
    """The same for what the reference held on its chip during the check
    (`check.MemoryProbe.report`)."""
    m = device["memory"]
    n, c = float(m["parameters"]), m["check"]
    say(f"device memory, the check: in use when it began "
        f"{_bytes(c['at_start_bytes'], n)}, arrays at most "
        f"{_bytes(c['arrays_peak_bytes'], n)}, gradient call temp "
        f"{_bytes(c['grad_call_temp_bytes'], n)} fresh output "
        f"{_bytes(c['grad_call_fresh_output_bytes'], n)}, peak "
        f"{_bytes(c['peak_bytes'], n)}")


def close_arms(arms: Dict[str, Arm]) -> None:
    """Stop the trainers and free what they hold on the device, so that the
    reference has the chip to itself."""
    for arm in arms.values():
        arm.trainer.close()
        arm.trainer._state = None
        arm.trainer._iter = None
        arm.feed = None
        arm.trainer = None
    gc.collect()


def make_out_dir() -> str:
    base = os.path.join(ROOT, "runs")
    os.makedirs(base, exist_ok=True)
    return tempfile.mkdtemp(prefix="bench_", dir=base)


def remove_out_dir(path: str) -> None:
    shutil.rmtree(path, ignore_errors=True)
