"""One run of one benchmark cell: set-up, a timed window, and the check.

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration and a traffic mix; both are data files found by name:

* ``configs/<config>.json``: the dataset twin's sizes (rows, features,
  clients and sketch size), its spectrum and label noise, the ridge
  weight and the seed of the fixed dataset;
* ``workloads/<traffic>.json``: the driver (sync or async), codecs,
  channel, the round rate the window is sized for, and the round counts
  of the traced call and the comparison;
* ``limits/<cell>.json``: the limit of each number ``correct`` is
  decided on;
* ``metrics/<metric>.py``: one reader per per-layer metric.

A configuration from outside the paper's Table II needs no code: a
``configs/<config>.json`` that names its source and states
``published``, ``published_sizes`` (the source's numbers), ``assumed``
(every other number the harness reads, with its reason) and
``reduced`` (each number cut below the published one), a traffic file
where no existing one fits, ``limits/<cell>.json``, and the entries of
``BENCHMARK.json``: the configuration, the cell, and the cell's name in
the ``workloads`` of each per-layer metric it reports.

The timed path is the program's public API as its users drive it:
``make_problem``, ``newton_solve``, ``make_optimizer``, ``CommConfig``
and ``run_rounds`` with ``obs=TelemetryConfig(...)`` (host spans, round
records to a JSONL file), float32, x64 off.

A run makes one timed ``run_rounds`` call of a fixed number of rounds
(``window_rounds``), after a short warm-up call where the mix asks for
one. The timed call's first round compiles and belongs to set-up; the
window is the rest of the call's round loop, closed loop (each round
starts when the one before it ended). With ``trace`` a short call
compiles first and a second one runs under ``jax.profiler``; the result
then carries the per-layer metrics.

``correct``: the reference (``reference.py``) follows the call's first
``compare_steps`` rounds on the schedule the configuration asks for;
every round's billed wire bytes and who delivered in it are compared
with that schedule; see ``check``. The call runs under the first seed
from ``--seed`` on whose compared rounds the sketch has full rank
(``run_seed``), which the result line reports.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import os
import pathlib
import statistics
import sys
import time

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent.parent


class NoChip(RuntimeError):
    """No TPU, too few chips, or a device kind without peaks."""


# ---------------------------------------------------------------------------
# the cell's files
# ---------------------------------------------------------------------------

def _read_json(path: pathlib.Path) -> dict:
    with path.open() as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    per_layer: list  # the manifest's per-layer entries this cell reports
    end_to_end: list


def load_cell(name: str, *, sizes: "dict | None" = None) -> Cell:
    """The cell ``name`` of the checkout's ``BENCHMARK.json`` with its
    files.
    ``sizes`` (tests only) overrides numbers of the configuration and the
    traffic mix, to run the same path at a size a CPU can hold."""
    manifest = _read_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{', '.join(sorted(cells))}")
    w = cells[name]
    config = _read_json(HERE / "configs" / f"{w['config']}.json")
    traffic = _read_json(HERE / "workloads" / f"{w['traffic']}.json")
    limits = _read_json(HERE / "limits" / f"{name}.json")
    for key, value in (sizes or {}).items():
        for table in (config, traffic):
            if key in table:
                table[key] = value
    per_layer = [m for m in manifest["per_layer"]
                 if name in m.get("workloads", [name])]
    end_to_end = [m for m in manifest["end_to_end"]
                  if name in m.get("workloads", [name])]
    return Cell(name, int(w["chips"]), config, traffic, limits, per_layer,
                end_to_end)


def load_reader(metric: str):
    """The ``read(run)`` function of ``metrics/<metric>.py``."""
    path = HERE / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks_for(kind: str) -> dict:
    table = _read_json(HERE / "peaks.json")["devices"]
    if kind not in table:
        raise NoChip(f"device kind {kind!r} is not in peaks.json "
                     f"(have {', '.join(sorted(table))})")
    return table[kind]


def find_chips(chips: int) -> "tuple[list, dict]":
    """The TPU devices JAX found and their peaks; ``NoChip`` where there
    is no TPU, fewer than ``chips`` of them, or a kind without peaks."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"needs {chips} chips, JAX found {len(devices)}")
    return devices, peaks_for(devices[0].device_kind)


# ---------------------------------------------------------------------------
# the system under test, driven through its public API
# ---------------------------------------------------------------------------

def _compile_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where it is set, else a fixed directory inside the checkout. Every
    program is kept whatever its size: the program compiles a dense
    problem into its executables as a constant (about 2 GB each at
    SUSY's size), and a size limit on the cache would have every run
    compile them again."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return path


class CompileClock:
    """Host times at which a program finished compiling or loading from
    the cache (JAX's backend-compile event), while the clock is open."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.ends: "list[float]" = []

    def _on(self, event, duration, **kwargs):
        if event == self.EVENT:
            self.ends.append(time.perf_counter())

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on)
        return False

    def between(self, t0: float, t1: float) -> int:
        return sum(t0 < t <= t1 for t in self.ends)


@dataclasses.dataclass
class System:
    """What set-up built: the problem as the program holds it and the
    pieces of each ``run_rounds`` call."""

    problem: object
    w0: object
    w_star: object
    comm_kwargs: dict
    k: int


def data_key(config: dict):
    import jax

    return jax.random.PRNGKey(int(config["data_seed"]))


def partition_key(config: dict):
    import jax

    return jax.random.fold_in(data_key(config), 1)


def make_data(config: dict):
    """The configuration's dataset twin, made on the device in one call."""
    import jax.numpy as jnp

    from benchmarks.chip import twin

    return twin.make_classification(
        data_key(config), int(config["n"]), int(config["dim"]),
        spectrum_decay=float(config["spectrum_decay"]),
        label_noise=float(config["label_noise"]), dtype=jnp.float32)


def buffer_size(config: dict, traffic: dict) -> int:
    return max(1, int(config["m_clients"] * float(traffic["buffer_share"])))


def build_system(cell: Cell, X, y) -> System:
    import jax.numpy as jnp

    from benchmarks.chip import twin
    from repro.comm import ChannelModel
    from repro.core import make_problem, newton_solve
    from repro.core.losses import logistic

    cfg, tr = cell.config, cell.traffic
    m = int(cfg["m_clients"])
    problem = make_problem(X, y, m=m, lam=float(cfg["lam"]),
                           objective=logistic, key=partition_key(cfg),
                           heterogeneity="iid")
    w0 = jnp.zeros((int(cfg["dim"]),), jnp.float32)
    w_star = newton_solve(problem, w0)
    comm = dict(codecs=tr["codecs"], scheduler="full")
    if tr.get("channel") == "straggler_edge":
        comm["channel"] = ChannelModel(**twin.straggler_edge_channel(m))
    if tr["driver"] == "async":
        comm.update(async_mode=True, buffer_size=buffer_size(cfg, tr),
                    staleness=tr["staleness"])
    return System(problem, w0, w_star, comm, int(cfg["sketch_k"]))


def run_call(system: System, rounds: int, seed: int,
             records: pathlib.Path):
    """One ``run_rounds`` call as the program's users make it, its round
    records written by the telemetry's JSONL sink to ``records``.
    Returns the ``History`` and the host time the call returned."""
    from repro.comm import CommConfig
    from repro.core import make_optimizer, run_rounds
    from repro.obs import TelemetryConfig

    records.parent.mkdir(parents=True, exist_ok=True)
    records.unlink(missing_ok=True)
    hist = run_rounds(make_optimizer("flens", k=system.k), system.problem,
                      system.w0, system.w_star, rounds=rounds, seed=seed,
                      comm=CommConfig(seed=seed, **system.comm_kwargs),
                      obs=TelemetryConfig(sink=f"jsonl:{records}"))
    return hist, time.perf_counter()


# ---------------------------------------------------------------------------
# what a call produced
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Call:
    """A finished ``run_rounds`` call, as host numbers."""

    rounds: list  # telemetry round records: wall_s, compile, phases
    loss: np.ndarray
    grad_norm: np.ndarray
    bytes: np.ndarray  # (T,) billed wire bytes per round, up and down
    delivered: np.ndarray  # (T, m) who delivered
    scheduled: np.ndarray  # (T,) clients sent the model
    staleness: "np.ndarray | None"  # async: (T, m), NaN off the commit
    wall_time_s: float
    finalize_s: float
    t_return: float

    @property
    def first_steady(self) -> int:
        """Index of the first round after the last compile round."""
        flags = [r["compile"] for r in self.rounds]
        last = max((i for i, c in enumerate(flags) if c), default=-1)
        return last + 1

    def window(self) -> "tuple[float, float]":
        """Host start and end of the call's steady rounds."""
        loop_end = self.t_return - self.finalize_s
        before = sum(r["wall_s"] for r in self.rounds[:self.first_steady])
        return loop_end - self.wall_time_s + before, loop_end


def read_call(hist, t_return: float, records: pathlib.Path) -> Call:
    with records.open() as f:
        rounds = [r for r in map(json.loads, f) if r.get("type") == "round"]
    traces = hist.traces or []
    return Call(
        rounds=rounds,
        loss=np.asarray(hist.loss, dtype=np.float64),
        grad_norm=np.asarray(hist.grad_norm, dtype=np.float64),
        bytes=np.asarray([t.total_bytes for t in traces], dtype=np.float64),
        delivered=np.asarray([t.delivered for t in traces], dtype=bool),
        scheduled=np.asarray([int(np.sum(t.scheduled)) for t in traces]),
        staleness=(np.asarray([t.staleness for t in traces],
                              dtype=np.float64)
                   if traces and traces[0].staleness is not None else None),
        wall_time_s=float(hist.wall_time_s),
        finalize_s=float(hist.telemetry["setup_phase_s"].get("finalize",
                                                             0.0)),
        t_return=t_return,
    )


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------

def schedule(cell: Cell, call: Call, seed: int):
    """The schedule the configuration asks of this call, and the number
    of its commits that break the configuration's rules (async)."""
    from benchmarks.chip import reference

    cfg, tr = cell.config, cell.traffic
    if tr["driver"] == "async":
        return reference.async_schedule(seed, call.delivered,
                                        buffer_size(cfg, tr),
                                        tr["staleness"])
    return reference.sync_schedule(seed, len(call.rounds),
                                   int(cfg["m_clients"])), 0


def reference_partition(cell: Cell, X, y):
    from benchmarks.chip import reference

    cfg = cell.config
    return reference.partition(X, y, int(cfg["m_clients"]),
                               partition_key(cfg))


def check(cell: Cell, call: Call, X, y, seed: int, dtype=None,
          fault: "str | None" = None, per_round: bool = False) -> dict:
    """The numbers ``correct`` is decided on (see ``reference.compare``
    and ``reference.schedule_gap``), from the reference following this
    call; ``dtype``/``fault`` put the control or a planted fault in the
    program's place. ``per_round`` adds each compared round's loss and
    gradient gaps (``reference.round_gaps``) under ``rounds``."""
    import jax.numpy as jnp

    from benchmarks.chip import reference

    cfg, tr = cell.config, cell.traffic
    codecs = tr["codecs"]
    codecs = {"default": codecs} if isinstance(codecs, str) else dict(codecs)
    steps = int(tr["compare_steps"])
    follow = dict(steps=steps, k=int(cfg["sketch_k"]), lam=float(cfg["lam"]),
                  codecs=codecs)
    part = reference_partition(cell, X, y)
    sched, broken = schedule(cell, call, seed)
    ref = reference.follow(part, sched, **follow)
    if dtype is None and fault is None:
        program = {"loss": call.loss, "grad_norm": call.grad_norm}
    else:
        program = reference.follow(part, sched, dtype=dtype or jnp.float32,
                                   fault=fault, **follow)
    program["bytes"] = call.bytes
    want = reference.expected_bytes(sched, dim=int(cfg["dim"]),
                                    k=int(cfg["sketch_k"]), codecs=codecs)
    numbers = reference.compare(program, ref, want, steps)
    numbers["schedule_gap"] = broken + reference.schedule_gap(
        sched, call.delivered, call.scheduled, call.staleness)
    if per_round:
        losses, grads = reference.round_gaps(program, ref, steps)
        numbers["rounds"] = {"loss_gap": losses.tolist(),
                             "grad_gap": grads.tolist()}
    return numbers


def run_seed(cell: Cell, seed: int, rounds: int) -> int:
    """The seed a run drives the program with: ``seed`` modulo 2**32, or
    the first after it whose compared rounds each draw a sketch of full
    rank, min(``sketch_k``, ``dim``). Where ``dim`` is below its power
    of two n, the SRHT's k rows of H_n can agree on the first ``dim``
    columns (SUSY: a quarter of rounds); the server's damped solve of
    such a sketch takes a step that rounding sets, and the float32
    reference with each client's rows reversed disagrees with itself by
    as much as the bfloat16 control. So every run compares work of one
    difficulty; the rounds after the compared ones draw their sketches
    as they come. ``rounds`` is the call's round count, which splits
    the run's key. The draws run on the host's CPU where JAX has one, so
    that no program of theirs counts in the chip's peak memory."""
    import jax
    import jax.numpy as jnp

    from benchmarks.chip import reference

    k, dim = int(cell.config["sketch_k"]), int(cell.config["dim"])
    steps = int(cell.traffic["compare_steps"])
    try:
        host = jax.local_devices(backend="cpu")[0]
    except RuntimeError:
        host = None
    s = int(seed) % (1 << 32)
    with jax.default_device(host):
        while True:
            keys = jax.random.split(jax.random.PRNGKey(s), rounds)
            ranks = [np.linalg.matrix_rank(np.asarray(reference.srht_matrix(
                keys[t], k, dim, jnp.float32), np.float64))
                for t in range(steps)]
            if min(ranks) == min(k, dim):
                return s
            s = (s + 1) % (1 << 32)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

KERNELS = ("srht",)
ROUND_MODULE = "jit__round"


@dataclasses.dataclass
class TracedRun:
    """What a per-layer metric's ``read(run)`` gets."""

    rounds: list  # telemetry records of the call's steady rounds
    reduction: object  # xplane.Reduction of the traced call
    shapes: dict  # rows, clients, dim, k, eval_rows of one round
    peaks: dict


def round_shapes(cell: Cell) -> dict:
    """Rows, clients, width and sketch size of one round (the real rows;
    the driver's evaluation runs over all of them)."""
    cfg = cell.config
    return {"rows": float(cfg["n"]), "clients": float(cfg["m_clients"]),
            "dim": int(cfg["dim"]), "k": int(cfg["sketch_k"]),
            "eval_rows": float(cfg["n"])}


def window_rounds(traffic: dict, seconds: float) -> int:
    """Rounds of the timed call: the compile round, then as many steady
    rounds as ``seconds`` holds at the mix's stated round rate (a fixed
    amount of work for a given length), and never fewer than the
    comparison needs."""
    steady = math.ceil(seconds * float(traffic["rounds_per_s"]))
    return 1 + max(int(traffic["compare_steps"]) + 1, steady)


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: "float | None" = None,
             sizes: "dict | None" = None,
             out: "pathlib.Path | None" = None) -> dict:
    """One run of cell ``name``; returns the result line as a dict.
    Round records and traces go under ``out`` (``.bench_out`` of the
    checkout). ``sizes`` is for the tests only (see ``load_cell``).

    Without ``trace`` set-up ends where the timed call's compile round
    ends, and the window is the rest of that one call; a mix that states
    ``warmup_rounds`` runs that many in a call of its own first. With
    ``trace`` a short call first compiles every program, and a second
    call of ``trace_rounds`` runs under ``jax.profiler``."""
    t_start = time.perf_counter() if t_start is None else t_start
    import jax

    jax.config.update("jax_enable_x64", False)
    cell = load_cell(name, sizes=sizes)
    devices, peaks = find_chips(cell.chips)
    _compile_cache()
    dev = devices[0]
    out = ROOT / ".bench_out" if out is None else out
    records = out / f"{name}.rounds.jsonl"
    tr = cell.traffic
    rounds = int(tr["trace_rounds"]) if trace else window_rounds(tr, seconds)
    seed_run = run_seed(cell, seed, rounds)

    X, y = make_data(cell.config)
    system = build_system(cell, X, y)
    trace_dir = out / "trace" / name
    warmup = int(tr["warmup_rounds"])
    if warmup or trace:
        # an asynchronous mix's first commits of stale groups compile the
        # driver's eager delta arithmetic: a short call runs them first
        run_call(system, max(warmup, int(tr["compare_steps"]) + 1),
                 seed_run, records)
    if trace:
        import shutil

        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    try:
        with CompileClock() as clock:
            hist, t_ret = run_call(system, rounds, seed_run, records)
    finally:
        if trace:
            jax.profiler.stop_trace()
    call = read_call(hist, t_ret, records)
    w_start, w_end = call.window()
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    compiled = clock.between(w_start, w_end)
    del hist, system
    gc.collect()

    window = call.rounds[call.first_steady:]
    ts = [r["round"] for r in window]
    finite = np.isfinite(call.loss) & np.isfinite(call.grad_norm)
    failed = int(sum(not finite[t + 1] for t in ts))
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": None, "attempted": len(ts), "failed": failed}
    if trace:
        from benchmarks.chip import xplane

        red = xplane.reduce(xplane.newest_xplane(str(trace_dir)),
                            ROUND_MODULE, KERNELS)
        run = TracedRun(window, red, round_shapes(cell), peaks)
        metrics = {}
        for m in cell.per_layer:
            value = load_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        result["breakdown"] = {"device_ops": red.device_ops,
                               "idle_gaps": red.idle_gaps}
    else:
        updates = int(call.delivered[ts].sum())
        walls = np.asarray([r["wall_s"] for r in window])
        values = {
            "setup_s": w_start - t_start,
            "client_updates_per_s": updates / (w_end - w_start),
            "round_ms_p90": float(np.percentile(walls, 90)) * 1e3,
            "peak_mem_gb": (peak or 0) / 1e9,
        }
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    result["metrics"] = metrics
    result["device"] = device
    result["compiled_in_window"] = compiled

    jax.clear_caches()
    gc.collect()
    numbers = check(cell, call, X, y, seed_run)
    checks = {k: {"value": v, "limit": cell.limits[k]}
              for k, v in numbers.items()}
    result["correct"] = bool(failed == 0 and all(
        c["value"] <= c["limit"] for c in checks.values()))
    result["run_seed"] = seed_run
    result["checks"] = checks
    return result


def emit(result: dict) -> None:
    """Standard output: the count of compiles in the window, then the
    result line. Standard error closes with each compared number beside
    its limit."""
    print(f"compiled_in_window={result['compiled_in_window']}", flush=True)
    for k, c in result["checks"].items():
        print(f"check {k}={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
