"""Federated optimizer interface + round-loop driver.

Every algorithm implements:

  * ``init(problem, w0) -> state``          (state is a pytree dict)
  * ``round(problem, state, key, comm=None) -> state``
      (pure, jittable; one comm round — client payloads are routed
      through ``comm.uplink``, server broadcasts through
      ``comm.downlink``, and aggregation weights through
      ``comm.weights`` so codecs / partial participation perturb the
      optimization; ``comm=None`` is the exact legacy path)
  * ``uplink_floats(problem)`` / ``downlink_floats(problem)``
      static per-client-per-round communication formulas (floats), used to
      reproduce Table I empirically.

``state`` always carries the current iterate under key ``"w"``.

``run_rounds(..., comm=CommConfig(...))`` threads a simulated transport
(``repro.comm``) through every round: codecs give exact encoded bytes
in BOTH directions (uplink payloads and the server's model broadcast),
the channel model gives simulated wall-clock with compute, stragglers
and dropout, and the scheduler picks the per-round cohort. The
resulting ``History`` carries byte-accurate ``cumulative_bytes`` /
``sim_time_s`` axes next to the legacy float-count formulas.

The loop itself is mode-agnostic: ``make_session`` resolves the
``CommConfig`` (or None) to a ``Session`` — ``NullSession`` (no
transport, the exact legacy jaxpr), ``CommSession`` (synchronous
lock-step), or ``AsyncSession`` (``CommConfig(async_mode=True)``,
event-driven commits where ``sim_time_s`` becomes the server-clock axis
and ``History.staleness`` records each commit's mean model lag) — and
``run_rounds`` drives ``prepare -> step* -> finalize`` identically for
all three. The jitted round function is shared: only the host-side
clock differs.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm import CommConfig, RoundTrace, make_session
from repro.core.federated import ClientPopulation, FederatedProblem
from repro.obs import NULL_TELEMETRY, Telemetry, TelemetryConfig
from repro.obs import log as obs_log

OptState = Dict[str, Any]


def root_key(seed: int, *salts: int) -> jax.Array:
    """Mint a trajectory root PRNG key from an integer seed.

    The one sanctioned place library code turns a raw integer into key
    material (lint rule RA001, ``repro.analysis.lint``): every other
    key must derive from an existing key via ``split`` / ``fold_in``,
    or live at a documented ``(seed, id)``-salted site carrying an
    explicit ``# noqa: RA001`` suppression. Extra ``salts`` fold in
    left to right, giving disjoint deterministic streams (e.g.
    ``root_key(seed, 1)`` for an input batch next to the model init's
    ``root_key(seed)``).
    """
    key = jax.random.PRNGKey(seed)  # noqa: RA001 — the sanctioned mint site itself
    for s in salts:
        key = jax.random.fold_in(key, s)
    return key


def build_round(opt: "FederatedOptimizer", problem, session, probe_key,
                *, population=None, comm=None):
    """Build the one jitted round closure plus its abstract-probe factory.

    Shared by ``run_rounds`` and the trace auditor
    (``repro.analysis.audit``), so the jaxpr the auditor inspects IS the
    driver's jaxpr — not a reconstruction that could drift. Returns
    ``(_round, trace_with)``:

      * ``_round`` carries the dense ``(state, memory, key, mask,
        codec_key)`` signature, or the population ``(cohort, state,
        memory, key, mask, codec_key)`` one when ``population`` is
        given (``comm`` is then required for the probe cohort size);
      * ``trace_with(state)`` builds the ``trace_round`` callback the
        ``Session`` protocol's ``prepare`` / ``begin_variant`` probes
        consume (``jax.eval_shape`` only — nothing executes, so any
        ``probe_key`` works; shapes don't depend on it).

    The EF21 memory rides through as a pytree next to the optimizer
    state; without error feedback (or with only lossless codecs) it is
    an EMPTY pytree — zero extra jaxpr inputs — and on the no-transport
    path ``comm_round`` returns the no-op NULL_COMM view, so the
    identity/legacy jaxprs stay bit-identical.

    Population mode threads the materialized cohort through as a traced
    pytree argument: cohort shapes are fixed at (c, n_shard, M) by the
    scheduler's cohort size, so every round of every cohort reuses one
    jaxpr — only the data changes, never the trace.
    """
    if population is not None:
        def _round(cohort, s, mem, k, mask, ck):
            cr = session.comm_round(mem, mask, ck)
            s_next = opt.round(cohort, s, k, comm=cr)
            return s_next, cr.memory_out, cr.stats_out

        # probe cohort: ids are irrelevant (shape-only eval_shape trace)
        _probe_cohort = population.materialize(np.zeros(
            comm.scheduler.cohort_size(population.m), dtype=np.int64))

        def trace_with(s):
            return lambda cr: opt.round(_probe_cohort, s, probe_key,
                                        comm=cr)
    else:
        def _round(s, mem, k, mask, ck):
            cr = session.comm_round(mem, mask, ck)
            s_next = opt.round(problem, s, k, comm=cr)
            return s_next, cr.memory_out, cr.stats_out

        def trace_with(s):
            return lambda cr: opt.round(problem, s, probe_key, comm=cr)

    return _round, trace_with


class FederatedOptimizer:
    name: str = "base"

    def init(self, problem: FederatedProblem, w0: jax.Array) -> OptState:
        return {"w": w0}

    def round(
        self, problem: FederatedProblem, state: OptState, key: jax.Array,
        comm=None,
    ) -> OptState:
        raise NotImplementedError

    def round_signature(self, round_idx: int, state: OptState):
        """Host-side pre-round hook: return a hashable signature naming
        the static variant of the next round's trace. Rounds sharing a
        signature share one jitted round function and one payload byte
        plan; a new signature re-traces and re-bills (the signature must
        therefore determine every static choice the round makes — e.g.
        the current sketch size). Optimizers with adaptive sketch
        policies update their k here from the trajectory signals the
        driver hands back. Default: one signature (``None``) for the
        whole trajectory — the single-jaxpr fast path."""
        return None

    # -- communication accounting (per client, per round) -------------------
    def uplink_floats(self, problem: FederatedProblem) -> int:
        raise NotImplementedError

    def downlink_floats(self, problem: FederatedProblem) -> int:
        # server broadcasts the model every round for every method here
        return problem.dim


@dataclasses.dataclass
class History:
    """Per-round trajectory of one optimizer on one problem."""

    name: str
    loss: np.ndarray  # (T+1,) global loss, loss[0] at w0
    gap: np.ndarray  # (T+1,) loss - loss(w*)
    grad_norm: np.ndarray  # (T+1,)
    uplink_floats: int  # per client per round
    downlink_floats: int
    wall_time_s: float
    rounds: int
    # byte-accurate transport axes (repro.comm). Without a CommConfig the
    # bytes curve is derived from the float formulas (all clients, raw
    # dtype width) and sim time is zero.
    cumulative_bytes: Optional[np.ndarray] = None  # (T+1,) up+down, all clients
    sim_time_s: Optional[np.ndarray] = None  # (T+1,) cumulative simulated s
    traces: Optional[list] = None  # per-round RoundTrace records (comm runs)
    # async runs: (T,) mean staleness (server steps of model lag) of each
    # commit's cohort; None for sync / no-comm runs
    staleness: Optional[np.ndarray] = None
    clients: int = 1  # m — scales the per-client float formulas to totals
    itemsize: int = 8  # bytes per float of the problem dtype
    # final error-feedback memory norms per payload (comm runs with EF;
    # empty dict when EF is off or nothing was eligible)
    ef_residuals: Optional[dict] = None
    # telemetry run summary (repro.obs) when run_rounds was given an
    # ``obs=TelemetryConfig(...)``; None on uninstrumented runs
    telemetry: Optional[dict] = None

    # -- JSONL export/import -------------------------------------------------
    # One ``history`` header line with every scalar/curve field, then one
    # ``round_trace`` line per RoundTrace — so benchmark curves (and the
    # staleness axis) can be re-plotted without re-running the trajectory.

    _JSONL_SCHEMA = "repro.history/v1"

    def to_jsonl(self, path) -> pathlib.Path:
        """Write this trajectory as JSONL (see ``from_jsonl``)."""

        def arr(a):
            # strict JSON has no NaN/Infinity token: non-finite entries
            # (diverged runs, absent staleness) travel as null
            if a is None:
                return None
            return [None if (isinstance(v, float) and not np.isfinite(v))
                    else v
                    for v in np.asarray(a, dtype=np.float64).tolist()]

        header = {
            "type": "history",
            "schema": self._JSONL_SCHEMA,
            "name": self.name,
            "rounds": int(self.rounds),
            "uplink_floats": int(self.uplink_floats),
            "downlink_floats": int(self.downlink_floats),
            "wall_time_s": float(self.wall_time_s),
            "clients": int(self.clients),
            "itemsize": int(self.itemsize),
            "loss": arr(self.loss),
            "gap": arr(self.gap),
            "grad_norm": arr(self.grad_norm),
            "cumulative_bytes": arr(self.cumulative_bytes),
            "sim_time_s": arr(self.sim_time_s),
            "staleness": arr(self.staleness),
            "ef_residuals": self.ef_residuals,
            "telemetry": self.telemetry,
        }
        path = pathlib.Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        with path.open("w") as f:
            f.write(json.dumps(header, allow_nan=False) + "\n")
            for tr in self.traces or []:
                f.write(json.dumps({"type": "round_trace", **tr.to_dict()},
                                   allow_nan=False) + "\n")
        return path

    @classmethod
    def from_jsonl(cls, path) -> "History":
        """Reconstruct a ``History`` written by ``to_jsonl`` (including
        per-round ``RoundTrace`` records and the staleness axis)."""

        def arr(v):
            if v is None:
                return None
            return np.asarray([np.nan if x is None else x for x in v],
                              dtype=np.float64)

        with pathlib.Path(path).open() as f:
            lines = [json.loads(line) for line in f if line.strip()]
        if not lines or lines[0].get("type") != "history":
            raise ValueError(f"{path}: not a History JSONL (missing header)")
        h = lines[0]
        if h.get("schema") != cls._JSONL_SCHEMA:
            raise ValueError(
                f"{path}: schema {h.get('schema')!r} != "
                f"{cls._JSONL_SCHEMA!r}")
        traces = [RoundTrace.from_dict(rec) for rec in lines[1:]
                  if rec.get("type") == "round_trace"]
        return cls(
            name=h["name"],
            loss=arr(h["loss"]),
            gap=arr(h["gap"]),
            grad_norm=arr(h["grad_norm"]),
            uplink_floats=int(h["uplink_floats"]),
            downlink_floats=int(h["downlink_floats"]),
            wall_time_s=float(h["wall_time_s"]),
            rounds=int(h["rounds"]),
            cumulative_bytes=arr(h["cumulative_bytes"]),
            sim_time_s=arr(h["sim_time_s"]),
            traces=traces or None,
            staleness=arr(h["staleness"]),
            clients=int(h["clients"]),
            itemsize=int(h["itemsize"]),
            ef_residuals=h.get("ef_residuals"),
            telemetry=h.get("telemetry"),
        )

    @property
    def cumulative_uplink(self) -> np.ndarray:
        """(T+1,) cumulative uplink BYTES summed across all clients —
        the formula-derived counterpart of the uplink share of
        ``cumulative_bytes`` (same axis and units, so the two are
        directly comparable on identity-codec full-participation runs).
        """
        per_round = float(self.uplink_floats) * self.itemsize * self.clients
        return np.arange(len(self.loss)) * per_round


class _ProfilerHook:
    """Opt-in ``jax.profiler`` trace of N rounds
    (``TelemetryConfig.profile_rounds``), from the first round that does
    not compile: the trace holds steady rounds, not the compile. Host-side
    start/stop only — the traced round functions are untouched. A trace
    that was asked for and cannot start is an error, not a run without a
    trace."""

    def __init__(self, obs: "TelemetryConfig | None"):
        self._dir = obs.profile_dir if obs is not None else None
        self._wanted = max(int(obs.profile_rounds), 0) if obs else 0
        self._remaining = 0

    def before_round(self, t: int, compile_expected: bool) -> None:
        if self._wanted and not compile_expected:
            jax.profiler.start_trace(self._dir)
            self._remaining, self._wanted = self._wanted, 0
            obs_log.info("jax.profiler trace started",
                         profile_dir=self._dir, first_round=t,
                         rounds=self._remaining)

    def after_round(self) -> None:
        if self._remaining > 0:
            self._remaining -= 1
            if self._remaining == 0:
                jax.profiler.stop_trace()

    def close(self) -> None:
        """Stop a still-open trace (fewer executed rounds than asked)."""
        if self._remaining > 0:
            self._remaining = 0
            jax.profiler.stop_trace()


def run_rounds(
    opt: FederatedOptimizer,
    problem: "FederatedProblem | ClientPopulation",
    w0: jax.Array,
    w_star: jax.Array,
    rounds: int,
    seed: int = 0,
    comm: Optional[CommConfig] = None,
    obs: Optional[TelemetryConfig] = None,
    client_mesh=None,
) -> History:
    """Drive ``rounds`` communication rounds and record the trajectory.

    With ``comm=None`` this is the exact legacy path (identical jaxprs,
    bit-identical trajectories). With a ``CommConfig`` every round flows
    through the simulated transport and the returned ``History`` carries
    per-round ``RoundTrace`` records. All modes run the same loop: the
    ``Session`` protocol (``repro.comm.session``) owns the clock.

    ``problem`` may also be a ``ClientPopulation`` (population mode):
    only the scheduled cohort's shards are materialized each round, so
    the client axis scales to ``m ~ 10^5`` with memory bounded by the
    cohort size. Population mode requires a ``CommConfig`` (there is no
    dense legacy path for a population), evaluates loss/grad on the
    population's deterministic ``eval_problem()`` subsample, and rejects
    optimizers carrying dense per-client state (``per_client_state``,
    e.g. FedNew's ADMM duals — unsampled clients would silently keep
    stale duals). ``client_mesh`` optionally shards each materialized
    cohort's client axis over a device mesh
    (``repro.sharding.rules.shard_cohort``).

    ``obs=TelemetryConfig(...)`` turns on the ``repro.obs`` telemetry
    layer: host-side phase spans around the jit boundaries
    (``step`` with the session's ``session.*`` phases, ``launch`` and
    ``wait`` under it, ``eval`` — never inside traced code, and on the
    clock of any ``jax.profiler`` trace), a compile-vs-execute
    wall-clock split (the first call of each jitted round variant is
    billed as compile), session metrics (bytes, deliveries, jitted round
    launches, staleness distribution, async queue depths), and the async
    flight recorder. The default (``obs=None``) is the shared
    no-op telemetry: zero overhead and bit-identical trajectories —
    instrumentation can never perturb the optimization (tested). The
    run summary lands on ``History.telemetry``.
    """
    telemetry = Telemetry(obs) if obs is not None else NULL_TELEMETRY
    population = problem if getattr(problem, "is_population", False) else None
    if population is not None:
        if getattr(opt, "per_client_state", False):
            raise NotImplementedError(
                f"{opt.name} keeps dense per-client state across rounds "
                f"(per_client_state=True); population mode materializes "
                f"only the sampled cohort, so unsampled clients would "
                f"silently carry stale state — use a dense problem "
                f"(population.materialize_all()) or a stateless-client "
                f"optimizer")
        # loss/grad (and optimizer init geometry) come from the
        # population's deterministic evaluation subsample
        eval_prob = population.eval_problem()
    else:
        eval_prob = problem
    m = population.m if population is not None else problem.m
    loss_fn = jax.jit(eval_prob.global_value)
    grad_fn = jax.jit(eval_prob.global_grad)

    itemsize = jnp.dtype(eval_prob.X.dtype).itemsize
    loss_star = float(loss_fn(w_star))
    state = opt.init(eval_prob, w0)
    keys = jax.random.split(root_key(seed), rounds)

    formula_bytes = float(
        (opt.uplink_floats(eval_prob) + opt.downlink_floats(eval_prob))
        * itemsize * m)
    session = make_session(
        comm,
        m=m,
        mask_dtype=eval_prob.X.dtype,
        client_weights=(population.client_weights
                        if population is not None
                        else np.asarray(problem.client_weights)),
        keys=keys,
        state0=state,
        formula_bytes_per_round=formula_bytes,
        obs=telemetry,
        population=population,
        client_mesh=client_mesh,
    )

    # Adaptive-k policies change payload sizes mid-trajectory; the async
    # clock prices in-flight uploads at dispatch time, so round-varying
    # plans are a synchronous-driver feature. Fail fast with the fix.
    policy = getattr(opt, "policy", None)
    if comm is not None and comm.async_mode and policy is not None:
        if getattr(policy, "adaptive", False):
            raise NotImplementedError(
                "adaptive-k sketch policies vary payload bytes per round, "
                "which the asynchronous driver cannot bill truthfully "
                "(in-flight uploads are priced at dispatch time); use the "
                "synchronous driver or a constant-k policy")
        if (getattr(policy, "schedule", "fresh") == "rotate"
                and comm.has_error_feedback):
            # stale commit groups share one EF memory pytree across model
            # versions: a group based on the previous epoch can straddle
            # a rotation boundary and briefly compensate across bases
            # (EF21 re-contracts within the epoch). Per-version memory
            # would fix it properly — a known follow-up.
            obs_log.warn_with_context(
                "async driver + rotating sketch policy + error feedback: "
                "commit groups based on pre-rotation model versions share "
                "the EF memory of the new epoch, so residuals can briefly "
                "straddle a rotation boundary under stale commits; the "
                "synchronous driver keeps the epoch-reset invariant exact",
                optimizer=opt.name,
                policy=getattr(policy, "spec", lambda: None)())

    # The one jitted round function every driver mode shares — built by
    # ``build_round`` (also the trace auditor's entry point, so static
    # analysis inspects the exact jaxpr the driver runs).
    probe_key = root_key(seed)
    _round, trace_with = build_round(
        opt, problem, session, probe_key, population=population, comm=comm)

    with telemetry.trace.span("prepare"):
        session.prepare(trace_with(state))

    losses = [float(loss_fn(state["w"]))]
    gnorms = [float(jnp.linalg.norm(grad_fn(state["w"])))]
    # one jitted round PER static variant: the default round_signature
    # (None for every round) keeps the single shared trace; an adaptive
    # sketch policy announces each k change here, and the session probes
    # that variant's byte plan so per-round traces bill the true sizes
    round_fns: Dict[Any, Any] = {}
    retraces = telemetry.metrics.counter("variant_retraces")
    profiler = _ProfilerHook(obs)
    sig_prev = object()  # sentinel: no signature compares equal to it
    t0 = time.perf_counter()
    for t in range(rounds):
        sig = opt.round_signature(t, state)
        compile_expected = sig not in round_fns
        profiler.before_round(t, compile_expected)
        # host wall-clock attribution wraps the jit BOUNDARIES only:
        # begin_variant/step/eval run exactly the code they always ran —
        # the spans never reach inside traced functions
        with telemetry.round(t, compile_expected=compile_expected):
            if sig != sig_prev:
                with telemetry.trace.span("begin_variant"):
                    session.begin_variant(sig, trace_with(state))
                sig_prev = sig
            fn = round_fns.get(sig)
            if fn is None:
                if round_fns:  # a NEW variant after the first = a retrace
                    retraces.inc()
                fn = round_fns[sig] = jax.jit(_round)
            with telemetry.trace.span("step"):
                state = session.step(fn)
                if telemetry.enabled:
                    # honest span timing: settle async dispatch before
                    # the host timer stops (device values are unchanged)
                    with telemetry.trace.span("wait"):
                        jax.block_until_ready(state["w"])
            with telemetry.trace.span("eval"):
                losses.append(float(loss_fn(state["w"])))
                gnorms.append(float(jnp.linalg.norm(grad_fn(state["w"]))))
        profiler.after_round()
    wall = time.perf_counter() - t0
    profiler.close()

    with telemetry.trace.span("finalize"):
        transport = session.finalize()
    losses = np.asarray(losses)
    total_bytes = (float(transport.cumulative_bytes[-1])
                   if len(transport.cumulative_bytes) else 0.0)
    summary = telemetry.finalize(extra={
        "optimizer": opt.name,
        "driver": ("null" if comm is None
                   else "async" if comm.async_mode else "sync"),
        "rounds_requested": rounds,
        "clients": m,
        "total_bytes": total_bytes,
        "sim_time_s": float(transport.sim_time_s[-1])
        if len(transport.sim_time_s) else 0.0,
        "wall_time_s": wall,
    })
    return History(
        name=opt.name,
        loss=losses,
        gap=np.maximum(losses - loss_star, 0.0),
        grad_norm=np.asarray(gnorms),
        uplink_floats=opt.uplink_floats(eval_prob),
        downlink_floats=opt.downlink_floats(eval_prob),
        wall_time_s=wall,
        rounds=rounds,
        cumulative_bytes=transport.cumulative_bytes,
        sim_time_s=transport.sim_time_s,
        traces=transport.traces,
        staleness=transport.staleness,
        clients=m,
        itemsize=itemsize,
        ef_residuals=transport.ef_residuals,
        telemetry=summary,
    )
