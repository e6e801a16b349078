"""The ``Session`` driver protocol: one round loop, three clocks.

``run_rounds`` used to special-case its three modes (no transport,
synchronous transport, asynchronous transport) with an isinstance
ladder. Instead, every mode now implements one small protocol and the
driver is a single protocol-driven loop:

  * ``prepare(trace_round)`` — trace-time discovery before the first
      round executes: the async driver probes the payload byte plan
      (its clock needs encoded sizes up front) and launches the initial
      cohort; the sync driver probes EF memory shapes when error
      feedback is on; the null session does nothing.
  * ``begin_variant(sig, trace_round)`` — announce the static round
      variant about to execute (adaptive-k sketch policies change
      payload sizes mid-trajectory; ``sig`` comes from
      ``FederatedOptimizer.round_signature``). Sessions probe each new
      variant's payload byte plan once (``jax.eval_shape`` — nothing
      executes) and install it, so per-round accounting bills the true
      round-varying sizes: the null session derives its formula bytes
      from an identity-codec plan, the sync session swaps its live plan
      per variant, and the async session rejects mid-run variant
      changes (its clock prices in-flight uploads at dispatch time).
  * ``comm_round(memory, mask, codec_key)`` — build the in-jit
      transport view the optimizer's round receives (``CommRound``, or
      the no-op ``NULL_COMM`` on the no-transport path). Called at
      trace time by the driver's uniform round builder.
  * ``step(round_fn)`` — advance one server round/commit and return the
      new optimizer state. ``round_fn(state, memory, key, mask,
      codec_key) -> (state, memory)`` is the one jitted round function
      shared by every mode.
  * ``finalize() -> Transport`` — the transport axes (cumulative bytes,
      simulated time, traces, staleness, EF residuals) for ``History``.

Sessions own the host-side trajectory state (optimizer state between
rounds, per-round keys, EF memory, clocks); the jitted round function
stays pure. Adding a fourth driver mode means implementing this
protocol — not deepening a branch in ``run_rounds``.
"""
from __future__ import annotations

from typing import Any, Optional

import numpy as np

from repro.comm.async_driver import AsyncSession, PopulationAsyncSession
from repro.comm.config import (
    NULL_COMM,
    CommConfig,
    CommSession,
    PopulationCommSession,
    count_round_calls,
    plan_bytes,
    probe_round,
)
from repro.comm.metrics import Transport
from repro.obs import NULL_TELEMETRY
from repro.obs import log as obs_log


class Session:
    """Protocol base for round drivers (see module docstring)."""

    def prepare(self, trace_round) -> None:
        raise NotImplementedError

    def begin_variant(self, sig, trace_round) -> None:
        raise NotImplementedError

    def comm_round(self, memory, mask, codec_key):
        raise NotImplementedError

    def step(self, round_fn) -> Any:
        raise NotImplementedError

    def finalize(self) -> Transport:
        raise NotImplementedError


class NullSession(Session):
    """No-transport driver: rounds execute back to back with the no-op
    ``NULL_COMM`` view — the exact legacy jaxpr. The byte axis is
    derived from an identity-codec probe of the round's payload plan
    (the measured wire: every payload occurrence at its raw encoded
    size, both directions), falling back to the per-optimizer
    float-count formulas when no probe context is available; adaptive-k
    variants re-probe, so the formula axis is round-varying too."""

    def __init__(self, keys, state0, formula_bytes_per_round: float,
                 m: "int | None" = None, mask_dtype=None,
                 obs=NULL_TELEMETRY):
        self.keys = keys
        self._state = state0
        self._formula = float(formula_bytes_per_round)
        self.m = m
        self._mask_dtype = mask_dtype
        self._plans: dict = {}
        self._per_round: "list[float]" = []
        self._t = 0
        self.obs = obs

    def prepare(self, trace_round) -> None:
        pass

    def begin_variant(self, sig, trace_round) -> None:
        if self.m is None:
            return  # no probe context: keep the float-formula fallback
        if sig not in self._plans:
            plan: dict = {}
            try:
                with self.obs.trace.span("probe_plan"):
                    probe_round(CommConfig(), self.m, self._mask_dtype, plan,
                                trace_round, full_cohort=True)
            except Exception as e:  # un-traceable round: formula fallback
                plan = None
                obs_log.warn_with_context(
                    f"payload-plan probe failed ({e!r}); the no-comm byte "
                    f"axis falls back to the per-optimizer float-count "
                    f"formulas for this run (these can undercount the "
                    f"wire)", round=self._t, variant=sig)
            self._plans[sig] = plan
        plan = self._plans[sig]
        if plan is not None:
            per_client = (plan_bytes(plan, down=False)
                          + plan_bytes(plan, down=True))
            self._formula = float(per_client * self.m)

    def comm_round(self, memory, mask, codec_key):
        return NULL_COMM

    def step(self, round_fn) -> Any:
        with self.obs.trace.span("launch"):
            self._state, _, _ = round_fn(self._state, {},
                                         self.keys[self._t], None, None)
        self._per_round.append(self._formula)
        self._t += 1
        count_round_calls(self.obs, 1)
        return self._state

    def finalize(self) -> Transport:
        per_round = np.asarray(self._per_round, dtype=np.float64)
        return Transport(
            cumulative_bytes=np.concatenate([[0.0], np.cumsum(per_round)]),
            sim_time_s=np.zeros(self._t + 1),
        )


def make_session(
    comm: Optional[CommConfig],
    *,
    m: int,
    mask_dtype,
    client_weights: np.ndarray,
    keys,
    state0,
    formula_bytes_per_round: float,
    obs=NULL_TELEMETRY,
    population=None,
    client_mesh=None,
) -> Session:
    """Resolve a ``CommConfig`` (or None) to its driver session — the
    single place mode dispatch happens. ``obs`` is the live telemetry
    runtime (``repro.obs.Telemetry``) or the shared no-op.

    ``population`` (a ``repro.core.federated.ClientPopulation``) selects
    the lazy cohort-materialization drivers; it requires a transport
    (``comm`` must not be None — a population has no dense legacy path
    to fall back to). ``client_mesh`` optionally shards each
    materialized cohort's client axis over a device mesh
    (``repro.sharding.rules.shard_cohort``).
    """
    if population is not None:
        if comm is None:
            raise ValueError(
                "population-mode runs need a CommConfig: pass "
                "run_rounds(..., comm=CommConfig(scheduler='uniform:q')) "
                "(materializing all clients of a population is exactly "
                "what populations exist to avoid — use "
                "population.materialize_all() explicitly if you really "
                "want the dense problem)")
        if comm.async_mode:
            return PopulationAsyncSession(
                comm, population, keys=keys, state0=state0,
                mask_dtype=mask_dtype, obs=obs, client_mesh=client_mesh)
        return PopulationCommSession(
            comm, population, mask_dtype=mask_dtype, keys=keys,
            state0=state0, obs=obs, client_mesh=client_mesh)
    if comm is None:
        return NullSession(keys, state0, formula_bytes_per_round,
                           m=m, mask_dtype=mask_dtype, obs=obs)
    if comm.async_mode:
        return AsyncSession(comm, m=m, client_weights=client_weights,
                            keys=keys, state0=state0, mask_dtype=mask_dtype,
                            obs=obs)
    return CommSession(comm, m=m, mask_dtype=mask_dtype, keys=keys,
                       state0=state0, obs=obs)
