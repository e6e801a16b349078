"""Event-driven asynchronous federated round driver.

The synchronous driver (``CommSession``) makes the server wait for the
slowest delivering client every round, so a single straggler inflates
``sim_time_s`` for everyone — exactly the device-heterogeneity problem
FedNL (Safaryan et al., 2021) and FLECS (Agafonov et al., 2022) motivate
second-order FL with. This module replaces the lock-step clock with an
event simulation built on the per-client delivery times the channel
model already produces (``ChannelModel.client_times``):

  * every client runs its own download -> compute -> upload cycle on a
    persistent clock, computing on the model *version it last received*;
  * uploads arrive at the server when the client's simulated link
    finishes; dropped uploads trigger a deterministic re-dispatch (the
    client re-fetches the current model and retries);
  * the server commits an aggregation step as soon as a quorum of
    uploads has buffered — a FedBuff-style buffer of ``K = buffer_size``
    arrivals, or ``ceil(async_quantile * m)`` when no buffer size is
    set — instead of waiting for the full cohort;
  * contributions based on version ``v`` at server version ``t`` carry
    staleness ``tau = t - v`` and are weighted by a pluggable staleness
    rule (``constant``, ``inverse`` = 1/(1+tau), ``poly:a`` =
    (1+tau)^-a) on top of the existing participation weights.

Aggregation semantics
---------------------
Buffered arrivals are grouped by base model version. Each group re-runs
the optimizer's (jitted) round from the snapshot of its base version
with the group's delivery mask — so partial cohorts perturb the
optimization through the exact machinery the sync driver uses
(``CommRound.weights`` / ``where_delivered``) — and contributes the
model *delta* it would have produced. The server combines deltas:

    w_{t+1} = w_t + eta_s * sum_g c_g (w'_g - w_{v_g}),
    c_g  =  staleness(tau_g) * P_g / sum_h P_h

(P_g = group participation mass, eta_s = ``CommConfig.server_lr`` — the
FedBuff-style global server learning rate, 1.0 by default and then
bit-identical to not having the knob). Participation is renormalized over the
commit — the same renormalization the sync driver applies to partial
cohorts — while the staleness factor *damps* the applied step, so a
fully-stale commit under ``inverse`` moves the model by 1/(1+tau) of its
delta instead of being silently renormalized back to a full step.

Auxiliary optimizer state (momentum, guards, duals) advances along the
*freshest* group's round; stale groups contribute model deltas only.
When a commit consists of a single group based on the current version
(always the case in lock-step-equivalent configs), the combined state
IS that round's output — no delta arithmetic — which is what makes the
``async_quantile=1.0`` / full-participation path bit-identical to the
synchronous driver: same key schedule, same jaxpr, same floats.

Error-feedback memory (``repro.comm.feedback``) is threaded through
every group round and gated by that group's delivery mask, so memory
rows advance exactly when a client's payload is actually consumed by a
server commit — delivery-keyed updates that now span server steps.

Determinism: channel randomness for the cohort dispatched after commit
``t`` comes from the same ``(seed, t)`` key schedule the sync driver
uses; retries after a dropped upload fold the retry count in. A
trajectory is exactly reproducible from ``CommConfig.seed``.
"""
from __future__ import annotations

import dataclasses
import heapq
import math
from collections import defaultdict
from typing import Any, Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm import feedback
from repro.comm.metrics import RoundTrace
from repro.obs import NULL_TELEMETRY
from repro.obs import log as obs_log
from repro.sharding.rules import client_mesh_scope

# a dropped upload is retried with fresh channel coins; after this many
# consecutive drops the delivery is forced so the simulation cannot spin
# forever under dropout_prob -> 1.0
MAX_RETRIES = 8

# begin_variant sentinel: "no variant announced yet" (None is a valid
# round signature — the default single-trace trajectory)
_NO_VARIANT = object()


def make_staleness(spec: "str | Callable[[float], float]"):
    """Resolve a staleness-weighting spec to a ``tau -> weight`` callable.

    ``"constant"`` — every contribution weighs 1 regardless of lag;
    ``"inverse"`` — 1/(1+tau), the FedAsync polynomial special case;
    ``"poly:a"`` — (1+tau)^-a (``a`` defaults to 0.5).
    A callable is passed through unchanged.
    """
    if callable(spec):
        return spec
    if spec == "constant":
        return lambda tau: 1.0
    if spec == "inverse":
        return lambda tau: 1.0 / (1.0 + tau)
    kind, _, arg = str(spec).partition(":")
    if kind in ("poly", "polynomial"):
        a = float(arg or 0.5)
        return lambda tau: (1.0 + tau) ** (-a)
    raise ValueError(
        f"unknown staleness spec {spec!r}; want 'constant', 'inverse', "
        f"'poly:<a>', or a callable")


@dataclasses.dataclass
class _Flight:
    """One client upload cycle in the air."""

    client: int
    version: int  # model version the client computed on
    straggler: bool
    dropped: bool  # upload lost in transit: re-dispatch on landing
    retry: int = 0


class AsyncSession:
    """Host-side event-driven driver state for one trajectory.

    Owns the per-client clocks, the arrival event heap, the server
    buffer, per-version state snapshots, the EF memory pytree, and the
    per-commit ``RoundTrace`` records. The jitted round function is
    injected per step so the session stays optimizer-agnostic — it has
    the same ``(state, memory, key, mask, codec_key)`` signature the
    synchronous driver jits.
    """

    def __init__(
        self,
        config,
        m: int,
        client_weights: np.ndarray,
        keys: jax.Array,  # (rounds, 2) per-version optimizer round keys
        state0: Any = None,
        mask_dtype=jnp.float64,  # noqa: RA005 — caller passes the problem dtype; the default only names the widest mask the goldens were recorded with
        obs=NULL_TELEMETRY,
    ):
        self.config = config
        self.m = m
        self.obs = obs
        self.client_weights = np.asarray(client_weights, dtype=np.float64)
        self.keys = keys
        self._state0 = state0
        self.plan: Dict[str, int] = {}
        self.traces: List[RoundTrace] = []
        self.ef_memory: Dict[str, jax.Array] = {}
        self._mask_dtype = mask_dtype
        self._root = jax.random.PRNGKey(config.seed)  # noqa: RA001 — the transport root stream; repro.comm cannot import repro.core.base (cycle)
        self._staleness = make_staleness(config.staleness)
        if config.buffer_size is not None:
            self.quorum = min(m, int(config.buffer_size))
        else:
            self.quorum = max(1, min(m, int(math.ceil(
                config.async_quantile * m))))
        # lock-step-equivalent: full scheduler, no dropout, full quorum.
        # Every commit then aggregates exactly the fresh full cohort, so
        # the round runs with mask=None — the identical jaxpr (and key
        # schedule) the sync driver uses, hence bit-identical. Churn and
        # correlated outages (dynamics.forces_mask) break the static
        # full-cohort guarantee, so they force the masked path.
        dyn = config.dynamics
        self.lockstep = (config.scheduler.is_full
                         and config.channel.dropout_prob == 0.0
                         and self.quorum == m
                         and (dyn is None or not dyn.forces_mask))
        # dynamics bookkeeping (inert when dynamics is None)
        self._elig_prev = None
        self._attacker_arr = None
        self.robust_stats: Dict[str, float] = {}

        self.version = 0
        self.server_clock = 0.0
        self._snapshots: Dict[int, Any] = {}
        self._heap: list = []  # (time, seq, _Flight)
        self._seq = 0
        self._buffer: List[tuple] = []  # (client, version, straggler, t_arr)
        self._idle: set = set()
        self._quorum_capped = False
        self._pending_down = np.zeros(m, dtype=np.float64)
        self._pending_dropped = np.zeros(m, dtype=bool)
        self._variant_sig: Any = _NO_VARIANT

    # -- key schedule (matches CommSession.begin_round exactly) -------------
    def _round_keys(self, version: int):
        k = jax.random.fold_in(self._root, version)
        return jax.random.split(k, 3)  # k_sched, k_chan, k_codec

    @property
    def bytes_up_per_client(self) -> int:
        from repro.comm.config import plan_bytes

        return plan_bytes(self.plan, down=False)

    @property
    def bytes_down_per_client(self) -> int:
        """Exact encoded broadcast bytes per dispatched client (the
        ``down:*`` plan entries the prepare-time probe filled)."""
        from repro.comm.config import plan_bytes

        return plan_bytes(self.plan, down=True)

    # -- Session protocol: trace-time discovery -----------------------------
    def prepare(self, trace_round) -> None:
        """One abstract probe of the round (nothing executes): fills the
        payload byte plan — the async clock needs encoded bytes in BOTH
        directions *before* the first round runs, unlike the sync driver
        which reads them after — discovers the EF memory shapes along
        the way, then snapshots the initial state and launches every
        client's first cycle."""
        from repro.comm.config import probe_round

        spec = probe_round(self.config, self.m, self._mask_dtype, self.plan,
                           trace_round, full_cohort=self.lockstep)
        self.ef_memory = feedback.init_memory(spec)
        if self._state0 is not None:
            self.start(self._state0)

    def begin_variant(self, sig, trace_round) -> None:
        """The async clock prices in-flight uploads at dispatch time, so
        the payload plan must stay constant for the whole trajectory:
        the first announced variant is accepted (its plan was already
        probed by ``prepare``), any later change — an adaptive-k policy
        resizing payloads mid-run — is rejected."""
        if self._variant_sig is _NO_VARIANT:
            self._variant_sig = sig
        elif sig != self._variant_sig:
            raise NotImplementedError(
                "round-varying payload plans (adaptive-k sketch policies) "
                "are not supported by the asynchronous driver: uploads "
                "already in flight were priced at dispatch time; use the "
                "synchronous driver")

    def comm_round(self, memory, mask, codec_key):
        """In-jit transport view for the driver's round builder."""
        from repro.comm.config import CommRound

        return CommRound(self.config, self.plan, mask, codec_key,
                         memory=memory)

    def finalize(self):
        from repro.comm.metrics import transport_from_traces

        if self.obs.enabled:
            ef_bytes = sum(
                int(np.prod(a.shape)) * jnp.dtype(a.dtype).itemsize
                for a in jax.tree_util.tree_leaves(self.ef_memory))
            self.obs.metrics.gauge("ef_memory_bytes").set(float(ef_bytes))
        return transport_from_traces(
            self.traces,
            staleness=np.array([tr.mean_staleness for tr in self.traces]),
            ef_residuals=self.ef_residual_norms(),
        )

    # -- event machinery ----------------------------------------------------
    def start(self, state) -> None:
        """Snapshot the initial model and put every client in the air."""
        self._snapshots[0] = state
        self._dispatch_cohort(range(self.m), now=0.0)

    def _dispatch_cohort(self, clients, now: float) -> None:
        """Send the current model to ``clients`` that the scheduler picks
        this version; the rest idle until the next commit."""
        from repro.comm.config import apply_churn

        clients = list(clients)
        if not clients:
            return
        k_sched, k_chan, _ = self._round_keys(self.version)
        eligible = apply_churn(self, self.version)
        chan = self.config.channel_at(self.version)
        scheduled = self.config.scheduler.participants(
            k_sched, self.version, self.m, chan, eligible=eligible)
        cohort = [j for j in clients if scheduled[j]]
        if not cohort and not self._heap and not self._buffer:
            # nothing else in flight: avoid a stall (alive clients only;
            # a fully-departed landed set falls back to everyone — the
            # empty-eligibility warning in apply_churn covers that case)
            cohort = [j for j in clients if self._alive(j)] or clients
        self._idle.update(j for j in clients if j not in cohort)
        draw = chan.draw(k_chan, self.m)
        times = self._flight_times(draw)
        for j in cohort:
            self._idle.discard(j)
            self._launch(j, now, times[j], bool(draw.straggler[j]),
                         bool(draw.dropout[j]), retry=0)

    def _alive(self, j: int) -> bool:
        """Is client ``j`` churn-eligible as of the last dispatch?"""
        return self._elig_prev is None or bool(self._elig_prev[j])

    def _retire_ef(self, departed: np.ndarray) -> None:
        """Zero newly-departed clients' EF memory rows (dense layout)."""
        if self.ef_memory:
            z = jnp.asarray(departed)
            self.ef_memory = {k: v.at[z].set(0)
                              for k, v in self.ef_memory.items()}

    def _retire_flight(self, flight: _Flight, now: float) -> None:
        """A departed client's upload landed: it is retired, never
        buffered — the client leaves the simulation until it returns."""
        self._pending_dropped[flight.client] = True
        self._idle.add(flight.client)

    def _consume_stats(self, stats: Dict[str, Any]) -> None:
        """Drain a group round's traced robust-aggregation counters."""
        for stat_name, val in stats.items():
            v = float(val)
            self.robust_stats[stat_name] = \
                self.robust_stats.get(stat_name, 0.0) + v
            self.obs.metrics.counter(stat_name).inc(v)

    def _pack_threat(self, mask, ids=None):
        """Bundle the attacker indicator next to the delivery mask when
        a threat is active (matches ``CommSession._pack_threat``)."""
        dyn = self.config.dynamics
        if dyn is None or dyn.threat is None:
            return mask
        if ids is None:
            if self._attacker_arr is None:
                self._attacker_arr = jnp.asarray(
                    dyn.threat.attacker_mask(np.arange(self.m)),
                    dtype=self._mask_dtype)
            return (mask, self._attacker_arr)
        return (mask, jnp.asarray(dyn.threat.attacker_mask(ids),
                                  dtype=self._mask_dtype))

    def _count_corrupted(self, delivered: np.ndarray,
                         ids: "np.ndarray | None") -> None:
        """Host-side tally of corrupted uploads the server consumed."""
        dyn = self.config.dynamics
        if dyn is None or dyn.threat is None:
            return
        att = dyn.threat.attacker_mask(
            np.arange(self.m) if ids is None else ids)
        n_bad = float((att & delivered).sum())
        self.robust_stats["uploads_corrupted"] = \
            self.robust_stats.get("uploads_corrupted", 0.0) + n_bad
        self.obs.metrics.counter("uploads_corrupted").inc(n_bad)

    def _redispatch(self, j: int, now: float, retry: int) -> None:
        """A dropped upload landed: the client re-fetches the current
        model and retries with fresh (deterministic) channel coins."""
        if not self._alive(j):
            self._idle.add(j)  # departed mid-flight: no retry
            return
        _, k_chan, _ = self._round_keys(self.version)
        chan = self.config.channel_at(self.version)
        draw = chan.draw(jax.random.fold_in(k_chan, retry), self.m)
        dropped = bool(draw.dropout[j]) and retry < MAX_RETRIES
        times = self._flight_times(draw)
        self._launch(j, now, times[j], bool(draw.straggler[j]), dropped,
                     retry=retry)

    def _flight_times(self, draw) -> np.ndarray:
        """Per-client cycle times for a full (m,) dispatch draw — both
        directions priced at their exact encoded sizes."""
        bytes_up = np.full(self.m, float(self.bytes_up_per_client))
        bytes_down = np.full(self.m, float(self.bytes_down_per_client))
        return self.config.channel_at(self.version).client_times(
            draw, bytes_up, bytes_down)

    def _launch(self, j: int, now: float, dt: float, straggler: bool,
                dropped: bool, retry: int) -> None:
        self._pending_down[j] += self.bytes_down_per_client
        self._seq += 1
        flight = _Flight(client=j, version=self.version,
                         straggler=straggler, dropped=dropped, retry=retry)
        heapq.heappush(self._heap, (now + dt, self._seq, flight))
        self.obs.flight.record(
            "dispatch", now, client=j, version=self.version,
            eta=now + dt, straggler=straggler, retry=retry)
        if retry:
            self.obs.metrics.counter("upload_retries").inc()

    def _pump(self) -> float:
        """Advance the event clock until the commit quorum buffers;
        returns the commit time (the quorum-th arrival's landing).

        The quorum is capped at the number of uploads that can still
        arrive (buffered + in flight): a partial-participation scheduler
        may idle more clients than ``buffer_size`` expects, and waiting
        for uploads nobody will send would deadlock the clock. The cap
        is announced once per trajectory; the per-commit cohort is
        always visible in ``RoundTrace.delivered``."""
        t = self.server_clock
        while True:
            need = max(1, min(self.quorum, len(self._buffer) + len(self._heap)))
            if need < self.quorum and not self._quorum_capped:
                self._quorum_capped = True
                obs_log.warn_with_context(
                    f"async commit quorum capped at {need} (< configured "
                    f"{self.quorum}): the scheduler keeps fewer clients in "
                    f"flight than the quorum asks for",
                    server_version=self.version, quorum=self.quorum,
                    capped_to=need)
            if len(self._buffer) >= need:
                return t
            if not self._heap:
                # everything idled out (pathological scheduler draw):
                # force-dispatch so the trajectory can make progress
                self._dispatch_cohort(sorted(self._idle), now=t)
                continue
            t, _, flight = heapq.heappop(self._heap)
            if not self._alive(flight.client):
                # the client churned out while its upload was in the
                # air: deterministic retirement (never buffered)
                self._retire_flight(flight, t)
                self.obs.flight.record(
                    "retire", t, client=flight.client,
                    version=flight.version)
                self.obs.metrics.counter("uploads_retired").inc()
                continue
            if flight.dropped:
                self._pending_dropped[flight.client] = True
                self.obs.flight.record(
                    "drop", t, client=flight.client, version=flight.version,
                    retry=flight.retry)
                self._redispatch(flight.client, t, flight.retry + 1)
            else:
                self._buffer.append(
                    (flight.client, flight.version, flight.straggler, t))
                self.obs.flight.record(
                    "arrival", t, client=flight.client,
                    version=flight.version,
                    server_version=self.version,
                    buffered=len(self._buffer))

    # -- one server commit --------------------------------------------------
    def step(self, round_fn) -> Any:
        """Run the event simulation up to the next server commit and
        return the committed state. ``round_fn(state, memory, key, mask,
        codec_key) -> (state, memory)`` is the jitted optimizer round,
        launched once per base model version in the commit."""
        from repro.comm.config import count_round_calls

        span = self.obs.trace.span
        with span("session.pump"):
            commit_time = self._pump()
            committed, self._buffer = self._buffer, []
            # group arrivals by the model version they computed on
            groups: Dict[int, List[int]] = {}
            for client, version, _, _ in committed:
                groups.setdefault(version, []).append(client)
            order = sorted(groups, reverse=True)  # freshest first

        outputs = {v: self._run_group(round_fn, v, groups[v]) for v in order}
        with span("session.aggregate"):
            state_new = self._combine(groups, order, outputs)
        with span("session.account"):
            if self.obs.enabled:
                self._observe_commit(committed, commit_time)
            self._record_trace(committed, commit_time)
            count_round_calls(self.obs, len(order))
        self.version += 1
        self.server_clock = commit_time
        self._snapshots[self.version] = state_new
        with span("session.gc"):
            self._gc_snapshots()
        with span("session.dispatch"):
            self._release(committed)
            self._dispatch_cohort(
                sorted({c for c, _, _, _ in committed} | self._idle),
                now=commit_time)
        return state_new

    def _run_group(self, round_fn, v: int, members: "list[int]") -> Any:
        """One jitted round from the snapshot of version ``v`` over the
        commit's ``members`` that computed on it; returns its state."""
        span = self.obs.trace.span
        with span("session.schedule"):
            if self.lockstep:
                mask = None
            else:
                mvec = np.zeros(self.m)
                mvec[members] = 1.0
                mask = jnp.asarray(mvec, self._mask_dtype)
            _, _, k_codec = self._round_keys(v)
            mask = self._pack_threat(mask)
        with span("launch"):
            out, self.ef_memory, stats = round_fn(
                self._snapshots[v], self.ef_memory, self.keys[v], mask,
                k_codec)
        with span("session.stats"):
            self._consume_stats(stats)
        return out

    def _combine(self, groups, order, outputs) -> Any:
        """The committed state from the groups' round outputs."""
        fresh = order[0]
        eta = float(self.config.server_lr)
        if len(order) == 1 and fresh == self.version and eta == 1.0:
            # single fresh group at unit server lr: the round output IS
            # the next state (no delta arithmetic — preserves sync
            # bit-exactness; the staleness weight is 1 at tau=0 by
            # convention)
            return outputs[fresh]
        # c_g = eta_s * staleness(tau_g) * P_g / sum_h P_h:
        # participation mass is renormalized over the commit (as the
        # sync driver renormalizes partial cohorts) but staleness
        # DAMPS the step rather than being renormalized away — an
        # all-stale commit under "inverse" moves the model by
        # 1/(1+tau) of its delta, and a weight of exactly 0
        # contributes exactly nothing. The FedBuff-style global
        # server learning rate eta_s scales every committed delta on
        # top (eta_s = 1 is bit-identical to not having the knob).
        p_mass = {v: float(self.client_weights[groups[v]].sum())
                  for v in order}
        p_total = sum(p_mass.values())
        w_new = self._snapshots[self.version]["w"]
        for v in order:
            c = (eta * self._staleness(float(self.version - v))
                 * p_mass[v] / p_total)
            delta = outputs[v]["w"] - self._snapshots[v]["w"]
            w_new = w_new + c * delta
        # auxiliary state rides the freshest cohort's round when that
        # cohort is current; otherwise the current state is kept and
        # only the model moves (stale aux must not overwrite fresher)
        base = (outputs[fresh] if fresh == self.version
                else self._snapshots[self.version])
        state_new = dict(base)
        state_new["w"] = w_new
        return state_new

    def _release(self, committed) -> None:
        """Hook: the committed clients landed (the dense driver tracks
        them through the dispatch set it passes)."""

    def _observe_commit(self, committed, commit_time: float) -> None:
        """Populate commit-time telemetry (host-side, after the commit's
        rounds; only called when telemetry is enabled)."""
        mt = self.obs.metrics
        mt.histogram("commit_buffer_depth").observe(len(committed))
        mt.histogram("inflight_depth").observe(len(self._heap))
        mt.histogram("staleness").observe_many(
            float(self.version - v) for _, v, _, _ in committed)
        mt.histogram("buffered_upload_age_s").observe_many(
            commit_time - t_arr for _, _, _, t_arr in committed)
        self.obs.flight.record(
            "commit", commit_time, version=self.version + 1,
            server_version=self.version,
            clients=sorted(c for c, _, _, _ in committed),
            inflight=len(self._heap))

    def _record_trace(self, committed, commit_time: float) -> None:
        mask = np.zeros(self.m, dtype=bool)
        straggler = np.zeros(self.m, dtype=bool)
        stale = np.full(self.m, np.nan)
        for client, version, was_straggler, _ in committed:
            mask[client] = True
            straggler[client] = was_straggler
            stale[client] = float(self.version - version)
        bytes_up = float(self.bytes_up_per_client) * mask.astype(np.float64)
        # scheduled \ delivered = clients whose upload was lost in this
        # commit window and who did not land a retry before the commit —
        # keeps summarize()'s dropped_client_rounds honest in async mode
        self.traces.append(RoundTrace(
            round=self.version,
            scheduled=mask | self._pending_dropped,
            delivered=mask,
            straggler=straggler,
            bytes_up=bytes_up,
            bytes_down=self._pending_down,
            sim_time_s=commit_time - self.server_clock,
            staleness=stale,
            version=self.version + 1,
        ))
        self._count_corrupted(mask, None)
        if self.obs.enabled:
            tr = self.traces[-1]
            mt = self.obs.metrics
            mt.counter("bytes_up").inc(float(tr.bytes_up.sum()))
            mt.counter("bytes_down").inc(float(tr.bytes_down.sum()))
            mt.counter("delivered_client_rounds").inc(float(mask.sum()))
            mt.counter("dropped_client_rounds").inc(
                float(self._pending_dropped.sum()))
            mt.counter("straggler_client_rounds").inc(float(straggler.sum()))
            self.obs.annotate(
                bytes_up=float(tr.bytes_up.sum()),
                bytes_down=float(tr.bytes_down.sum()),
                delivered=int(mask.sum()),
                version=self.version + 1,
                mean_staleness=tr.mean_staleness,
                sim_time_s=float(tr.sim_time_s))
        self._pending_down = np.zeros(self.m, dtype=np.float64)
        self._pending_dropped = np.zeros(self.m, dtype=bool)

    def _gc_snapshots(self) -> None:
        """Drop model snapshots no in-flight or buffered cycle references."""
        alive = {self.version}
        alive.update(f.version for _, _, f in self._heap if not f.dropped)
        alive.update(v for _, v, _, _ in self._buffer)
        for v in [v for v in self._snapshots if v not in alive]:
            del self._snapshots[v]

    def ef_residual_norms(self) -> Dict[str, float]:
        """Per-payload Frobenius norm of the current EF residuals."""
        return feedback.residual_norms(self.ef_memory)


class PopulationAsyncSession(AsyncSession):
    """Event-driven driver over a lazy ``ClientPopulation``.

    Same event machinery as ``AsyncSession`` (heap, buffer, versioned
    snapshots, staleness-weighted delta commits) with the client axis
    replaced by sampled cohorts:

      * each new model version samples its cohort ids from the
        population (``Scheduler.sample_ids`` on the SAME
        ``fold_in(seed, version)`` stream the sync population driver
        uses, so both drivers schedule identical cohorts) and dispatches
        the ids not already in flight; landed clients return to the
        anonymous pool instead of being tracked per id;
      * dropped uploads are *replaced*, not retried: the client goes
        back to the pool and the next version's draw samples fresh ids —
        the realistic cross-device semantic (FedBuff-style systems
        replace failed clients). If every in-flight upload drops, the
        current version's cohort redraws its channel coins with a
        folded attempt counter (forced delivery after ``MAX_RETRIES``
        attempts) so the clock always advances;
      * each commit group materializes its members' shards on demand,
        padded to the scheduler's fixed cohort size (pad rows duplicate
        the first member under a zero delivery mask), so every group of
        every round reuses one jaxpr;
      * EF memory lives in the bounded LRU hot-set store
        (``feedback.BoundedMemory``): rows are gathered for the group,
        gated by the group's delivery mask inside the round, and
        scattered back for the real members only.

    Lock-step configs (full scheduler, no dropout, full quorum) sample
    the whole population as one cohort with ``mask=None`` — the
    identical jaxpr and key schedule as ``PopulationCommSession``, hence
    bit-identical across the drivers.
    """

    def __init__(self, config, population, *, keys, state0=None,
                 mask_dtype=jnp.float64, obs=NULL_TELEMETRY,  # noqa: RA005 — caller passes the problem dtype; default matches the recorded goldens
                 client_mesh=None):
        super().__init__(config, m=population.m,
                         client_weights=population.client_weights,
                         keys=keys, state0=None, mask_dtype=mask_dtype,
                         obs=obs)
        self.population = population
        self.client_mesh = client_mesh
        self.cohort_size = config.scheduler.cohort_size(population.m)
        self.ef_store: "feedback.BoundedMemory | None" = None
        # quorum counts against what can actually be in flight — one
        # cohort — not against the population
        if config.buffer_size is not None:
            self.quorum = min(self.cohort_size, int(config.buffer_size))
        else:
            self.quorum = max(1, min(self.cohort_size, int(math.ceil(
                config.async_quantile * self.cohort_size))))
        dyn = config.dynamics
        self.lockstep = (config.scheduler.is_full
                         and config.channel.dropout_prob == 0.0
                         and self.quorum == self.m
                         and (dyn is None or not dyn.forces_mask))
        # population-mode event bookkeeping: O(in-flight), never O(m)
        self._in_flight: set = set()
        # client id -> dispatched broadcast bytes (defaultdict: the
        # inherited _launch accumulates with `+=`)
        self._pending_down = defaultdict(float)
        self._pending_dropped = {}  # client id -> True (lost this window)
        self._attempt = 0  # channel redraws of the current version's cohort
        self._state0 = state0

    # -- trace-time discovery ------------------------------------------------
    def prepare(self, trace_round) -> None:
        from repro.comm.config import probe_round

        spec = probe_round(self.config, self.cohort_size, self._mask_dtype,
                           self.plan, trace_round, full_cohort=self.lockstep)
        if spec:
            capacity = self.config.ef_capacity
            if capacity is None:
                capacity = min(self.m, 8 * self.cohort_size)
            self.ef_store = feedback.BoundedMemory(
                spec, max(capacity, self.cohort_size))
        self.ef_memory = {}
        if self._state0 is not None:
            self.start(self._state0)

    # -- event machinery -----------------------------------------------------
    def start(self, state) -> None:
        self._snapshots[0] = state
        self._dispatch_cohort((), now=0.0)

    def _dispatch_cohort(self, clients, now: float) -> None:
        """Sample the current version's cohort and replenish the flight
        pool up to the cohort size. ``clients`` (the dense driver's
        landed set) is ignored: population clients are anonymous between
        cycles.

        The concurrency cap mirrors the dense driver, where only landed
        clients are re-dispatched so at most one cohort is ever in the
        air: without it every commit would add a full cohort while
        consuming only a quorum, the backlog would grow without bound,
        and staleness would diverge linearly in the round count."""
        from repro.comm.config import apply_churn

        budget = self.cohort_size - len(self._in_flight)
        if budget <= 0:
            return
        k_sched, k_chan, _ = self._round_keys(self.version)
        eligible = apply_churn(self, self.version)
        chan = self.config.channel_at(self.version)
        ids = self.config.scheduler.sample_ids(
            k_sched, self.version, self.m, chan, eligible=eligible)
        cohort = np.asarray(
            [j for j in ids if int(j) not in self._in_flight][:budget],
            dtype=np.int64)
        if cohort.size == 0:
            return
        attempt = self._attempt
        self._attempt += 1
        if attempt:
            # the whole previous dispatch of this version dropped:
            # redraw the coins deterministically, forcing delivery once
            # the attempt budget is spent so the clock cannot stall
            k_chan = jax.random.fold_in(k_chan, attempt)
        draw = chan.draw_for(k_chan, cohort)
        if attempt >= MAX_RETRIES:
            draw = dataclasses.replace(
                draw, dropout=np.zeros_like(draw.dropout))
        per_up = float(self.bytes_up_per_client)
        per_down = float(self.bytes_down_per_client)
        times = chan.client_times_for(
            cohort, self.m, draw,
            np.full(cohort.size, per_up), np.full(cohort.size, per_down))
        for i, j in enumerate(cohort):
            j = int(j)
            self._in_flight.add(j)
            self._launch(j, now, float(times[i]), bool(draw.straggler[i]),
                         bool(draw.dropout[i]), retry=attempt)

    def _redispatch(self, j: int, now: float, retry: int) -> None:
        """A dropped upload landed: the client returns to the pool (the
        scheduler replaces it from the population at the next version).
        ``_pump`` already marked it in ``_pending_dropped``."""
        self._in_flight.discard(j)
        if not self._heap and not self._buffer:
            # every in-flight upload dropped: redraw this version's
            # cohort (attempt counter folded into the coins)
            self._dispatch_cohort((), now=now)

    def _retire_flight(self, flight: _Flight, now: float) -> None:
        """A departed client's upload landed: back to the anonymous pool
        (the next dispatch samples a replacement from the survivors)."""
        self._pending_dropped[flight.client] = True
        self._in_flight.discard(flight.client)
        if not self._heap and not self._buffer:
            self._dispatch_cohort((), now=now)

    def _retire_ef(self, departed: np.ndarray) -> None:
        """Departed clients leave the EF hot set deterministically."""
        if self.ef_store is not None:
            self.ef_store.retire(departed)

    # -- one server commit ---------------------------------------------------
    def _run_group(self, round_fn, v: int, members: "list[int]") -> Any:
        """Population-mode group round: the members' shards materialize
        on demand. ``round_fn(cohort, state, memory, key, mask,
        codec_key) -> (state, memory)`` is the jitted cohort round."""
        span = self.obs.trace.span
        n_real = len(members)
        # fixed-width cohort: pad with the first member's id under a
        # zero delivery mask, so every group reuses one jaxpr
        padded = np.asarray(members + [members[0]] * (self.cohort_size
                                                      - n_real))
        with span("session.materialize"):
            cohort = self.population.materialize(padded)
            if self.client_mesh is not None:
                from repro.sharding.rules import shard_cohort

                cohort = shard_cohort(self.client_mesh, cohort)
            memory = self.ef_store.gather(padded) if self.ef_store else {}
        with span("session.schedule"):
            if self.lockstep:
                mask = None
            else:
                mvec = np.zeros(self.cohort_size)
                mvec[:n_real] = 1.0
                mask = jnp.asarray(mvec, self._mask_dtype)
            _, _, k_codec = self._round_keys(v)
            mask = self._pack_threat(mask, padded)
        with span("launch"), client_mesh_scope(self.client_mesh):
            out, mem_out, stats = round_fn(
                cohort, self._snapshots[v], memory, self.keys[v], mask,
                k_codec)
        with span("session.stats"):
            self._consume_stats(stats)
        if self.ef_store is not None:
            with span("session.materialize"):
                # real members only: pad rows are frozen duplicates
                self.ef_store.scatter(members, mem_out)
        return out

    def _release(self, committed) -> None:
        """The committed clients return to the anonymous pool, and the
        new version's cohort draws its coins afresh."""
        for client, _, _, _ in committed:
            self._in_flight.discard(client)
        self._attempt = 0

    def _record_trace(self, committed, commit_time: float) -> None:
        down = dict(self._pending_down)
        dropped = set(self._pending_dropped)
        ids = sorted({c for c, _, _, _ in committed} | dropped | set(down))
        index = {cid: i for i, cid in enumerate(ids)}
        n = len(ids)
        delivered = np.zeros(n, dtype=bool)
        straggler = np.zeros(n, dtype=bool)
        stale = np.full(n, np.nan)
        for client, version, was_straggler, _ in committed:
            i = index[client]
            delivered[i] = True
            straggler[i] = was_straggler
            stale[i] = float(self.version - version)
        scheduled = delivered.copy()
        for cid in dropped:
            scheduled[index[cid]] = True
        bytes_up = (float(self.bytes_up_per_client)
                    * delivered.astype(np.float64))
        bytes_down = np.asarray([down.get(cid, 0.0) for cid in ids])
        tr = RoundTrace(
            round=self.version,
            scheduled=scheduled,
            delivered=delivered,
            straggler=straggler,
            bytes_up=bytes_up,
            bytes_down=bytes_down,
            sim_time_s=commit_time - self.server_clock,
            staleness=stale,
            version=self.version + 1,
            ids=np.asarray(ids, dtype=np.int64),
            population=self.m,
        )
        self.traces.append(tr)
        self._count_corrupted(delivered, tr.ids)
        if self.obs.enabled:
            mt = self.obs.metrics
            mt.counter("bytes_up").inc(float(tr.bytes_up.sum()))
            mt.counter("bytes_down").inc(float(tr.bytes_down.sum()))
            mt.counter("delivered_client_rounds").inc(float(delivered.sum()))
            mt.counter("dropped_client_rounds").inc(float(len(dropped)))
            mt.counter("straggler_client_rounds").inc(float(straggler.sum()))
            self.obs.annotate(
                bytes_up=float(tr.bytes_up.sum()),
                bytes_down=float(tr.bytes_down.sum()),
                delivered=int(delivered.sum()),
                version=self.version + 1,
                mean_staleness=tr.mean_staleness,
                sim_time_s=float(tr.sim_time_s))
        self._pending_down = defaultdict(float)
        self._pending_dropped = {}

    def finalize(self):
        from repro.comm.metrics import transport_from_traces

        if self.obs.enabled:
            ef_bytes = self.ef_store.nbytes if self.ef_store else 0
            self.obs.metrics.gauge("ef_memory_bytes").set(float(ef_bytes))
            if self.ef_store is not None:
                self.obs.metrics.gauge("ef_hot_set_evictions").set(
                    float(self.ef_store.evictions))
        return transport_from_traces(
            self.traces,
            staleness=np.array([tr.mean_staleness for tr in self.traces]),
            ef_residuals=self.ef_residual_norms(),
        )

    def ef_residual_norms(self) -> Dict[str, float]:
        if self.ef_store is not None:
            return self.ef_store.residual_norms()
        return {}
