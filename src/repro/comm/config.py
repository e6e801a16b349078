"""CommConfig + the per-round runtime objects the driver threads through.

Three layers:

  * ``CommConfig``  — user-facing description: which codec per payload
      name *and direction*, which participation scheduler, which channel
      model, seed.
  * ``CommSession`` — driver-side (host) state for one trajectory: draws
      cohorts/channel randomness per round, accumulates ``RoundTrace``s,
      and owns the *payload plan* (exact encoded bytes per payload name,
      recorded once at jit-trace time — payload shapes are static).
      Implements the ``Session`` protocol (``prepare`` / ``step`` /
      ``finalize``, see ``repro.comm.session``) for the synchronous
      lock-step clock.
  * ``CommRound``   — the view optimizers see *inside* the jitted round:
      ``uplink(name, x)`` routes a stacked per-client payload through its
      codec (so compression error perturbs the optimization),
      ``downlink(name, x)`` routes a server broadcast through its
      direction-aware codec (encoded once, received by every scheduled
      client), and ``weights(p)`` masks + renormalizes aggregation
      weights for the delivering cohort.

The wire API is symmetric: downlink payloads resolve codecs under the
``"down:"``-prefixed name (``codecs={"down:w": "bf16"}`` or the
``downlink_codecs={"w": "bf16"}`` shorthand) and are billed at their
exact encoded size per receiving client — the broadcast is no longer a
``downlink_floats * itemsize`` formula.

With ``CommConfig(error_feedback=...)`` lossy payloads additionally
carry client-side error-feedback memory (``repro.comm.feedback``): the
driver threads the memory pytree through the jitted round and ``uplink``
emits the updated memory via ``CommRound.memory_out``. Under the default
``ef_variant="ef21"`` the memory is the payload *estimate* ``g`` — the
wire carries the compressed innovation ``C(x - g)`` and the server
consumes the advanced estimate ``g + C(x - g)``; under ``"ef14"`` it is
the accumulated residual ``e`` and the wire carries the compensated
payload ``C(x + e)``.

Bit-exactness contract: with identity codecs and full participation
(no dropout), ``CommRound.uplink`` AND ``CommRound.downlink`` return
their input objects unchanged and ``weights`` returns ``p`` unchanged —
the round's jaxpr is identical to the no-comm path, so trajectories
match today's bit-for-bit, in both wire directions.

Scenario dynamics (``CommConfig(dynamics=DynamicsConfig(...))``, see
``repro.dynamics``) compose on top: churn filters the eligible id set
the scheduler samples from (departed clients' EF rows are retired
deterministically), a ``ChannelProcess`` modulates the channel per
round, a ``ThreatModel`` corrupts a seeded subset of uplinks inside
the traced round (before the codec — attackers craft their wire
payload), and a robust aggregator transforms the decoded payload
before the optimizer's weighted aggregation. Every layer defaults off,
and with ``dynamics=None`` every code path here is literally the
pre-dynamics one.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm import feedback
from repro.comm.channel import ChannelModel
from repro.comm.codecs import Codec, IdentityCodec, make_codec
from repro.comm.metrics import RoundTrace, Transport, transport_from_traces
from repro.comm.scheduler import Scheduler, make_scheduler
from repro.obs import NULL_TELEMETRY
from repro.obs import log as obs_log
from repro.sharding.rules import client_mesh_scope, vmap_clients

# payload-name prefix that selects the downlink (server -> client)
# direction in codec specs and in the byte plan
DOWN = "down:"

# control-plane payloads default to lossless regardless of the default
# codec (compressing a 1-scalar guard loss or an O(1) sketch seed saves
# nothing and can poison the accept/reject logic / the shared basis)
_LOSSLESS_BY_DEFAULT = ("loss", "down:seed")

# fold_in stream offset separating downlink codec keys from the uplink
# payload counter (keeps uplink key schedules unchanged by the presence
# of downlink payloads)
_DOWNLINK_KEY_STREAM = 1 << 20

# fold_in stream offset for threat-model corruption keys (disjoint from
# both the uplink payload counter and the downlink stream, so turning a
# threat on never perturbs codec randomness)
_THREAT_KEY_STREAM = 1 << 21

# begin_variant sentinel: "no variant announced yet" (None is a valid
# round signature — the default single-trace trajectory)
_NO_VARIANT = object()


def plan_bytes(plan: "Dict[str, int]", *, down: bool) -> int:
    """Sum one direction of a payload byte plan (keys are payload
    occurrences; downlink occurrences carry the ``"down:"`` prefix)."""
    return int(sum(v for k, v in plan.items()
                   if k.startswith(DOWN) == down))


@dataclasses.dataclass
class CommConfig:
    """Transport description for one federated run.

    ``codecs`` maps payload names (``"h_sk"``, ``"sg"``, ``"grad"``,
    ``"w_local"``, ...) to codec specs; the ``"default"`` entry covers
    unnamed payloads. A bare string/Codec is shorthand for
    ``{"default": ...}``. Downlink (server -> client broadcast) payloads
    resolve under the ``"down:"``-prefixed name — ``"down:w"`` for the
    model broadcast — falling back to ``"down:default"`` and then to
    identity, NEVER to the uplink ``"default"``: turning on uplink
    compression must not silently degrade the broadcast.
    ``downlink_codecs`` is a shorthand that merges into ``codecs`` with
    the prefix applied: ``downlink_codecs="bf16"`` ==
    ``codecs["down:default"] = "bf16"``, ``downlink_codecs={"w": ...}``
    == ``codecs["down:w"] = ...`` (explicit ``down:`` entries in
    ``codecs`` win on conflict).

    ``error_feedback`` gates client-side error-feedback memory per
    payload (see ``repro.comm.feedback``): ``True`` enables it for every
    *eligible* payload with a *lossy* codec, a collection of names
    enables those payloads only, and a ``{name: bool}`` dict (optional
    ``"default"`` entry) gives full control. Lossless payloads never
    allocate memory regardless, and call sites can opt a payload out
    entirely with ``uplink(..., ef_eligible=False)`` (per-round random
    sketch bases). ``ef_variant`` picks the recursion: ``"ef21"``
    (compressed-estimate tracking, default) or ``"ef14"`` (classic
    residual compensation). ``ef_capacity`` bounds EF state in
    population mode (``run_rounds`` over a ``ClientPopulation``): dense
    memory rows are kept only for an LRU hot set of that many client
    ids, the long tail re-entering with a zero row (on-sample reset);
    default is ``min(m, 8 × cohort size)``. Dense-``m`` runs ignore it.

    ``async_mode=True`` swaps the synchronous lock-step driver for the
    event-driven async driver (``repro.comm.async_driver``): each client
    computes on the model version it last received and the server
    commits once a quorum of uploads has arrived — ``buffer_size`` (a
    FedBuff-style K) when set, else ``ceil(async_quantile * m)``.
    ``staleness`` weights stale contributions on top of participation
    weights: ``"constant"``, ``"inverse"`` (1/(1+tau)), or
    ``"poly:a"`` ((1+tau)^-a); see ``make_staleness``. ``server_lr`` is
    the FedBuff-style global server learning rate: every committed model
    delta is additionally scaled by it *after* staleness weighting
    (default 1.0 is bit-identical to not having the knob). It is an
    async-driver control — configuring it with ``async_mode=False``
    raises. With the full scheduler, no dropout, a full quorum
    (``async_quantile=1.0``, ``buffer_size`` unset) and ``server_lr=1``
    the async driver is lock-step-equivalent and reproduces the
    synchronous trajectory bit-identically.
    """

    codecs: "Dict[str, Any] | str | Codec" = "identity"
    downlink_codecs: "Dict[str, Any] | str | Codec | None" = None
    scheduler: "str | Scheduler" = "full"
    channel: ChannelModel = dataclasses.field(default_factory=ChannelModel)
    seed: int = 0
    error_feedback: "bool | str | Dict[str, bool] | tuple | frozenset" = False
    ef_variant: str = "ef21"
    ef_capacity: "int | None" = None  # EF hot-set size (population mode)
    async_mode: bool = False
    buffer_size: "int | None" = None
    async_quantile: float = 1.0
    staleness: "str | Any" = "constant"
    server_lr: float = 1.0
    dynamics: "Any | None" = None  # repro.dynamics.DynamicsConfig

    def __post_init__(self):
        if self.dynamics is not None:
            from repro.dynamics import DynamicsConfig

            if not isinstance(self.dynamics, DynamicsConfig):
                raise ValueError(
                    f"CommConfig.dynamics wants a "
                    f"repro.dynamics.DynamicsConfig, got {self.dynamics!r}")
            if self.dynamics.is_null:
                # all layers off: normalize away so every `dynamics is
                # None` fast path (and the bit-exactness gates) holds
                self.dynamics = None
        # always own a private copy: the downlink_codecs merge below must
        # never mutate a caller's dict (configs often share one spec)
        self.codecs = (dict(self.codecs) if isinstance(self.codecs, dict)
                       else {"default": self.codecs})
        if self.downlink_codecs is not None:
            shorthand = (self.downlink_codecs
                         if isinstance(self.downlink_codecs, dict)
                         else {"default": self.downlink_codecs})
            for name, spec in shorthand.items():
                self.codecs.setdefault(f"{DOWN}{name}", spec)
        if self.server_lr <= 0.0:
            raise ValueError(f"server_lr must be > 0, got {self.server_lr}")
        if self.server_lr != 1.0 and not self.async_mode:
            raise ValueError(
                "server_lr scales asynchronous commit deltas; it requires "
                "async_mode=True (the synchronous driver applies rounds "
                "verbatim)")
        if self.ef_variant not in feedback.EF_VARIANTS:
            raise ValueError(
                f"unknown ef_variant {self.ef_variant!r}; "
                f"want one of {feedback.EF_VARIANTS}")
        if self.buffer_size is not None and self.buffer_size < 1:
            raise ValueError(
                f"buffer_size must be >= 1, got {self.buffer_size}")
        if self.ef_capacity is not None and self.ef_capacity < 1:
            raise ValueError(
                f"ef_capacity must be >= 1, got {self.ef_capacity}")
        if not 0.0 < self.async_quantile <= 1.0:
            raise ValueError(
                f"async_quantile must be in (0, 1], got {self.async_quantile}")
        # validate the staleness spec eagerly (bad specs fail at config
        # time, not mid-trajectory); AsyncSession resolves it for real
        from repro.comm.async_driver import make_staleness

        make_staleness(self.staleness)
        self._codec_cache: Dict[str, Codec] = {}
        self.scheduler = make_scheduler(self.scheduler)

    def codec_for(self, payload: str) -> Codec:
        """Resolve a payload (``"name"`` uplink / ``"down:name"``
        downlink) to its codec. Each direction has its own default."""
        if payload not in self._codec_cache:
            if payload in self.codecs:
                spec = self.codecs[payload]
            elif payload in _LOSSLESS_BY_DEFAULT:
                spec = "identity"
            elif payload.startswith(DOWN):
                spec = self.codecs.get(f"{DOWN}default", "identity")
            else:
                spec = self.codecs.get("default", "identity")
            self._codec_cache[payload] = make_codec(spec)
        return self._codec_cache[payload]

    def ef_for(self, payload: str) -> bool:
        """EF is folded in only where it can matter: requested AND lossy."""
        return (feedback.ef_requested(self.error_feedback, payload)
                and not self.codec_for(payload).lossless)

    @property
    def has_error_feedback(self) -> bool:
        return feedback.any_ef_requested(self.error_feedback)

    def channel_at(self, t: int):
        """The channel as seen at round ``t``: the static model itself
        when no ``ChannelProcess`` is configured (the literal same
        object — zero change to the default path), else a per-round
        modulated view with the same method signatures."""
        dyn = self.dynamics
        if dyn is None or dyn.channel is None:
            return self.channel
        return dyn.channel.at(self.channel, t)


def count_round_calls(obs, n: int) -> None:
    """Record the ``n`` jitted round launches of the round executing: the
    ``round_calls`` field of its record and the counter of that name."""
    if obs.enabled:
        obs.metrics.counter("round_calls").inc(n)
        obs.annotate(round_calls=n)


def apply_churn(session, t: int) -> "np.ndarray | None":
    """Shared churn bookkeeping for every comm session at round/version
    ``t``: returns the eligible id array (or ``None`` without churn),
    retires newly-departed clients' EF rows via the session's
    ``_retire_ef`` hook, and publishes the ``active_population`` gauge.

    Idempotent within one ``t`` (the async driver may dispatch the same
    version more than once). If churn empties the population entirely,
    the full id set is restored with a one-time warning — a trajectory
    cannot run over zero clients.
    """
    dyn = session.config.dynamics
    if dyn is None or dyn.churn is None:
        return None
    elig = dyn.churn.eligible_mask(t, session.m)
    if not elig.any():
        if not getattr(session, "_churn_warned", False):
            session._churn_warned = True
            obs_log.warn_with_context(
                "churn left zero eligible clients; treating the full "
                "population as eligible so the trajectory can proceed",
                round=t, m=session.m)
        elig = np.ones(session.m, dtype=bool)
    prev = session._elig_prev
    session._elig_prev = elig
    if prev is not None:
        departed = np.nonzero(prev & ~elig)[0]
        if departed.size:
            session._retire_ef(departed)
            session.obs.metrics.counter("clients_departed").inc(
                float(departed.size))
    if session.obs.enabled:
        session.obs.metrics.gauge("active_population").set(float(elig.sum()))
    return np.nonzero(elig)[0].astype(np.int64)


class CommRound:
    """In-jit view of one round's transport. Constructed inside the
    traced round function; ``mask``/``key``/``memory`` are traced
    arrays, the codec table and byte plan are static Python closed over
    by the trace.

    ``memory`` is the EF21 residual pytree threaded through the jitted
    round by the driver (``{payload_key: (m, ...)}``); ``uplink`` folds
    the matching residual into EF-enabled lossy payloads and writes the
    updated residual to ``memory_out``. ``ef_record`` switches the
    object into the shape-discovery mode ``CommSession.
    init_error_feedback`` uses under ``jax.eval_shape``.
    """

    def __init__(
        self,
        config: CommConfig,
        plan: Dict[str, int],
        mask: "jax.Array | None",
        key: "jax.Array | None",
        memory: "Dict[str, jax.Array] | None" = None,
        ef_record: "Dict[str, jax.ShapeDtypeStruct] | None" = None,
    ):
        self._config = config
        self._plan = plan
        # with a ThreatModel active the sessions pack the per-client
        # attacker indicator next to the delivery mask as a 2-tuple
        # (both traced; jit flattens the pytree) — unpack it here so the
        # rest of the round sees the plain delivery mask
        self.attackers = None
        if isinstance(mask, tuple):
            mask, self.attackers = mask
        self.mask = mask
        self._key = key
        self._n_payloads = 0
        self._n_down = 0
        self._occurrences: Dict[str, int] = {}
        self._ef_record = ef_record
        # memory_out starts as a same-structure copy so payloads a round
        # happens to skip still thread their residual through unchanged
        self.memory_out: Dict[str, jax.Array] = dict(memory or {})
        # traced robust-aggregation counters (uploads_clipped, ...);
        # empty without dynamics — zero extra jaxpr outputs
        self.stats_out: Dict[str, jax.Array] = {}

    def _payload_key(self, name: str) -> str:
        """Stable per-round key for the i-th uplink of ``name`` — a round
        calling ``uplink("g", ...)`` twice bills (and remembers) both."""
        occ = self._occurrences.get(name, 0)
        self._occurrences[name] = occ + 1
        return name if occ == 0 else f"{name}#{occ}"

    def uplink(self, name: str, x: jax.Array,
               wire_shape: "tuple | None" = None,
               ef_eligible: bool = True,
               ef_reset=None) -> jax.Array:
        """Route a stacked per-client payload ``x: (m, ...)`` through its
        codec's simulated encode→decode; records exact encoded bytes.

        ``wire_shape`` overrides the shape billed for payloads whose
        algorithm already defines a native wire format (e.g. FedNL
        transmits a rank-1 ``(M+1,)`` eigenpair, not the materialized
        (M, M) difference); the codec still prices that shape, so codec
        compression stays reflected in the byte accounting.

        ``ef_eligible=False`` declares that this payload's coordinate
        system is redrawn every round (two-sided sketches): cross-round
        error-feedback memory would mix incompatible bases, so EF is
        skipped for it even when ``CommConfig.error_feedback`` asks.

        ``ef_reset`` (a traced 0/1 scalar, or None) zeroes the EF memory
        BEFORE compensating: rotating sketch schedules pass
        ``SketchPolicy.ef_reset(t)`` so the residual accumulated in the
        previous epoch's basis is discarded the round the basis
        rotates, instead of being injected into the new basis. The
        reset is a pure function of the round index and the declared
        schedule, so the server's estimate resets in lock-step."""
        codec = self._config.codec_for(name)
        pkey = self._payload_key(name)
        self._plan[pkey] = codec.nbytes(
            tuple(wire_shape) if wire_shape is not None
            else tuple(x.shape[1:]), x.dtype)
        self._n_payloads += 1
        dyn = self._config.dynamics
        threat = dyn.threat if dyn is not None else None
        robust = dyn.robust if dyn is not None else None
        if (threat is not None and self.attackers is not None
                and threat.applies(name)):
            # corruption happens BEFORE the codec: the attacker crafts
            # its wire payload, so compression and EF operate on the
            # corrupted upload exactly as on an honest one. The key
            # stream is disjoint from codec/downlink streams.
            x = threat.corrupt(
                jax.random.fold_in(
                    self._key, _THREAT_KEY_STREAM + self._n_payloads),
                x, self.attackers)
        if isinstance(codec, IdentityCodec):
            if robust is None:
                return x  # same object: zero jaxpr change
            decoded = x
        else:
            decoded = self._roundtrip(codec, name, pkey, x, ef_eligible,
                                      ef_reset)
        if robust is not None:
            # server-side defense on what was received (post-decode);
            # EF memory above tracks the *wire* payload — the client
            # cannot observe the server's clipping/trimming
            decoded = robust(decoded, self.mask, self.stats_out)
        return decoded

    def _roundtrip(self, codec, name, pkey, x, ef_eligible, ef_reset):
        """Simulated encode->decode of one lossy payload (+ EF memory)."""
        ef = ef_eligible and self._config.ef_for(name)
        if ef and self._ef_record is not None:
            self._ef_record[pkey] = jax.ShapeDtypeStruct(x.shape, x.dtype)
        if codec.deterministic:
            keys = jnp.zeros((x.shape[0], 2), jnp.uint32)  # unused by codec
        else:
            base = jax.random.fold_in(self._key, self._n_payloads)
            keys = jax.random.split(base, x.shape[0])
        if ef and pkey in self.memory_out:
            mem = self.memory_out[pkey]
            if ef_reset is not None:
                # basis rotated: the residual's coordinate system is
                # stale — compensate from a zeroed memory this round
                mem = mem * (1 - jnp.asarray(ef_reset, mem.dtype))
            decoded, mem_new = feedback.compensate(
                codec, keys, x, mem,
                variant=self._config.ef_variant)
            # dropped clients never ran the round: freeze their memory
            # rows with the same gate that protects optimizer state.
            # The frozen fallback is the post-reset ``mem``: the basis
            # rotation is schedule knowledge, not computation — a client
            # absent on the boundary round must still drop its old-epoch
            # residual, or it would compensate into the new basis later.
            self.memory_out[pkey] = self.where_delivered(mem_new, mem)
            return decoded
        return vmap_clients(codec.roundtrip)(keys, x)

    def downlink(self, name: str, x: jax.Array,
                 wire_shape: "tuple | None" = None) -> jax.Array:
        """Route a server->client broadcast through its downlink codec's
        simulated encode->decode; records exact encoded bytes.

        The server encodes ONCE and every scheduled client decodes the
        same bytes, so ``x`` is the unstacked server-side array (no
        client axis) and the plan bills ``nbytes`` per receiving client
        (each client pulls the broadcast over its own link). Codecs
        resolve under ``"down:<name>"`` — see ``CommConfig.codecs`` —
        and the identity codec returns ``x`` unchanged, preserving the
        bit-exactness contract in the downlink direction too.

        No error feedback applies: EF memory is a per-client *uplink*
        construct; a broadcast has one sender whose compression error is
        common knowledge.
        """
        codec = self._config.codec_for(f"{DOWN}{name}")
        pkey = self._payload_key(f"{DOWN}{name}")
        self._plan[pkey] = codec.nbytes(
            tuple(wire_shape) if wire_shape is not None
            else tuple(x.shape), x.dtype)
        self._n_down += 1
        if isinstance(codec, IdentityCodec):
            return x  # same object: zero jaxpr change
        if codec.deterministic:
            key = jnp.zeros((2,), jnp.uint32)  # unused by codec
        else:
            key = jax.random.fold_in(
                self._key, _DOWNLINK_KEY_STREAM + self._n_down)
        return codec.roundtrip(key, x)

    def weights(self, p: jax.Array) -> jax.Array:
        """Aggregation weights restricted to the delivering cohort."""
        if self.mask is None:
            return p
        pm = p * self.mask
        return pm / jnp.sum(pm)

    def where_delivered(self, new: jax.Array, old: jax.Array) -> jax.Array:
        """Per-client state update gate: non-delivering clients keep
        ``old`` (e.g. FedNew duals). Leading axis must be the client axis."""
        if self.mask is None:
            return new
        shape = (-1,) + (1,) * (new.ndim - 1)
        return jnp.where(self.mask.reshape(shape) > 0, new, old)


class _NullComm:
    """No-transport stand-in: every optimizer routes through this when
    ``comm=None`` so the comm-aware code path is the only code path."""

    mask = None

    def uplink(self, name, x, wire_shape=None, ef_eligible=True,
               ef_reset=None):
        return x

    def downlink(self, name, x, wire_shape=None):
        return x

    def weights(self, p):
        return p

    def where_delivered(self, new, old):
        return new

    @property
    def memory_out(self):
        return {}

    @property
    def stats_out(self):
        return {}


NULL_COMM = _NullComm()


def probe_round(config: CommConfig, m: int, mask_dtype, plan: Dict[str, int],
                trace_round, *, full_cohort: bool):
    """One ``jax.eval_shape`` pass of the optimizer's round with a
    recording ``CommRound`` — nothing executes. Fills ``plan`` with the
    exact encoded bytes of every payload occurrence and returns the
    ``{payload_key: ShapeDtypeStruct}`` spec of EF-enabled lossy
    payloads (empty when error feedback is off). Shared by both round
    drivers: the sync session probes for EF shapes only, the async
    session also needs the byte plan before the first round runs.

    ``full_cohort`` selects the mask the real driver will pass
    (``None`` on the statically-full / lock-step path, a traced (m,)
    array otherwise) so the probe traces the same jaxpr structure.
    """
    spec: Dict[str, jax.ShapeDtypeStruct] = {}
    mask = None if full_cohort else jnp.zeros((m,), mask_dtype)
    if config.dynamics is not None and config.dynamics.threat is not None:
        # with a threat the sessions pack (delivery, attackers); probe
        # the same pytree structure
        mask = (mask, jnp.zeros((m,), mask_dtype))
    ck = jax.random.PRNGKey(0)  # noqa: RA001 — shape-only eval_shape probe; the key value never executes

    def probe(mask, ck):
        cr = CommRound(config, plan, mask, ck, ef_record=spec)
        return trace_round(cr)

    jax.eval_shape(probe, mask, ck)
    return spec


class CommSession:
    """Host-side per-trajectory comm state (cohorts, randomness, traces).

    Implements the ``Session`` driver protocol (``repro.comm.session``)
    for the synchronous lock-step clock: ``prepare`` runs the EF shape
    probe when error feedback is on, ``step`` draws a cohort, executes
    the jitted round, and accounts it, ``finalize`` folds the traces
    into the ``Transport`` axes ``History`` carries.
    """

    def __init__(
        self,
        config: CommConfig,
        m: int,
        mask_dtype=jnp.float64,  # noqa: RA005 — caller passes the problem dtype; the default only names the widest mask the goldens were recorded with
        keys: "jax.Array | None" = None,
        state0: Any = None,
        obs=NULL_TELEMETRY,
    ):
        self.config = config
        self.m = m
        self.obs = obs
        # keyed by payload occurrence (``name`` / ``name#i``, downlink
        # occurrences under ``down:name``): a round uplinking the same
        # name twice accumulates both, it does not overwrite the first
        # entry. The dict OBJECT is stable for the whole trajectory
        # (traced rounds close over it); ``begin_variant`` swaps its
        # CONTENTS when an adaptive sketch policy changes payload sizes,
        # so per-round accounting follows the active variant.
        self.plan: Dict[str, int] = {}
        self._plans: "Dict[Any, Dict[str, int]]" = {}
        self._variant: Any = _NO_VARIANT
        self.traces: "list[RoundTrace]" = []
        self.ef_memory: Dict[str, jax.Array] = {}
        self.keys = keys
        self._state = state0
        self._t = 0
        self._root = jax.random.PRNGKey(config.seed)  # noqa: RA001 — the transport root stream; repro.comm cannot import repro.core.base (cycle)
        self._mask_dtype = mask_dtype
        # static decision: identical jit trace structure for every round.
        # Churn and correlated outages invalidate the statically-full
        # path — the delivery mask must then be traced every round.
        dyn = config.dynamics
        self._always_full = (
            config.scheduler.is_full and config.channel.dropout_prob == 0.0
            and (dyn is None or not dyn.forces_mask))
        # dynamics bookkeeping (all inert when dynamics is None)
        self._elig_prev = None
        self._attacker_arr = None
        self.robust_stats: Dict[str, float] = {}
        # probe geometry: subclasses with a cohort axis narrower than m
        # (population mode) override these so abstract probes trace the
        # same shapes the real rounds will
        self._probe_m = m
        self._pending = None

    @property
    def _probe_full(self) -> bool:
        return self._always_full

    @property
    def bytes_up_per_client(self) -> int:
        """Exact encoded uplink bytes per delivering client per round,
        summed over every payload occurrence (valid after the first
        round has been traced)."""
        return plan_bytes(self.plan, down=False)

    @property
    def bytes_down_per_client(self) -> int:
        """Exact encoded broadcast bytes per scheduled client per round
        (``down:*`` plan entries; valid after the first trace)."""
        return plan_bytes(self.plan, down=True)

    # -- Session protocol ----------------------------------------------------
    def prepare(self, trace_round) -> None:
        """EF shape discovery (one abstract probe, only when requested —
        without EF the byte plan fills during the first real trace and
        the round's jaxpr stays untouched)."""
        if self.config.has_error_feedback:
            self.init_error_feedback(trace_round)

    def begin_variant(self, sig, trace_round) -> None:
        """Install the payload byte plan of the round variant about to
        run. The first variant keeps the lazy pre-policy behavior (the
        plan fills during the first real jit trace — no extra abstract
        interpretation on the common single-variant path); when a
        SECOND variant appears (adaptive-k changed payload sizes), the
        outgoing plan is snapshotted and the new variant is probed once
        (``jax.eval_shape`` — nothing executes) and cached, so
        ``end_round`` bills round-varying sizes truthfully even when a
        jitted trace is reused."""
        if self._variant is _NO_VARIANT:
            self._variant = sig
            return
        if sig == self._variant:
            return
        self._plans[self._variant] = dict(self.plan)
        plan = self._plans.get(sig)
        if plan is None:
            plan = {}
            probe_round(self.config, self._probe_m, self._mask_dtype, plan,
                        trace_round, full_cohort=self._probe_full)
            self._plans[sig] = plan
        self.plan.clear()
        self.plan.update(plan)
        self._variant = sig

    def comm_round(self, memory, mask, codec_key) -> CommRound:
        """The in-jit transport view ``run_rounds``'s round builder
        hands to the optimizer (called at trace time)."""
        return CommRound(self.config, self.plan, mask, codec_key,
                         memory=memory)

    def step(self, round_fn) -> Any:
        """One lock-step round: draw cohort, execute, account."""
        t = self._t
        span = self.obs.trace.span
        with span("session.schedule"):
            mask, ck = self.begin_round(t)
        with span("launch"):
            self._state, self.ef_memory, stats = round_fn(
                self._state, self.ef_memory, self.keys[t], mask, ck)
        with span("session.stats"):
            self._consume_stats(stats)
        with span("session.account"):
            self.end_round()
        self._t += 1
        count_round_calls(self.obs, 1)
        return self._state

    def _consume_stats(self, stats: Dict[str, Any]) -> None:
        """Drain the round's traced robust-aggregation counters into
        telemetry (empty dict — the no-dynamics case — is free)."""
        for stat_name, val in stats.items():
            v = float(val)
            self.robust_stats[stat_name] = \
                self.robust_stats.get(stat_name, 0.0) + v
            self.obs.metrics.counter(stat_name).inc(v)

    def _retire_ef(self, departed: np.ndarray) -> None:
        """Zero newly-departed clients' EF memory rows (dense layout)."""
        if self.ef_memory:
            z = jnp.asarray(departed)
            self.ef_memory = {k: v.at[z].set(0)
                              for k, v in self.ef_memory.items()}

    def _pack_threat(self, mask, ids=None):
        """Bundle the attacker indicator next to the delivery mask when
        a threat is active (``ids`` selects the cohort rows; dense
        sessions pass None and cache the (m,) indicator)."""
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

    def finalize(self) -> Transport:
        if self.obs.enabled:
            # final EF memory footprint (bytes held across all clients)
            ef_bytes = sum(
                int(np.prod(a.shape)) * jnp.dtype(a.dtype).itemsize
                for a in jax.tree_util.tree_leaves(self.ef_memory))
            self.obs.metrics.gauge("ef_memory_bytes").set(float(ef_bytes))
        return transport_from_traces(
            self.traces, ef_residuals=self.ef_residual_norms())

    def init_error_feedback(self, trace_round) -> "Dict[str, jax.Array]":
        """Discover EF payload shapes and zero-init the memory pytree.

        ``trace_round(comm_round)`` must invoke the optimizer's round
        exactly as the driver will; it is traced abstractly once (via
        ``probe_round`` — nothing executes), which notes the shape/dtype
        of every EF-enabled lossy payload. Payload shapes are static, so
        one probe suffices. With no EF-eligible payloads the memory
        stays an empty pytree and the jitted round's jaxpr is unchanged.
        """
        spec = probe_round(self.config, self._probe_m, self._mask_dtype, {},
                           trace_round, full_cohort=self._probe_full)
        self.ef_memory = feedback.init_memory(spec)
        return self.ef_memory

    def ef_residual_norms(self) -> "Dict[str, float]":
        """Per-payload Frobenius norm of the current EF residuals."""
        return feedback.residual_norms(self.ef_memory)

    def begin_round(self, t: int):
        """Draw this round's cohort + channel randomness.

        Returns ``(mask, codec_key)`` to pass into the jitted round:
        ``mask`` is None on the statically-full path (bit-exactness) or a
        float (m,) delivery mask otherwise.
        """
        k = jax.random.fold_in(self._root, t)
        k_sched, k_chan, k_codec = jax.random.split(k, 3)
        eligible = apply_churn(self, t)
        chan = self.config.channel_at(t)
        scheduled = self.config.scheduler.participants(
            k_sched, t, self.m, chan, eligible=eligible)
        draw = chan.draw(k_chan, self.m)
        delivered = scheduled & ~draw.dropout
        if scheduled.any() and not delivered.any():
            # every scheduled client dropped: the server re-polls one
            # (deterministically the lowest-index scheduled client) so
            # aggregation weights stay well-defined
            delivered = np.zeros_like(scheduled)
            delivered[int(np.argmax(scheduled))] = True
        self._pending = (t, scheduled, delivered, draw)
        if self._always_full:
            return self._pack_threat(None), k_codec
        mask = jnp.asarray(delivered, dtype=self._mask_dtype)
        return self._pack_threat(mask), k_codec

    def end_round(self) -> RoundTrace:
        """Account the round just executed (reads the traced byte plan —
        both directions carry real encoded sizes, downlink included)."""
        t, scheduled, delivered, draw = self._pending
        per_client = float(self.bytes_up_per_client)
        bytes_up = per_client * delivered.astype(np.float64)
        bytes_down = (float(self.bytes_down_per_client)
                      * scheduled.astype(np.float64))
        sim = self.config.channel_at(t).round_time(
            draw, delivered, bytes_up, bytes_down)
        trace = RoundTrace(
            round=t,
            scheduled=scheduled,
            delivered=delivered,
            straggler=draw.straggler & delivered,
            bytes_up=bytes_up,
            bytes_down=bytes_down,
            sim_time_s=sim,
        )
        self.traces.append(trace)
        self._pending = None
        self._count_corrupted(delivered, None)
        if self.obs.enabled:
            self._observe(trace)
        return trace

    def _count_corrupted(self, delivered: np.ndarray,
                         ids: "np.ndarray | None") -> None:
        """Host-side tally of corrupted uploads that reached the server
        this round (attacker AND delivered client-rounds)."""
        dyn = self.config.dynamics
        if dyn is None or dyn.threat is None:
            return
        att = dyn.threat.attacker_mask(
            np.arange(self.m) if ids is None else ids)
        n_bad = float((att & delivered).sum())
        self.robust_stats["uploads_corrupted"] = \
            self.robust_stats.get("uploads_corrupted", 0.0) + n_bad
        self.obs.metrics.counter("uploads_corrupted").inc(n_bad)

    def _observe(self, trace: RoundTrace) -> None:
        """Populate per-round telemetry (host-side, after the round ran)."""
        mt = self.obs.metrics
        up = float(trace.bytes_up.sum())
        down = float(trace.bytes_down.sum())
        mt.counter("bytes_up").inc(up)
        mt.counter("bytes_down").inc(down)
        mt.counter("scheduled_client_rounds").inc(
            float(trace.scheduled.sum()))
        mt.counter("delivered_client_rounds").inc(
            float(trace.delivered.sum()))
        mt.counter("dropped_client_rounds").inc(
            float((trace.scheduled & ~trace.delivered).sum()))
        mt.counter("straggler_client_rounds").inc(
            float(trace.straggler.sum()))
        self.obs.annotate(
            bytes_up=up, bytes_down=down,
            delivered=int(trace.delivered.sum()),
            dropped=int((trace.scheduled & ~trace.delivered).sum()),
            sim_time_s=float(trace.sim_time_s))


class PopulationCommSession(CommSession):
    """Synchronous driver over a lazy ``ClientPopulation``.

    Per round: sample the cohort's client *ids* from the population
    (``Scheduler.sample_ids`` — same draw, and therefore the same
    cohort, as the dense ``participants`` mask under one seed),
    materialize exactly those ``(c, n_shard, M)`` shards, draw the
    cohort's channel coins *per client id*, gather the cohort's EF rows
    from the bounded hot-set store, run the one jitted cohort round, and
    scatter the updated rows back. Nothing ``(m,)``-shaped is ever
    allocated except O(m) host-side metadata (shard sizes, scheduler
    draws), so m ~ 10⁵ populations with q ~ 10⁻³ participation run in
    cohort-bounded memory.

    The round function signature gains the cohort problem as its first
    (traced pytree) argument; since every cohort of one scheduler has
    the same static size ``c`` and pad width, round 2..T reuse round 1's
    jaxpr — cohort membership changes never retrace.
    """

    def __init__(self, config: CommConfig, population, *,
                 mask_dtype=jnp.float64, keys=None, state0=None,  # noqa: RA005 — caller passes the problem dtype; default matches the recorded goldens
                 obs=NULL_TELEMETRY, client_mesh=None):
        super().__init__(config, population.m, mask_dtype=mask_dtype,
                         keys=keys, state0=state0, obs=obs)
        self.population = population
        self.cohort_size = config.scheduler.cohort_size(population.m)
        self.client_mesh = client_mesh
        self.ef_store: "feedback.BoundedMemory | None" = None
        # probes must trace cohort-shaped rounds, not (m,) ones
        self._probe_m = self.cohort_size
        self._pending_ids = None
        self._pending_real = None

    @property
    def _probe_full(self) -> bool:
        # every cohort member is scheduled by construction; the mask only
        # carries dropout, so no-dropout channels keep the mask=None
        # (bit-exact identity) path even under q < 1 sampling. Churn
        # (cohorts padded below the static size) and outages force it.
        dyn = self.config.dynamics
        return (self.config.channel.dropout_prob == 0.0
                and (dyn is None or not dyn.forces_mask))

    def _materialize(self, ids):
        cohort = self.population.materialize(ids)
        if self.client_mesh is not None:
            from repro.sharding.rules import shard_cohort

            cohort = shard_cohort(self.client_mesh, cohort)
        return cohort

    def init_error_feedback(self, trace_round):
        spec = probe_round(self.config, self._probe_m, self._mask_dtype, {},
                           trace_round, full_cohort=self._probe_full)
        capacity = self.config.ef_capacity
        if capacity is None:
            capacity = min(self.m, 8 * self.cohort_size)
        capacity = max(capacity, self.cohort_size)
        self.ef_store = feedback.BoundedMemory(spec, capacity)
        self.ef_memory = {}
        return self.ef_memory

    def begin_round(self, t: int):
        """Sample cohort ids + per-id channel coins for round ``t``.

        The key schedule is byte-identical to the dense driver's
        (``fold_in(root, t)`` split into sched/chan/codec streams), so a
        population run and a dense run of the same seed schedule the
        same cohorts, and so does the async driver's version stream.
        """
        k = jax.random.fold_in(self._root, t)
        k_sched, k_chan, k_codec = jax.random.split(k, 3)
        eligible = apply_churn(self, t)
        chan = self.config.channel_at(t)
        ids = self.config.scheduler.sample_ids(
            k_sched, t, self.m, chan, eligible=eligible)
        n_real = len(ids)
        if n_real < self.cohort_size:
            # churn shrank the eligible set below the static cohort
            # size: pad with the first sampled id under a zero delivery
            # mask so every round keeps the one traced jaxpr
            ids = np.concatenate([
                ids, np.full(self.cohort_size - n_real, ids[0],
                             dtype=np.int64)])
        draw = chan.draw_for(k_chan, ids)
        delivered = ~draw.dropout
        delivered[n_real:] = False
        if not delivered.any():
            # every sampled client dropped: re-poll the lowest id so
            # aggregation weights stay well-defined (dense-path rule)
            delivered = np.zeros_like(delivered)
            delivered[0] = True
        scheduled = np.ones_like(delivered)
        scheduled[n_real:] = False
        self._pending = (t, scheduled, delivered, draw)
        self._pending_ids = ids
        self._pending_real = n_real
        if self._probe_full:
            return ids, self._pack_threat(None, ids), k_codec
        mask = jnp.asarray(delivered, dtype=self._mask_dtype)
        return ids, self._pack_threat(mask, ids), k_codec

    def step(self, round_fn) -> Any:
        """One cohort round: sample ids, materialize, execute, account.

        ``round_fn(cohort, state, memory, key, mask, codec_key)`` — the
        population-mode round signature (cohort problem is a traced
        pytree argument, so one jaxpr serves every cohort).
        """
        t = self._t
        span = self.obs.trace.span
        with span("session.schedule"):
            ids, mask, ck = self.begin_round(t)
        with span("session.materialize"):
            cohort = self._materialize(ids)
            memory = self.ef_store.gather(ids) if self.ef_store else {}
        with span("launch"), client_mesh_scope(self.client_mesh):
            self._state, mem_out, stats = round_fn(
                cohort, self._state, memory, self.keys[t], mask, ck)
        with span("session.stats"):
            self._consume_stats(stats)
        if self.ef_store is not None:
            with span("session.materialize"):
                # real ids only: churn-padded rows duplicate ids[0] and
                # must not race its real row on scatter
                self.ef_store.scatter(ids[:self._pending_real], mem_out)
        with span("session.account"):
            self.end_round()
        self._t += 1
        count_round_calls(self.obs, 1)
        return self._state

    def _retire_ef(self, departed: np.ndarray) -> None:
        """Departed clients leave the EF hot set (their slot is freed
        and zeroed — deterministic retirement, not LRU luck)."""
        if self.ef_store is not None:
            self.ef_store.retire(departed)

    def end_round(self) -> RoundTrace:
        t, scheduled, delivered, draw = self._pending
        ids = self._pending_ids
        per_client = float(self.bytes_up_per_client)
        bytes_up = per_client * delivered.astype(np.float64)
        bytes_down = (float(self.bytes_down_per_client)
                      * scheduled.astype(np.float64))
        sim = self.config.channel_at(t).round_time_for(
            ids, self.m, draw, delivered, bytes_up, bytes_down)
        trace = RoundTrace(
            round=t,
            scheduled=scheduled,
            delivered=delivered,
            straggler=draw.straggler & delivered,
            bytes_up=bytes_up,
            bytes_down=bytes_down,
            sim_time_s=sim,
            ids=ids,
            population=self.m,
        )
        self.traces.append(trace)
        self._pending = None
        self._pending_ids = None
        self._pending_real = None
        self._count_corrupted(delivered, ids)
        if self.obs.enabled:
            self._observe(trace)
        return trace

    def finalize(self) -> Transport:
        if self.obs.enabled:
            ef_bytes = self.ef_store.nbytes if self.ef_store else 0
            self.obs.metrics.gauge("ef_memory_bytes").set(float(ef_bytes))
            if self.ef_store is not None:
                self.obs.metrics.gauge("ef_hot_set_evictions").set(
                    float(self.ef_store.evictions))
        return transport_from_traces(
            self.traces, ef_residuals=self.ef_residual_norms())

    def ef_residual_norms(self) -> "Dict[str, float]":
        if self.ef_store is not None:
            return self.ef_store.residual_norms()
        return {}
