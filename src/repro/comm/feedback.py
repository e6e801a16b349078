"""Client-side error-feedback memory for lossy uplink codecs.

Biased compressors (top-k above all, but also quantizers) break the
fixed point of federated aggregation: every round throws away payload
mass, the discarded part never reaches the server, and the iterates
stall at a compression floor — the convergence gap
``examples/edge_clients.py`` measures under lossy codecs + partial
participation. Error feedback fixes this by making each client
*remember* what the codec dropped and re-offer it in later rounds. Two
standard recursions are implemented, per payload name and per client,
both with zero-initialized memory and identical wire formats (EF never
changes the encoded byte count — only which values ride in it):

``ef21`` (default) — compressed-estimate tracking, Richtárik et al.
(2021); the compressed-Hessian learning of FedNL (Safaryan et al.,
2022) is the same mechanism specialized to Hessians. The memory ``g_t``
is the client's current payload estimate (mirrored by the server in a
real deployment); the wire carries only the compressed *innovation*:

    transmit   c_t     = C(x_t - g_t)
    estimate   g_{t+1} = g_t + c_t          (what the server now holds)

On a fixed payload stream the residual ``x - g_t`` contracts
geometrically under any contractive ``C`` (``g_t -> x``), so the
server-side payload converges to the uncompressed one — and because the
server consumes the smooth estimate ``g_{t+1}`` rather than a raw
compressed payload, per-round noise is far lower than ``ef14``.

``ef14`` — classic error compensation (Seide et al. 2014; Stich et al.
2018), the ``e_{t+1} = e_t + x - C(x + e_t)`` recursion:

    transmit   m_t     = C(x_t + e_t)       (the compensated payload)
    remember   e_{t+1} = (x_t + e_t) - m_t  (what C dropped this time)

The residual stays bounded (not contracting) and the *time-averaged*
transmitted payload converges to the time-averaged true payload; the
per-round decode is spikier than ``ef21``'s, which matters for
Newton-type methods whose guards reject noisy steps.

Traced-memory design
--------------------
``CommRound.uplink`` runs inside the jitted round, so the memories
cannot live on a Python object that mutates per round — they form a
pytree of ``(m, ...)`` arrays (one leaf per EF-active payload
occurrence, stacked over clients) that the round driver threads through
the jitted step alongside the optimizer state:

  * payload shapes are discovered at trace time: ``CommSession.
    init_error_feedback`` runs one ``jax.eval_shape`` probe of the round
    with a recording ``CommRound``, then zero-initializes one ``(m, ...)``
    leaf per EF-active payload;
  * ``CommRound`` receives the memory pytree, ``uplink`` applies the
    selected recursion and writes the new memory into
    ``CommRound.memory_out``; ``run_rounds`` carries the updated pytree
    into the next round;
  * dropped clients never observe the round, so their memory rows are
    frozen via the delivery mask (``CommRound.where_delivered``, the
    same gate that protects per-client optimizer state and zeroes their
    aggregation weight);
  * under the asynchronous driver (``repro.comm.async_driver``) the same
    gate keys memory updates to *actual delivery*: one server commit may
    replay several version-grouped rounds, each advancing only the
    memory rows of the clients whose uploads that commit consumed, so a
    slow client's memory stays put across the server steps its payload
    spends in flight;
  * payloads whose codec is lossless (identity, bare sympack) allocate
    no memory at all, so the identity-codec path keeps a bit-identical
    jaxpr: the memory pytree is empty and ``uplink`` is unchanged.

Eligibility: EF memory only makes sense for payloads expressed in a
coordinate system that persists across rounds. Sketch-basis payloads
(FLeNS's ``h_sk``/``sg``, FedNS's ``sa``) are re-expressed in a fresh
random basis every round — cross-round memory would mix incompatible
bases and actively corrupt the estimate — so those call sites pass
``uplink(..., ef_eligible=False)`` and are skipped, exactly like
``wire_shape`` this is algorithm knowledge declared at the uplink.

Enable with ``CommConfig(error_feedback=True)`` (all eligible lossy
payloads), a collection of payload names, or a ``{name: bool}`` dict
with an optional ``"default"`` entry; pick the recursion with
``CommConfig(ef_variant="ef21"|"ef14")``.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.comm.codecs import Codec
from repro.sharding.rules import vmap_clients

EF_VARIANTS = ("ef21", "ef14")


def ef_requested(error_feedback: Any, payload: str) -> bool:
    """Resolve the per-payload gate from a ``CommConfig.error_feedback``
    spec: ``bool`` (all/none), a collection of payload names, or a
    ``{name: bool}`` dict with an optional ``"default"`` fallback."""
    if isinstance(error_feedback, bool):
        return error_feedback
    if isinstance(error_feedback, str):  # one payload name, not chars
        return payload == error_feedback
    if isinstance(error_feedback, dict):
        return bool(error_feedback.get(
            payload, error_feedback.get("default", False)))
    return payload in error_feedback


def any_ef_requested(error_feedback: Any) -> bool:
    """Whether the spec can enable EF for at least one payload name."""
    if isinstance(error_feedback, bool):
        return error_feedback
    if isinstance(error_feedback, str):
        return bool(error_feedback)
    if isinstance(error_feedback, dict):
        return any(bool(v) for v in error_feedback.values())
    return len(tuple(error_feedback)) > 0


def compensate(
    codec: Codec, keys: jax.Array, x: jax.Array, mem: jax.Array,
    variant: str = "ef21",
) -> "tuple[jax.Array, jax.Array]":
    """One error-feedback step on a stacked ``(m, ...)`` payload.

    Returns ``(decoded, new_mem)``: what the server reconstructs this
    round and the client memory to carry into the next round. ``keys``
    is ``(m, 2)`` per-client codec randomness (ignored by deterministic
    codecs).

    * ``ef21``: ``mem`` is the payload estimate ``g``; the wire carries
      ``C(x - g)`` and both sides advance to ``g + C(x - g)`` — decoded
      payload and new memory coincide.
    * ``ef14``: ``mem`` is the residual ``e``; the wire carries
      ``C(x + e)`` and the client keeps ``(x + e) - C(x + e)``.
    """
    if variant == "ef21":
        innovation = vmap_clients(codec.roundtrip)(keys, x - mem)
        estimate = mem + innovation
        return estimate, estimate
    if variant == "ef14":
        compensated = x + mem
        decoded = vmap_clients(codec.roundtrip)(keys, compensated)
        return decoded, compensated - decoded
    raise ValueError(
        f"unknown error-feedback variant {variant!r}; want one of {EF_VARIANTS}")


def init_memory(spec: "Dict[str, jax.ShapeDtypeStruct]") -> "Dict[str, jax.Array]":
    """Zero memories from a discovered ``{payload_key: ShapeDtypeStruct}``."""
    return {name: jnp.zeros(s.shape, s.dtype) for name, s in spec.items()}


def residual_norms(memory: "Dict[str, jax.Array]") -> "Dict[str, float]":
    """Host-side diagnostic: per-payload Frobenius norm of the stacked
    memory (summed over clients). For ``ef21`` this is the estimate
    magnitude; for ``ef14`` the accumulated residual."""
    return {name: float(jnp.linalg.norm(e)) for name, e in memory.items()}


class BoundedMemory:
    """LRU-bounded EF row store for population-scale runs.

    Dense EF keeps one memory row per client per payload — ``O(m)``
    state that is exactly what population mode must not materialize.
    ``BoundedMemory`` keeps dense rows only for a *hot set* of
    ``capacity`` client ids with LRU eviction; a client outside the hot
    set re-enters with a **zero row** (on-sample reset — the FedBuff-
    style tradeoff: long-tail clients participate so rarely that their
    stale residual is worth less than its footprint).

    Per round the session calls ``gather(ids)`` to assemble the
    cohort-stacked ``(c, ...)`` memory pytree the jitted round consumes
    (assigning hot-set slots to new ids, evicting the least recently
    sampled), and ``scatter(ids, memory)`` afterwards to write the
    round's ``memory_out`` rows back into the store. Both are host-side
    O(c); total footprint is ``capacity × Σ row_bytes`` regardless of m,
    reported by the session through the existing ``repro.obs``
    ``ef_memory_bytes`` gauge.
    """

    def __init__(self, spec: "Dict[str, jax.ShapeDtypeStruct]", capacity: int):
        # ``spec`` rows are cohort-stacked (leading axis = cohort); the
        # store keeps ``capacity`` rows of each payload's row shape
        if capacity < 1:
            raise ValueError(f"BoundedMemory capacity must be >= 1, "
                             f"got {capacity}")
        self.capacity = int(capacity)
        self._bufs = {
            name: jnp.zeros((self.capacity,) + tuple(s.shape[1:]), s.dtype)
            for name, s in spec.items()
        }
        self._slot_of: "dict[int, int]" = {}  # client id -> slot (LRU order)
        self._free: "list[int]" = []  # slots released by retire()
        self.evictions = 0  # long-tail resets observed so far
        self.retirements = 0  # churn-departed rows dropped so far

    @property
    def payload_names(self):
        return tuple(self._bufs)

    @property
    def nbytes(self) -> int:
        """Bounded footprint: capacity × Σ per-payload row bytes."""
        return int(sum(b.nbytes for b in self._bufs.values()))

    def _assign(self, ids) -> "tuple[jnp.ndarray, list[int]]":
        """Slots for ``ids`` (LRU-refreshed), plus newly assigned slots."""
        fresh = []
        for cid in ids:
            cid = int(cid)
            if cid in self._slot_of:
                # refresh recency
                self._slot_of[cid] = self._slot_of.pop(cid)
                continue
            if self._free:
                slot = self._free.pop()
            elif len(self._slot_of) < self.capacity:
                # invariant: slots [0, len(_slot_of) + len(_free)) are
                # allocated, and _free holds the retired ones — so the
                # next virgin slot is the allocation high-water mark
                slot = len(self._slot_of) + len(self._free)
            else:
                # evict the least recently sampled id (oldest dict entry)
                victim = next(iter(self._slot_of))
                slot = self._slot_of.pop(victim)
                self.evictions += 1
            self._slot_of[cid] = slot
            fresh.append(slot)
        return (jnp.asarray([self._slot_of[int(c)] for c in ids],
                            dtype=jnp.int32), fresh)

    def gather(self, ids) -> "Dict[str, jax.Array]":
        """Cohort-stacked ``(c, ...)`` memory rows for ``ids``.

        Ids new to the hot set (or evicted since last sampled) read
        zeros — the on-sample reset.
        """
        if len(ids) > self.capacity:
            raise ValueError(
                f"cohort of {len(ids)} exceeds EF hot-set capacity "
                f"{self.capacity}; raise CommConfig.ef_capacity")
        slots, fresh = self._assign(ids)
        if fresh:
            z = jnp.asarray(fresh, dtype=jnp.int32)
            self._bufs = {name: buf.at[z].set(0)
                          for name, buf in self._bufs.items()}
        return {name: buf[slots] for name, buf in self._bufs.items()}

    def scatter(self, ids, memory: "Dict[str, jax.Array]") -> None:
        """Write the round's updated rows back (ids must be unique)."""
        if not self._bufs:
            return
        slots = jnp.asarray([self._slot_of[int(c)] for c in ids],
                            dtype=jnp.int32)
        self._bufs = {name: buf.at[slots].set(memory[name][: len(ids)])
                      for name, buf in self._bufs.items()}

    def retire(self, ids) -> int:
        """Drop hot-set rows for churn-departed clients.

        Zeros the rows (so a recycled slot starts clean even if a later
        ``gather`` misses it) and releases the slots for reuse. Ids not
        in the hot set are ignored. Returns the number retired.
        """
        gone = [int(c) for c in ids if int(c) in self._slot_of]
        if not gone:
            return 0
        slots = [self._slot_of.pop(c) for c in gone]
        z = jnp.asarray(slots, dtype=jnp.int32)
        self._bufs = {name: buf.at[z].set(0)
                      for name, buf in self._bufs.items()}
        self._free.extend(slots)
        self.retirements += len(gone)
        return len(gone)

    def residual_norms(self) -> "Dict[str, float]":
        return residual_norms(self._bufs)
