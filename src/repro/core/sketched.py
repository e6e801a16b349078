"""Sketched Newton-type federated baselines: FedNS and FedNDES (Li 2024).

FedNS: each client sketches its Hessian *square root* on the data axis —
uploads ``S_j A_j`` of size (k, M) — so the server reconstructs
``H ~= sum_j p_j (S_j A_j)^T (S_j A_j) + lam I``. Uplink O(kM).

FedNDES: FedNS with the sketch size chosen adaptively from the empirical
effective dimension d_lambda of the global Hessian (dimension-efficient
sketching), keeping the same O(kM) uplink at a smaller k.

Both draw their per-client data-axis sketches through a ``SketchPolicy``
(``repro.core.sketch_policy``): the default ``"srht"`` redraws every
round (bit-identical to the pre-policy code), while ``"srht:fixed"`` /
``"srht:rotate=R"`` persist each client's basis across rounds — which is
what makes the O(kM) ``sa`` payload eligible for error feedback.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.comm import NULL_COMM
from repro.core.base import FederatedOptimizer, OptState
from repro.core.sketch_policy import (
    SketchPolicy,
    adaptive_k,
    as_policy,
    loss_effective_dimension,
)
from repro.sharding.rules import vmap_clients


class FedNS(FederatedOptimizer):
    """Federated Newton sketch with per-client data-axis sketches."""

    name = "fedns"

    def __init__(self, k: int, mu: float = 1.0,
                 sketch: "str | SketchPolicy" = "srht"):
        self.policy = as_policy(sketch, k=k)
        if self.policy.adaptive:
            # nothing here ramps k mid-run (the guard signal is a FLeNS
            # construct); silently running constant-k would misrepresent
            # the request. FedNDES provides effective-dimension sizing.
            raise ValueError(
                f"{type(self).__name__} does not support adaptive-k sketch "
                f"policies ({self.policy.spec()!r}); use FLeNS for the "
                f"guard-driven ramp or FedNDES for effective-dimension "
                f"sizing")
        self.mu = mu

    @property
    def k(self) -> int:
        return self.policy.k

    @k.setter
    def k(self, value: int) -> None:
        self.policy = self.policy.with_k(value)

    def init(self, problem, w0):
        return {"w": w0, "t": jnp.asarray(0, jnp.int32)}

    def round(self, problem, state: OptState, key, comm=None) -> OptState:
        comm = NULL_COMM if comm is None else comm
        w, t = state["w"], state["t"]
        # clients sketch at the decoded broadcast (per-client data-axis
        # sketches are drawn locally — no basis broadcast needed); the
        # server steps from its exact iterate
        w_bcast = comm.downlink("w", w)
        p = comm.weights(problem.client_weights)
        gs = comm.uplink("grad", problem.local_grad(w_bcast))
        g = jnp.einsum("j,jm->m", p, gs)
        a = problem.local_hess_sqrt(w_bcast)  # (m, n_shard, M)
        n_shard = a.shape[1]
        # schedule-aware basis stream, split per client: fresh schedules
        # ride the per-round key; fixed/rotating schedules hold each
        # client's S_j constant within a rotation epoch
        keys = jax.random.split(self.policy.basis_key(key, t), problem.m)

        def client(aj, kj):
            s = self.policy.materialize(kj, n_shard, dtype=aj.dtype)
            # S acts on the data axis: (k, n) @ (n, M) -> (k, M)
            return s.apply(aj.T).T

        sa = vmap_clients(client)(a, keys)  # (m, k, M)
        # EF eligibility flows from the schedule: a fresh data-axis
        # basis makes cross-round memory meaningless, a fixed/rotating
        # one keeps the (k, M) payload in a stable coordinate system —
        # with the residual reset whenever a rotation draws a new basis
        sa = comm.uplink("sa", sa,
                         ef_eligible=self.policy.basis_persistent(),
                         ef_reset=self.policy.ef_reset(t))
        h_tilde = jnp.einsum("j,jka,jkb->ab", p, sa, sa)
        h_tilde = h_tilde + problem.lam * jnp.eye(problem.dim, dtype=w.dtype)
        return {"w": w - self.mu * jnp.linalg.solve(h_tilde, g), "t": t + 1}

    def uplink_floats(self, problem) -> int:
        return self.k * problem.dim + problem.dim


class FedNDES(FedNS):
    """FedNS with dimension-efficient (effective-dimension) sketch size.

    ``init`` estimates d_lambda at w0 and sets k = ceil(c * d_lambda),
    clipped to [k_min, n_shard]; thereafter behaves like FedNS.
    (In deployment the estimate comes from a preliminary sketched round;
    the simulator computes it exactly — same k, zero extra rounds.)
    """

    name = "fedndes"

    def __init__(self, mu: float = 1.0, sketch: "str | SketchPolicy" = "srht",
                 c: float = 2.0, k_min: int = 8):
        super().__init__(k=k_min, mu=mu, sketch=sketch)
        self.c = c
        self.k_min = k_min

    def init(self, problem, w0):
        d_lam = loss_effective_dimension(problem, w0)
        n_shard = problem.X.shape[1]
        self.k = adaptive_k(d_lam, c=self.c, k_min=self.k_min, k_max=n_shard)
        return super().init(problem, w0)
