"""FLeNS — Federated Learning with Enhanced Nesterov-Newton Sketch.

The paper's algorithm (Algorithm 1), made dimensionally consistent as
described in DESIGN.md §1.1:

  1. Nesterov look-ahead       v_t = w_t + beta_t (w_t - w_{t-1})
  2. Every client j computes   g_j(v_t)   and the two-sided sketch
                               H~_j = S H_j(v_t) S^T  in R^{k x k},
     with the SAME per-round SRHT S (the server broadcasts the O(1) seed).
     Efficient form: H_j = A_j^T A_j + lam I  (A_j = sqrt-Hessian rows),
     so  H~_j = (A_j S^T)^T (A_j S^T) + lam * S S^T  — never materializes
     the M x M Hessian; cost O(n_j M log M) via the FWHT.
  3. Uplink per client: H~_j (k^2 floats) + S g_j (k floats)  ->  O(k^2).
  4. Server aggregates and takes the sketched-subspace Newton step
         delta = S^T (H~ + lam_damp I)^{-1} (S g),
         w_{t+1} = v_t - mu * delta.

The sketch is a first-class scheduled object (``repro.core.
sketch_policy``): ``sketch="srht"`` reproduces the paper's fresh
per-round basis bit-for-bit, while ``"srht:fixed"`` / ``"srht:rotate=R"``
persist the basis across rounds (making the sketch uplinks EF-eligible)
and ``"...:adaptive"`` ramps k within declared bounds on guard rejects.

``variant="plus"`` is the beyond-paper FLeNS+ of DESIGN.md §1.2: clients
additionally upload the raw gradient (O(M), the same uplink order as
FedAvg) and the server adds a first-order step in the orthogonal
complement of the sketch subspace, removing the sketch floor:
         w_{t+1} = v_t - mu * delta - eta * (g - P_S g),
with P_S the exact projector onto range(S^T).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.comm import NULL_COMM
from repro.core.base import FederatedOptimizer, OptState
from repro.core.federated import FederatedProblem
from repro.core.sketch_policy import (
    SketchPolicy,
    as_policy,
    loss_effective_dimension,
)
from repro.sharding.rules import vmap_clients


# lower bound of the guard's backtracking trust scale: rejects halve the
# scale down to this floor, accepts double it back (capped at 1)
_MIN_TRUST_SCALE = 1.0 / 64.0


class FLeNS(FederatedOptimizer):
    name = "flens"

    def __init__(
        self,
        k: int,
        mu: float = 1.0,
        beta: float | str = "paper",
        sketch: "str | SketchPolicy" = "srht",
        lam_damp: float = 1e-8,
        variant: str = "paper",  # "paper" | "plus"
        eta: float | None = None,  # complement step size (plus); None -> 1/L1
        step_from: str = "v",  # "v" (standard accelerated) | "w" (paper literal)
        restart: bool = True,  # function-value adaptive momentum restart
    ):
        # the sketch is a scheduled first-class object: "srht" (fresh,
        # the paper's per-round basis), "srht:fixed", "srht:rotate=8",
        # "gaussian:adaptive", ... — see repro.core.sketch_policy
        self.policy = as_policy(sketch, k=k)
        self.mu = mu
        self.beta = beta
        self.lam_damp = lam_damp
        self.variant = variant
        self.eta = eta
        self.step_from = step_from
        self.restart = restart
        self._guard_scale = 1.0  # host-side adaptive-k reject detector
        if self.policy.adaptive and not restart:
            # the ramp is driven by guard rejections; without the guard
            # the trust scale never moves and "adaptive" would silently
            # degenerate to constant-k
            raise ValueError(
                "adaptive-k sketch policies need the guard (restart=True): "
                "the k ramp is driven by its rejected steps")
        if variant == "plus":
            self.name = "flens_plus"

    @property
    def k(self) -> int:
        return self.policy.k

    @k.setter
    def k(self, value: int) -> None:
        self.policy = self.policy.with_k(value)

    # -- momentum schedule ---------------------------------------------------
    def _beta_value(self, problem: FederatedProblem, w0: jax.Array) -> float:
        if isinstance(self.beta, (int, float)):
            return float(self.beta)
        h = problem.global_hessian(w0)
        evals = jnp.linalg.eigvalsh(h)
        l1 = float(evals[-1])
        gam = float(jnp.maximum(evals[0], problem.lam))
        if self.beta == "paper":  # Assumption A7: (L1 - gamma)/(L1 + gamma)
            return (l1 - gam) / (l1 + gam)
        if self.beta == "sqrt":  # classical accelerated-GD schedule
            sl, sg = l1 ** 0.5, gam ** 0.5
            return (sl - sg) / (sl + sg)
        raise ValueError(f"unknown beta rule {self.beta!r}")

    def init(self, problem, w0):
        if self.policy.adaptive:
            # adaptive-k: start from the effective dimension of the loss
            # Hessian clipped into the declared (k_min, k_max); the
            # guard-driven ramp happens in round_signature as the
            # trajectory unfolds
            d_eff = loss_effective_dimension(problem, w0)
            self.policy = self.policy.resolved(d_eff, cap=problem.dim)
            self._guard_scale = 1.0
        beta = self._beta_value(problem, w0)
        state = {
            "w": w0,
            "w_prev": w0,
            "beta": jnp.asarray(beta, w0.dtype),
            "loss": problem.global_value(w0),
            "scale": jnp.asarray(1.0, w0.dtype),
            # round counter: the rotation-epoch input of the sketch
            # schedule (and a no-op for the default fresh basis)
            "t": jnp.asarray(0, jnp.int32),
        }
        if self.variant == "plus":
            # eta lives in the state dict (NOT on the optimizer instance):
            # one optimizer object stays reusable across problems
            if self.eta is None:
                h = problem.global_hessian(w0)
                l1 = float(jnp.linalg.eigvalsh(h)[-1])
                eta = 1.0 / l1
            else:
                eta = float(self.eta)
            state["eta"] = jnp.asarray(eta, w0.dtype)
        return state

    # -- host-side adaptive-k hook (run_rounds calls this pre-round) ---------
    def round_signature(self, round_idx: int, state: OptState):
        if not self.policy.adaptive:
            return None
        # the FLeNS guard halves the trust scale on every rejected step:
        # a scale drop since the last round means the sketched model was
        # too coarse — ramp k (doubling, capped at k_max). At the scale
        # floor a reject no longer drops the value (max() pins it), but
        # sitting AT the floor still means the last round rejected — an
        # accept would have doubled away from it — so it counts too.
        # k never shrinks; the signature re-traces and re-bills.
        scale = float(state.get("scale", 1.0))
        rejected = scale < self._guard_scale or scale <= _MIN_TRUST_SCALE
        if round_idx > 0 and rejected:
            self.policy = self.policy.ramped()
        self._guard_scale = scale
        return ("flens_k", self.policy.k)

    # -- one communication round ----------------------------------------------
    def round(self, problem, state: OptState, key, comm=None) -> OptState:
        comm = NULL_COMM if comm is None else comm
        w, w_prev, beta = state["w"], state["w_prev"], state["beta"]
        t = state["t"]
        dim = problem.dim
        dtype = w.dtype

        # (1) Nesterov look-ahead (common knowledge: server-known w, w_prev)
        v = w + beta * (w - w_prev)

        # server broadcast: the look-ahead iterate clients compute on,
        # plus the O(1) sketch basis key (lossless by default — a
        # compressed key would desynchronize the shared basis). Fresh
        # schedules broadcast the per-round driver key; fixed/rotating
        # schedules broadcast the epoch key from the policy's own seed
        # stream, which is what keeps S identical across the rounds of
        # one epoch. The server keeps the exact v for its own step;
        # only client-side quantities see the decoded broadcast.
        v_bcast = comm.downlink("w", v)
        skey = comm.downlink("seed", self.policy.basis_key(key, t))

        # (2) the round's shared sketch, per the declared schedule
        s = self.policy.materialize(skey, dim, dtype=dtype)
        sst = s.apply(s.apply_t(jnp.eye(self.k, dtype=dtype)))  # S S^T (k,k)

        # client-side: local gradient + two-sided sketched Hessian
        gs = self._local_grads_at(problem, v_bcast)  # (m, M)
        a = self._local_hess_sqrt_at(problem, v_bcast)  # (m, n_shard, M)

        def client_sketch(aj):
            bj = s.apply(aj)  # A_j S^T : (n_shard, k)
            return bj.T @ bj  # (k, k), + lam S S^T added after aggregation

        h_sk = vmap_clients(client_sketch)(a)  # (m, k, k)
        sg = vmap_clients(s.apply)(gs)  # (m, k)

        # uplink: the k×k sketched Hessian (symmetric — sympack applies)
        # and the sketched gradient flow through the transport codecs.
        # EF eligibility flows from the schedule: both payloads live in
        # the basis S_t, so cross-round memory is meaningful exactly
        # when the basis persists across rounds (fixed/rotating
        # schedules) and meaningless for a fresh per-round draw. A
        # rotating schedule additionally resets the residual the round
        # the basis rotates — memory from the previous epoch lives in
        # the old basis.
        persistent = self.policy.basis_persistent()
        reset = self.policy.ef_reset(t)
        h_sk = comm.uplink("h_sk", h_sk, ef_eligible=persistent,
                           ef_reset=reset)
        sg = comm.uplink("sg", sg, ef_eligible=persistent, ef_reset=reset)

        # (3)+(4) server aggregation and sketched-subspace Newton step
        p = comm.weights(problem.client_weights)
        h_tilde = jnp.einsum("j,jab->ab", p, h_sk) + problem.lam * sst
        g_sk = jnp.einsum("j,jk->k", p, sg)
        eye_k = jnp.eye(self.k, dtype=dtype)
        delta_k = jnp.linalg.solve(h_tilde + self.lam_damp * eye_k, g_sk)
        delta = s.apply_t(delta_k)

        base = v if self.step_from == "v" else w
        scale = state.get("scale", jnp.asarray(1.0, dtype))
        w_next = base - scale * self.mu * delta

        if self.variant == "plus":
            gs_hat = comm.uplink("grad", gs)  # full gradient (O(M) uplink)
            g = jnp.einsum("j,jm->m", p, gs_hat)
            proj = s.apply_t(jnp.linalg.solve(sst, s.apply(g)))  # P_S g
            w_next = w_next - scale * state["eta"] * (g - proj)

        # Guarded step + adaptive momentum restart (O'Donoghue & Candes
        # flavour): clients piggyback their local loss (1 scalar of uplink),
        # so the server knows L(w_next). If the loss increased, the step is
        # rejected and the momentum killed for the next round — this is what
        # keeps the literal Assumption-A7 momentum (beta ~ 1) stable; see
        # EXPERIMENTS.md §Paper for the unguarded divergence measurement.
        if self.restart:
            # guard broadcast: clients evaluate the candidate iterate,
            # so the server ships w_next too — a guarded round's real
            # downlink is 2M + seed, not the M + 1 of the formula
            lv = problem.local_value(comm.downlink("w_next", w_next))
            lv = comm.uplink("loss", lv)  # the piggybacked scalar
        else:
            lv = problem.local_value(w_next)
        loss_next = jnp.sum(p * lv)
        if self.restart:
            # NaN-safe acceptance: a NaN loss is a rejected step, and the
            # stored loss must never become NaN (jnp.minimum would poison it)
            ok = loss_next <= state["loss"]
            w_out = jnp.where(ok, w_next, w)
            w_prev_out = jnp.where(ok, w, w_out)  # reject -> zero momentum
            loss_out = jnp.where(ok, loss_next, state["loss"])
            # backtracking across rounds: halve the trust scale on reject,
            # grow it back (capped at 1) on accept
            scale_out = jnp.where(ok, jnp.minimum(scale * 2.0, 1.0),
                                  jnp.maximum(scale * 0.5, _MIN_TRUST_SCALE))
        else:
            w_out, w_prev_out, loss_out = w_next, w, loss_next
            scale_out = scale
        out = {"w": w_out, "w_prev": w_prev_out, "beta": beta,
               "loss": loss_out, "scale": scale_out, "t": t + 1}
        if self.variant == "plus":
            out["eta"] = state["eta"]
        return out

    # Evaluated at the look-ahead point v (Algorithm 1 step 2 updates the
    # gradient/Hessian at v_t before communication).
    def _local_grads_at(self, problem, v):
        return problem.local_grad(v)

    def _local_hess_sqrt_at(self, problem, v):
        return problem.local_hess_sqrt(v)

    def uplink_floats(self, problem) -> int:
        extra = 1 if self.restart else 0  # piggybacked local-loss scalar
        if self.variant == "plus":
            return self.k * self.k + self.k + problem.dim + extra
        return self.k * self.k + self.k + extra

    def downlink_floats(self, problem) -> int:
        # a guarded round broadcasts BOTH the look-ahead model and the
        # candidate iterate w_next (clients evaluate the guard loss at
        # it) plus the O(1) sketch basis key — 2M + 1, matching the
        # measured wire (PR 4 found the old M + 1 undercounting by ~2x)
        if self.restart:
            return 2 * problem.dim + 1
        return problem.dim + 1  # model + sketch basis key
