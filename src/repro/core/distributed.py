"""Mesh-resident FLeNS: clients as data-parallel mesh slices.

The simulator in ``core/`` vmaps over a client axis on one host. This
module runs the SAME round on a real device mesh: every ``(pod, data)``
slice holds one client's shard, local sketches are computed on-device,
and the server aggregation is a ``psum`` over the client axes — the
O(k²) wire pattern shown in EXPERIMENTS §Dry-run, now as a usable
training API.

Numerical contract (tested in tests/test_distributed_flens.py): one
``distributed_round`` on an m-slice mesh == one simulator round with the
same m clients, same sketch seed — exactly, to float tolerance.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.losses import Objective
from repro.core.sketch_policy import SketchPolicy, as_policy


@dataclasses.dataclass(frozen=True)
class DistributedFLeNS:
    """FLeNS with clients distributed over mesh axes.

    The sketch basis is keyed by the broadcast int32 seed through a
    ``SketchPolicy`` (the seed doubles as the round index, so
    ``sketch="srht:rotate=R"`` / ``"srht:fixed"`` schedule the basis
    exactly like the simulator's optimizers); `round_fn()` returns a
    jit-compiled step.
    """

    mesh: Mesh
    objective: Objective
    dim: int
    k: int
    lam: float
    mu: float = 1.0
    beta: float = 0.0
    lam_damp: float = 1e-8
    client_axes: tuple = ("pod", "data")
    sketch: "str | SketchPolicy" = "srht"

    def _axes(self):
        return tuple(a for a in self.client_axes if a in self.mesh.axis_names)

    # -- client-local math ---------------------------------------------------
    def _local_grad(self, X, y, w):
        if self.objective.name == "logistic":
            margins = y * (X @ w)
            s = jax.nn.sigmoid(-margins)
            return -(X.T @ (s * y)) / X.shape[0] + self.lam * w
        r = X @ w - y
        return X.T @ r / X.shape[0] + self.lam * w

    def _local_hess_sqrt(self, X, y, w):
        if self.objective.name == "logistic":
            margins = y * (X @ w)
            p = jax.nn.sigmoid(margins)
            d = p * (1 - p)
        else:
            d = jnp.ones_like(y)
        return X * jnp.sqrt(d / X.shape[0])[:, None]

    # -- one communication round ------------------------------------------------
    def round_fn(self):
        axes = self._axes()
        dim, k = self.dim, self.k
        policy = as_policy(self.sketch, k=k)
        if policy.adaptive:
            raise ValueError(
                "DistributedFLeNS compiles one fixed-shape step: adaptive-k "
                f"sketch policies ({policy.spec()!r}) cannot resize it; "
                "use a constant-k fresh/fixed/rotating schedule")

        def body(X, y, w, w_prev, seed):
            w = w[0]
            w_prev = w_prev[0]
            v = w + self.beta * (w - w_prev)
            # the broadcast seed is the round index: fresh schedules key
            # the basis from PRNGKey(seed) directly (the pre-policy
            # wire contract), fixed/rotating ones from their epoch
            sketch = policy.sample(jax.random.PRNGKey(seed[0]), seed[0],  # noqa: RA001 — wire contract: the broadcast round seed IS the key material every client re-derives
                                   dim, dtype=w.dtype)
            sst = sketch.apply(sketch.apply_t(jnp.eye(k, dtype=w.dtype)))

            a = self._local_hess_sqrt(X, y, v)
            b = sketch.apply(a)  # (n_loc, k)
            h_sk = b.T @ b  # k x k — the uplink payload
            g_sk = sketch.apply(self._local_grad(X, y, v))

            # server aggregation == psum over the client axes
            h_sk = jax.lax.pmean(h_sk, axes)
            g_sk = jax.lax.pmean(g_sk, axes)

            h_tilde = h_sk + self.lam * sst + self.lam_damp * jnp.eye(
                k, dtype=w.dtype)
            delta = sketch.apply_t(jnp.linalg.solve(h_tilde, g_sk))
            w_next = v - self.mu * delta
            return w_next[None], w[None]

        spec_data = P(self._axes() or None, None)
        spec_y = P(self._axes() or None)
        rep = P(None, None)

        wrapped = jax.shard_map(
            body,
            mesh=self.mesh,
            in_specs=(spec_data, spec_y, rep, rep, P(None)),
            out_specs=(rep, rep),
            check_vma=False,
        )

        def step(X, y, w, w_prev, seed):
            w2, wp2 = wrapped(X, y, w[None], w_prev[None],
                              jnp.asarray(seed, jnp.int32)[None])
            return w2[0], wp2[0]

        return jax.jit(step)

    # -- data placement helper ----------------------------------------------------
    def shard_data(self, X, y):
        """Place the global dataset with rows sharded over the client axes."""
        axes = self._axes()
        sx = NamedSharding(self.mesh, P(axes or None, None))
        sy = NamedSharding(self.mesh, P(axes or None))
        return jax.device_put(X, sx), jax.device_put(y, sy)


def run_distributed(
    dist: DistributedFLeNS, X, y, w0, rounds: int, seed0: int = 0
):
    """Convenience driver: runs `rounds` rounds, returns the iterate path."""
    step = dist.round_fn()
    Xs, ys = dist.shard_data(X, y)
    w, w_prev = w0, w0
    ws = [w0]
    for t in range(rounds):
        w, w_prev = step(Xs, ys, w, w_prev, seed0 + t)
        ws.append(w)
    return w, ws
