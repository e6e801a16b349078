"""Plain reference of what a benchmark cell's timed rounds compute.

FLeNS (arXiv:2409.15216, Algorithm 1, with the guarded step and the
``paper`` momentum of the program's defaults) on a regularised logistic
regression, written straight in ``jax.numpy``: dense per-client tensors,
the SRHT as an explicit (k, dim) matrix, the codecs by their
definitions, matrix products at ``highest`` precision. It imports
nothing of the program and takes nothing the program made: the data
come from the benchmark's own generator, the partition, the optimum, the
momentum and every round are recomputed here. Who delivers in each round
follows from the configuration (``sync_schedule``, ``async_schedule``);
the one thing taken from a run is what the configuration leaves to the
channel's draw, the order in which asynchronous uploads land, and that
is held to the configuration's rules.

The random draws follow the program's stated key schedule, so the
reference walks the same trajectory and differs only by rounding:

* round keys ``split(PRNGKey(seed), rounds)``; the round's SRHT from its
  key: ``ks, kr = split(key)``, signs ``rademacher(ks, (n,))``, rows
  ``choice(kr, n, (k,), replace=False)``, ``n = next_pow2(dim)``;
* the transport's codec key of round t: the third of
  ``split(fold_in(PRNGKey(comm_seed), t), 3)``; the i-th uplink payload
  of the round (h_sk = 1, sg = 2, loss = 3) draws its per-client keys as
  ``split(fold_in(codec_key, i), clients)``.

Wire bytes follow the codecs' wire formats: identity is the raw array,
``qint8`` one byte per entry plus a float32 scale, ``topk<f>`` keeps
``ceil(f * size)`` entries as (int32 index, value) pairs; the downlink
carries w, the sketch key (two uint32) and the guard's w_next.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

# payload order within a FLeNS round, as the transport numbers its keys
UPLINKS = ("h_sk", "sg", "loss")
# control-plane payloads the transport sends losslessly unless named
LOSSLESS_BY_DEFAULT = ("loss",)
MIN_TRUST_SCALE = 1.0 / 64.0
LAM_DAMP = 1e-8


def next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def hadamard(n: int) -> np.ndarray:
    """Orthonormal Sylvester Hadamard matrix, H[i, j] = (-1)^popcount(i & j)
    / sqrt(n)."""
    i = np.arange(n)
    bits = np.bitwise_and(i[:, None], i[None, :])
    parity = np.zeros_like(bits)
    while bits.any():
        parity ^= bits & 1
        bits >>= 1
    return (1.0 - 2.0 * parity) / np.sqrt(n)


def codec_spec(codecs: dict, payload: str) -> str:
    if payload in codecs:
        return codecs[payload]
    if payload in LOSSLESS_BY_DEFAULT:
        return "identity"
    return codecs.get("default", "identity")


def topk_kept(spec: str, size: int) -> int:
    frac = float(spec[len("topk"):])
    return max(1, min(size, int(math.ceil(frac * size))))


def wire_bytes(spec: str, size: int, itemsize: int = 4) -> int:
    """Encoded bytes of one client's payload of ``size`` entries."""
    if spec == "identity":
        return size * itemsize
    if spec == "qint8":
        return size + 4
    if spec.startswith("topk"):
        return topk_kept(spec, size) * (4 + itemsize)
    raise NotImplementedError(f"no reference wire format for {spec!r}")


def codec_roundtrip(spec: str, keys, x):
    """Decoded payloads x (c, ...) of one uplink, per client."""
    if spec == "identity":
        return x
    if spec == "qint8":
        def one(key, xj):
            u = jax.random.uniform(key, xj.shape, jnp.float32).astype(xj.dtype)
            scale = jnp.maximum(jnp.max(jnp.abs(xj)) / 127.0,
                                jnp.finfo(xj.dtype).tiny)
            q = jnp.clip(jnp.floor(xj / scale + u), -127, 127)
            return (q * scale).astype(xj.dtype)
        return jax.vmap(one)(keys, x)
    if spec.startswith("topk"):
        kept = topk_kept(spec, math.prod(x.shape[1:]))

        def one(xj):
            flat = xj.reshape(-1)
            _, idx = jax.lax.top_k(jnp.abs(flat), kept)
            out = jnp.zeros_like(flat).at[idx].set(flat[idx])
            return out.reshape(xj.shape)
        return jax.vmap(one)(x)
    raise NotImplementedError(f"no reference codec for {spec!r}")


# ---------------------------------------------------------------------------
# the partition of a dataset into clients
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Partition:
    """Every client's shard, padded to one length: features
    (m, n_shard, dim), labels and the row mask (m, n_shard), and each
    client's row count."""

    X: jax.Array
    y: jax.Array
    mask: jax.Array
    sizes: np.ndarray  # (m,)

    @property
    def m(self) -> int:
        return len(self.sizes)

    def astype(self, dtype) -> "Partition":
        return Partition(self.X.astype(dtype), self.y.astype(dtype),
                         self.mask.astype(dtype), self.sizes)


def partition(X, y, m: int, key) -> Partition:
    """Clients' iid shards: a seeded permutation of the rows cut into m
    equal shards, the last one short where m does not divide n."""
    n, dim = X.shape
    perm = jax.random.permutation(key, n)
    n_shard = -(-n // m)
    pad = n_shard * m - n
    rows_X = jnp.concatenate([X[perm], jnp.zeros((pad, dim), X.dtype)])
    rows_y = jnp.concatenate([y[perm], jnp.zeros((pad,), y.dtype)])
    sizes = np.full((m,), n_shard, dtype=np.int64)
    sizes[-1] = n - n_shard * (m - 1)
    mask = jnp.asarray(np.arange(n_shard)[None, :] < sizes[:, None], X.dtype)
    return Partition(rows_X.reshape(m, n_shard, dim),
                     rows_y.reshape(m, n_shard) * mask, mask, sizes)


# ---------------------------------------------------------------------------
# the objective and FLeNS
# ---------------------------------------------------------------------------

def local_terms(X, y, mask, w, lam):
    """Per-client loss (c,), gradient (c, dim), Hessian weights (c, s)
    and row counts (c,) of the l2-regularised logistic loss."""
    nj = jnp.sum(mask, axis=1)
    margins = y * jnp.einsum("csd,d->cs", X, w)
    loss = (jnp.sum(jax.nn.softplus(-margins) * mask, axis=1) / nj
            + 0.5 * lam * jnp.sum(w * w))
    s = jax.nn.sigmoid(-margins) * mask
    grad = -jnp.einsum("csd,cs->cd", X, s * y) / nj[:, None] + lam * w
    p = jax.nn.sigmoid(margins)
    return loss, grad, p * (1.0 - p) * mask, nj


def global_eval(X, y, mask, w, lam):
    loss, grad, _, nj = local_terms(X, y, mask, w, lam)
    p = nj / jnp.sum(nj)
    return jnp.sum(p * loss), jnp.linalg.norm(p @ grad)


def global_hessian(X, y, mask, w, lam):
    _, _, d, nj = local_terms(X, y, mask, w, lam)
    p = nj / jnp.sum(nj)
    h = jnp.einsum("csa,csb->ab", X * (d * (p / nj)[:, None])[..., None], X)
    return h + lam * jnp.eye(X.shape[-1], dtype=X.dtype)


def newton_optimum(X, y, mask, lam, iters: int = 50):
    """w* by exact Newton from 0 (the objective is strongly convex)."""
    def step(_, w):
        _, grad, _, nj = local_terms(X, y, mask, w, lam)
        g = (nj / jnp.sum(nj)) @ grad
        return w - jnp.linalg.solve(global_hessian(X, y, mask, w, lam), g)

    return jax.lax.fori_loop(0, iters, step,
                             jnp.zeros((X.shape[-1],), X.dtype))


def srht_matrix(key, k: int, dim: int, dtype):
    """The round's SRHT as a (k, dim) matrix sqrt(n/k) P H_n D."""
    n = next_pow2(dim)
    ks, kr = jax.random.split(key)
    signs = jax.random.rademacher(ks, (n,), dtype=jnp.float32)
    rows = jax.random.choice(kr, n, (k,), replace=False)
    h = jnp.asarray(hadamard(n), jnp.float32)
    s = jnp.sqrt(n / k) * h[rows] * signs[None, :]
    return s[:, :dim].astype(dtype)


def init_state(X, y, mask, w0, lam):
    """FLeNS's state at w0 with the paper's momentum (L1 - g)/(L1 + g)."""
    h = global_hessian(X, y, mask, w0, lam)
    # factorizations run in float32 whatever the storage type: there is
    # no bfloat16 eigensolver or LU
    evals = jnp.linalg.eigvalsh(h.astype(jnp.float32)).astype(h.dtype)
    l1, gam = evals[-1], jnp.maximum(evals[0], lam)
    loss, _ = global_eval(X, y, mask, w0, lam)
    return {"w": w0, "w_prev": w0, "beta": (l1 - gam) / (l1 + gam),
            "loss": loss, "scale": jnp.asarray(1.0, w0.dtype)}


def flens_round(state, X, y, mask, deliv, key, codec_key, *, k: int,
                lam: float, codecs: tuple, fault: "str | None" = None):
    """One guarded FLeNS round over the clients (X, y, mask); ``deliv``
    (c,) marks who delivered. ``fault`` plants a known error for the
    benchmark's controls: ``"unchanged"`` returns the state as it came,
    ``"half"`` aggregates over the first half of those who delivered."""
    codecs = dict(codecs)
    w, w_prev, beta = state["w"], state["w_prev"], state["beta"]
    dtype = w.dtype
    c = X.shape[0]
    v = w + beta * (w - w_prev)
    S = srht_matrix(key, k, X.shape[-1], dtype)
    _, grad, dh, nj = local_terms(X, y, mask, v, lam)
    A = X * jnp.sqrt(dh / nj[:, None])[..., None]
    B = jnp.einsum("csd,kd->csk", A, S)
    # the Gram is symmetric by definition: mirror its upper triangle, so
    # that rounding cannot break the ties of a symmetric pair, and top-k
    # keeps the pair's first entry, as exact arithmetic would
    gram = jnp.triu(jnp.einsum("csk,csl->ckl", B, B))
    payloads = {"h_sk": gram + jnp.swapaxes(jnp.triu(gram, 1), 1, 2),
                "sg": grad @ S.T}
    for i, name in enumerate(UPLINKS[:2], start=1):
        keys = jax.random.split(jax.random.fold_in(codec_key, i), c)
        payloads[name] = codec_roundtrip(codec_spec(codecs, name), keys,
                                         payloads[name])
    wt = deliv * nj / jnp.sum(nj)
    if fault == "half":
        # the later half of those who delivered is left out
        wt = wt * (jnp.cumsum(deliv) <= jnp.sum(deliv) // 2)
    wt = wt / jnp.sum(wt)
    h = jnp.einsum("c,ckl->kl", wt, payloads["h_sk"]) + lam * S @ S.T
    g = wt @ payloads["sg"]
    h = (h + LAM_DAMP * jnp.eye(k, dtype=dtype)).astype(jnp.float32)
    delta = S.T @ jnp.linalg.solve(h, g.astype(jnp.float32)).astype(dtype)
    scale = state["scale"]
    w_next = v - scale * delta
    lv, _, _, _ = local_terms(X, y, mask, w_next, lam)
    keys = jax.random.split(jax.random.fold_in(codec_key, 3), c)
    lv = codec_roundtrip(codec_spec(codecs, "loss"), keys, lv[:, None])[:, 0]
    loss_next = jnp.sum(wt * lv)
    ok = loss_next <= state["loss"]
    out = {"w": jnp.where(ok, w_next, w),
           "w_prev": w,  # a rejected step also drops the momentum
           "beta": beta,
           "loss": jnp.where(ok, loss_next, state["loss"]),
           "scale": jnp.where(ok, jnp.minimum(scale * 2.0, 1.0),
                              jnp.maximum(scale * 0.5, MIN_TRUST_SCALE))}
    if fault == "unchanged":
        return state
    return out


# ---------------------------------------------------------------------------
# following a run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Schedule:
    """What a run's rounds have to do, by its configuration: the run's
    seed and round count, and for each round who delivers (T, m), how
    many broadcasts the server sends (T,), and, for an asynchronous run,
    each commit's staleness per client (NaN: not in the commit) and the
    staleness rule."""

    seed: int
    rounds: int
    delivered: np.ndarray  # (T, m) bool
    broadcasts: np.ndarray  # (T,)
    staleness: "np.ndarray | None" = None  # (T, m)
    staleness_rule: str = "constant"


def sync_schedule(seed: int, rounds: int, m: int) -> Schedule:
    """Every client is scheduled and delivers in every round (a full
    scheduler on a channel that loses nothing)."""
    return Schedule(seed, rounds, np.ones((rounds, m), dtype=bool),
                    np.full((rounds,), float(m)))


def async_schedule(seed: int, members: np.ndarray, buffer_size: int,
                   rule: str) -> "tuple[Schedule, int]":
    """The buffered asynchronous schedule of a full scheduler on a
    channel that drops nothing: every client is dispatched the model at
    version 0, each commit takes exactly ``buffer_size`` uploads, and a
    committed client is sent the new model at once, so its next upload's
    staleness is the commits since then. Which clients land first is the
    channel's draw, taken from the run (``members``, (T, m)). Returns the
    schedule and the number of commits whose membership breaks these
    rules."""
    members = np.asarray(members, dtype=bool)
    rounds, m = members.shape
    dispatched = np.zeros((m,), dtype=np.int64)
    staleness = np.full((rounds, m), np.nan)
    broadcasts = np.zeros((rounds,))
    broken = 0
    for t in range(rounds):
        got = np.flatnonzero(members[t])
        broken += int(len(got) != buffer_size)
        staleness[t, got] = t - dispatched[got]
        broadcasts[t] = m if t == 0 else members[t - 1].sum()
        dispatched[got] = t + 1
    return Schedule(seed, rounds, members, broadcasts, staleness, rule), broken


def schedule_gap(sched: Schedule, delivered: np.ndarray,
                 scheduled: np.ndarray,
                 staleness: "np.ndarray | None") -> int:
    """Rounds in which the run departs from the schedule: another set of
    clients delivered, another number was sent the model (sync), or a
    commit's staleness differs from the versions its clients were sent
    (async)."""
    delivered = np.asarray(delivered, dtype=bool)
    if delivered.shape != sched.delivered.shape:
        return max(sched.rounds, len(delivered))
    bad = np.any(delivered != sched.delivered, axis=1)
    if sched.staleness is None:
        bad |= np.asarray(scheduled) != sched.delivered.sum(axis=1)
    else:
        same = np.isclose(np.asarray(staleness, dtype=np.float64),
                          sched.staleness, equal_nan=True, rtol=0, atol=0)
        bad |= ~np.all(same, axis=1)
    return int(bad.sum())


def staleness_weight(rule: str, tau: float) -> float:
    if rule == "constant":
        return 1.0
    if rule == "inverse":
        return 1.0 / (1.0 + tau)
    kind, _, arg = rule.partition(":")
    if kind == "poly":
        return (1.0 + tau) ** (-float(arg or 0.5))
    raise NotImplementedError(f"no reference staleness rule {rule!r}")


def expected_bytes(sched: Schedule, *, dim: int, k: int,
                   codecs: dict) -> np.ndarray:
    """(T,) wire bytes of each round by the schedule, up and down, all
    clients: each delivered upload, and each broadcast of w, the sketch
    key (two uint32) and the guard's w_next."""
    up = (wire_bytes(codec_spec(codecs, "h_sk"), k * k)
          + wire_bytes(codec_spec(codecs, "sg"), k)
          + wire_bytes(codec_spec(codecs, "loss"), 1))
    down = 4 * dim + 8 + 4 * dim
    return (up * sched.delivered.sum(axis=1).astype(np.float64)
            + down * sched.broadcasts)


def follow(part: Partition, sched: Schedule, *, steps: int, k: int,
           lam: float, codecs: dict, dtype=jnp.float32,
           fault: "str | None" = None) -> dict:
    """Loss and gradient norm over all clients at w_0..w_steps, and the
    initial gap F(w_0) - F(w*), as the reference computes them in
    ``dtype`` (float32 at ``highest`` precision, or the control's lower
    precision)."""
    precision = "highest" if dtype == jnp.float32 else "default"
    with jax.default_matmul_precision(precision):
        return _follow(part, sched, steps, k, lam, codecs, dtype, fault)


def _follow(part, sched, steps, k, lam, codecs, dtype, fault):
    data = part.astype(dtype)
    Xe, ye, me = data.X, data.y, data.mask
    dim = Xe.shape[-1]
    w0 = jnp.zeros((dim,), dtype)
    ev = jax.jit(global_eval, static_argnames="lam")
    f32 = part.astype(jnp.float32)
    w_star = jax.jit(newton_optimum, static_argnames=("lam", "iters"))(
        f32.X, f32.y, f32.mask, lam=lam)
    del f32
    loss_star, _ = ev(Xe, ye, me, w_star.astype(dtype), lam=lam)
    state = jax.jit(init_state, static_argnames="lam")(Xe, ye, me, w0,
                                                      lam=lam)
    step = jax.jit(flens_round, static_argnames=("k", "lam", "codecs",
                                                 "fault"))
    round_keys = jax.random.split(jax.random.PRNGKey(sched.seed),
                                  sched.rounds)
    comm_root = jax.random.PRNGKey(sched.seed)
    codecs = tuple(sorted(codecs.items()))

    def run_round(state, version, deliv):
        codec_key = jax.random.split(jax.random.fold_in(comm_root, version),
                                     3)[2]
        return step(state, Xe, ye, me, jnp.asarray(deliv, dtype),
                    round_keys[version], codec_key, k=k, lam=lam,
                    codecs=codecs, fault=fault)

    states = [state]
    for t in range(steps):
        if sched.staleness is None:
            states.append(run_round(states[t], t, sched.delivered[t]))
        else:
            states.append(_commit(states, t, sched, run_round, part))
    losses, gnorms = [], []
    for st in states:
        loss, gnorm = ev(Xe, ye, me, st["w"], lam=lam)
        losses.append(float(loss))
        gnorms.append(float(gnorm))
    return {"loss": np.asarray(losses), "grad_norm": np.asarray(gnorms),
            "gap0": losses[0] - float(loss_star)}


def _commit(states, t, sched, run_round, part):
    """Commit t of an asynchronous run: the committed clients grouped by
    the version they computed on, each group's round from its version,
    the model deltas weighted by staleness times participation mass;
    the state beyond the model follows the freshest group when it is
    current."""
    stale = sched.staleness[t]
    groups: "dict[int, list[int]]" = {}
    for c in np.flatnonzero(sched.delivered[t]):
        groups.setdefault(t - int(stale[c]), []).append(int(c))
    order = sorted(groups, reverse=True)
    out = {}
    for v in order:
        deliv = np.zeros(part.m)
        deliv[groups[v]] = 1.0
        out[v] = run_round(states[v], v, deliv)
    fresh = order[0]
    if len(order) == 1 and fresh == t:
        return out[fresh]
    weights = part.sizes / part.sizes.sum()
    mass = {v: float(weights[groups[v]].sum()) for v in order}
    total = sum(mass.values())
    w = states[t]["w"]
    for v in order:
        c = staleness_weight(sched.staleness_rule, float(t - v)) * mass[v] / total
        w = w + c * (out[v]["w"] - states[v]["w"])
    base = out[fresh] if fresh == t else states[t]
    return {**base, "w": w}


def round_gaps(program: dict, ref: dict,
               steps: int) -> "tuple[np.ndarray, np.ndarray]":
    """Per round t = 1..``steps``: |F_program(w_t) - F_ref(w_t)| as a
    share of the reference's initial gap, and the relative gap between
    the two gradient norms ||grad F(w_t)||."""
    t = slice(1, steps + 1)
    lp, lr = np.asarray(program["loss"])[t], ref["loss"][t]
    gp, gr = np.asarray(program["grad_norm"])[t], ref["grad_norm"][t]
    with np.errstate(invalid="ignore", divide="ignore"):
        return np.abs(lp - lr) / ref["gap0"], np.abs(gp - gr) / gr


def compare(program: dict, ref: dict, want_bytes: np.ndarray,
            steps: int) -> dict:
    """The numbers ``correct`` is decided on:

    * ``loss_gap``: the largest |F_program(w_t) - F_ref(w_t)| over the
      first ``steps`` rounds, as a share of the reference's initial gap;
    * ``grad_gap``: the largest relative gap between the two gradient
      norms ||grad F(w_t)|| over those rounds;
    * ``bytes_gap``: the largest |billed - expected| wire bytes of any
      round of the call.
    """
    losses, grads = round_gaps(program, ref, steps)
    loss_gap, grad_gap = float(np.max(losses)), float(np.max(grads))
    billed = np.asarray(program["bytes"], dtype=np.float64)
    if billed.shape != want_bytes.shape:
        bytes_gap = float("inf")
    else:
        bytes_gap = float(np.max(np.abs(billed - want_bytes)))
    return {"loss_gap": loss_gap if np.isfinite(loss_gap) else float("inf"),
            "grad_gap": grad_gap if np.isfinite(grad_gap) else float("inf"),
            "bytes_gap": bytes_gap}
