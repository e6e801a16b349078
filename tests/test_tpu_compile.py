"""Compile the Pallas kernels and one FLeNS round for a described v5e.

Nothing runs: the TPU compiler installed next to JAX compiles for a
``v5e:2x2`` topology that is described, not attached, so Mosaic's
refusals (unsupported reshapes, 64-bit types, VMEM overruns, missing
lowerings) surface here at no chip time. Widths are the ones the main
path uses: n=16 (the population example), 32 (SUSY, d=18), 128, 2048
(d=2000) and 65536 (FEMNIST's d·C padded). The file runs under the
suite's x64 setting with float32 operands.

The topology is described inside a fixture only: one process at a time
may load the TPU library, so describing it on import would break every
other test worker.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.federated import FederatedProblem
from repro.core.flens import FLeNS
from repro.core.losses import logistic
from repro.kernels import ops as kops
from repro.kernels.codec_kernels import qint8_roundtrip_pallas, topk_mask_pallas
from repro.kernels.fwht import fwht_pallas
from repro.kernels.srht import srht_apply_pallas, srht_apply_t_pallas
from repro.sharding.rules import client_mesh_scope

WIDTHS = (16, 32, 128, 2048, 65536)
CODEC_SIZES = (68, 2000, 65536)
ROWS = 16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a persistent-cache entry written for a described chip cannot be
    # read back without one: keep these compiles out of the cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", True)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def force_pallas(monkeypatch):
    """Off the chip ``auto`` resolves to ``ref`` and ``get_impl``
    refuses "pallas": hand out the compiled kernels here instead."""
    monkeypatch.setattr(kops, "resolve_impl", lambda impl=None: "pallas")
    monkeypatch.setattr(kops, "get_impl",
                        lambda op, impl: kops._REGISTRY[op]["pallas"]())


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *specs) -> str:
    """Compile for the described chip; the HLO must call a kernel."""
    hlo = jax.jit(fn).lower(*specs).compile().as_text()
    assert "tpu_custom_call" in hlo
    return hlo


def _k(n: int) -> int:
    # the largest sketch a width meets: k=1024 at FEMNIST's 65536
    return min(n // 2, 1024)


@pytest.mark.parametrize("n", WIDTHS)
def test_fwht_compiles(one_chip, n):
    _compile(fwht_pallas, _spec(one_chip, (ROWS, n)))


@pytest.mark.parametrize("n", WIDTHS)
def test_srht_apply_compiles(one_chip, n):
    k = _k(n)
    _compile(srht_apply_pallas, _spec(one_chip, (ROWS, n)),
             _spec(one_chip, (n,)), _spec(one_chip, (k,), jnp.int32))


@pytest.mark.parametrize("n", WIDTHS)
def test_srht_apply_t_compiles(one_chip, n):
    k = _k(n)
    _compile(lambda y, s, r: srht_apply_t_pallas(y, s, r, n),
             _spec(one_chip, (ROWS, k)), _spec(one_chip, (n,)),
             _spec(one_chip, (k,), jnp.int32))


@pytest.mark.parametrize("size", CODEC_SIZES)
def test_topk_mask_compiles(one_chip, size):
    _compile(lambda x: topk_mask_pallas(x, max(1, size // 4)),
             _spec(one_chip, (size,)))


@pytest.mark.parametrize("size", CODEC_SIZES)
def test_qint8_roundtrip_compiles(one_chip, size):
    _compile(qint8_roundtrip_pallas, _spec(one_chip, (size,)),
             _spec(one_chip, (size,)))


def _flens_round_specs(clients, rest, m, n_shard, dim):
    prob = FederatedProblem(
        X=_spec(clients, (m, n_shard, dim)),
        y=_spec(clients, (m, n_shard)),
        mask=_spec(clients, (m, n_shard)),
        lam=1e-3, objective=logistic)
    vec, scalar = _spec(rest, (dim,)), _spec(rest, ())
    state = {"w": vec, "w_prev": vec, "beta": scalar, "loss": scalar,
             "scale": scalar, "t": _spec(rest, (), jnp.int32)}
    return prob, state, _spec(rest, (2,), jnp.uint32)


def test_flens_round_compiles_at_susy_shape(one_chip, force_pallas):
    """One whole FLeNS round at the paper's SUSY size (n = 5,000,000,
    d = 18, m = 1000, k = 10) with every kernel on Pallas."""
    args = _flens_round_specs(one_chip, one_chip, 1000, 5000, 18)
    opt = FLeNS(k=10)
    hlo = _compile(lambda p, s, kk: opt.round(p, s, kk), *args)
    assert "f64" not in hlo


def test_flens_round_compiles_over_client_mesh(topo, force_pallas):
    """The population round with its cohort split over four chips, as
    the sessions run it under ``client_mesh_scope``: the compiler cannot
    partition a Pallas kernel, so every kernel call must reach it inside
    a shard_map — per-client calls on each chip's own 25 clients."""
    mesh = Mesh(np.asarray(topo.devices), ("clients",),
                axis_types=(AxisType.Auto,))
    args = _flens_round_specs(NamedSharding(mesh, P("clients")),
                              NamedSharding(mesh, P()), 100, 64, 16)
    opt = FLeNS(k=8)
    with client_mesh_scope(mesh):
        hlo = _compile(lambda p, s, kk: opt.round(p, s, kk), *args)
    # the client sketch runs on (25, 64, 8) per chip, never all 100
    assert "f32[25,64,8]" in hlo
