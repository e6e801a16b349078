"""Population cohorts sharded over a device mesh (``client_mesh``).

``cohort_spec`` is checked in-process on abstract meshes; the sharded
``run_rounds`` runs in a subprocess with 4 forced host devices (the flag
must not leak into this process), against the same run on one device.
"""
import os
import pathlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.sharding.rules import cohort_spec

REPO = pathlib.Path(__file__).resolve().parent.parent


@pytest.mark.parametrize("shape", [(8, 5, 3), (8, 5), (4,)])
def test_cohort_spec_splits_client_axis(shape):
    mesh = AbstractMesh((4,), ("clients",))
    leaf = jax.ShapeDtypeStruct(shape, jnp.float32)
    assert cohort_spec(mesh, leaf) == P("clients")


def test_cohort_spec_raises_on_indivisible_cohort():
    """A cohort that does not divide the mesh is an error, never a
    silent replication of every client on every device."""
    mesh = AbstractMesh((4,), ("clients",))
    with pytest.raises(ValueError, match="does not divide the 4-way"):
        cohort_spec(mesh, jax.ShapeDtypeStruct((6, 5, 3), jnp.float32))


_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp
    import numpy as np

    from repro.comm import CommConfig
    from repro.core import (SyntheticPopulation, make_optimizer,
                            newton_solve, run_rounds)
    from repro.sharding.rules import shard_cohort

    pop = SyntheticPopulation(m=2000, dim=16, seed=1, dirichlet_alpha=0.3)
    comm = CommConfig(scheduler="uniform:1e-2", seed=1)
    cohort = comm.scheduler.cohort_size(pop.m)
    # an Auto axis: GSPMD partitions the vmapped round along it
    mesh = jax.make_mesh((4,), ("clients",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    placed = shard_cohort(mesh, pop.materialize(np.arange(cohort)))
    for leaf in jax.tree_util.tree_leaves(placed):
        rows = {s.index[0] for s in leaf.addressable_shards}
        assert len(rows) == 4, (leaf.shape, rows)

    w0 = jnp.zeros((pop.dim,), jnp.float64)
    w_star = newton_solve(pop.eval_problem(), w0)
    runs = [run_rounds(make_optimizer("flens", k=8), pop, w0, w_star,
                       rounds=4, seed=1, comm=comm, client_mesh=mesh_)
            for mesh_ in (mesh, None)]
    sharded, single = runs
    assert np.array_equal(sharded.cumulative_bytes, single.cumulative_bytes)
    np.testing.assert_allclose(sharded.gap, single.gap, rtol=1e-9,
                               atol=1e-12)
    assert single.gap[-1] < single.gap[0]
    print("SHARDED_MATCHES", cohort, sharded.gap.tolist())
""")


def test_client_mesh_population_matches_one_device():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SHARDED_MATCHES" in out.stdout
