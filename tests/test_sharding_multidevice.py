"""Multi-device sharding machinery tests.

These run in a *subprocess* with ``--xla_force_host_platform_device_count=8``
so the main pytest process keeps its single CPU device (the dry-run is the
only place 512 devices are forced; here 8 suffice to validate the rules).
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    results = {}

    # -- mesh construction (miniature production mesh: 2x2x2) ---------
    mesh2 = jax.make_mesh((4, 2), ("data", "model"))
    mesh3 = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
    results["mesh_axes"] = list(mesh3.axis_names)

    # -- LM sharding rules produce valid specs -------------------------
    from repro.configs import get_spec
    from repro.launch.steps import build_cell
    spec = get_spec("fastwarc_lm")
    cell = build_cell(spec, "train_1k", mesh=mesh2, scale="reduced")
    jitted = jax.jit(cell.step, in_shardings=cell.in_shardings,
                     out_shardings=cell.out_shardings)
    lowered = jitted.lower(*cell.args_shapes)
    compiled = lowered.compile()
    results["lm_train_compiles"] = True
    hlo = compiled.as_text()
    from repro.roofline.analysis import collective_bytes
    results["lm_coll_bytes"] = collective_bytes(hlo)["total"]

    # -- run REAL data through the sharded step end-to-end -------------
    args = cell.make_inputs(seed=0)
    with mesh2:
        state, metrics = jitted(*jax.device_put(
            args, cell.in_shardings) if False else args)
    results["lm_loss_finite"] = bool(jnp.isfinite(metrics["loss"]))

    # -- grouped MoE under a mesh: groups == batch extent ----------------
    from repro.models.moe import moe_init, moe_apply
    p = moe_init(jax.random.PRNGKey(0), 16, 32, 4, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 16))
    with mesh2:
        out_mesh, _ = jax.jit(
            lambda p, x: moe_apply(p, x, top_k=2, capacity_factor=16.0))(p, x)
    out_ref, _ = moe_apply(p, x, top_k=2, capacity_factor=16.0, groups=1)
    results["moe_mesh_matches_ref"] = bool(
        jnp.allclose(out_mesh, out_ref, atol=1e-5))

    # -- compressed psum over an axis (shard_map) ------------------------
    from repro.train.grad_compress import compressed_psum
    from jax import shard_map
    mesh1d = jax.make_mesh((8,), ("pod",))
    xs = jnp.arange(8.0 * 4).reshape(8, 4) / 7.0
    f = jax.jit(shard_map(lambda x: compressed_psum(x[0], "pod")[None],
                          mesh=mesh1d, in_specs=P("pod", None),
                          out_specs=P("pod", None)))
    got = np.asarray(f(xs))
    expect = np.asarray(xs).sum(0)
    err = float(jnp.abs(got[0] - expect).max())
    results["compressed_psum_err"] = err

    # -- elastic mesh shrink ----------------------------------------------
    from repro.train.elastic import shrunken_mesh
    devs = np.array(jax.devices()).reshape(4, 2)
    lost = {devs[1, 0].id}
    small = shrunken_mesh(devs, ("data", "model"), lost)
    results["shrunken_shape"] = dict(small.shape)

    print("RESULTS" + json.dumps(results))
""")


@pytest.fixture(scope="module")
def multidevice_results():
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "PYTHONPATH": "src"}, cwd=os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULTS")][-1]
    return json.loads(line[len("RESULTS"):])


def test_mesh_axes(multidevice_results):
    assert multidevice_results["mesh_axes"] == ["pod", "data", "model"]


def test_lm_cell_compiles_and_runs(multidevice_results):
    assert multidevice_results["lm_train_compiles"]
    assert multidevice_results["lm_loss_finite"]
    assert multidevice_results["lm_coll_bytes"] > 0  # actually distributed


def test_grouped_moe_matches_reference_under_mesh(multidevice_results):
    assert multidevice_results["moe_mesh_matches_ref"]


def test_compressed_psum_bounded_error(multidevice_results):
    # int8 quantization error bound: scale/2 per participant, 8 participants
    assert multidevice_results["compressed_psum_err"] < 8 * (1.0 / 127)


def test_elastic_shrink(multidevice_results):
    # lost 1 of 8 devices -> 3 full data rows of model=2 survive
    assert multidevice_results["shrunken_shape"] == {"data": 3, "model": 2}
