"""Self-test for the distributed spherical ops on 8 fake CPU devices.

Run as ``python -m repro.distributed.selftest``; the pytest suite shells out
to this module (device count must be fixed before jax initializes, so it
cannot run inside the main test process).

Verifies, on a (lat=2, lon=2, ensemble=2) mesh:
  * distributed SHT forward/inverse == single-device SHT (Algorithm 1),
  * distributed DISCO == single-device FFT DISCO (Algorithm 2),
  * distributed ensemble CRPS == single-device nodal CRPS (Algorithm 3).
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import functools  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402
from jax import shard_map  # noqa: E402

from repro.core import crps as crpslib  # noqa: E402
from repro.core.sphere import disco as dlib  # noqa: E402
from repro.core.sphere import grids, sht  # noqa: E402
from repro.distributed import dist_crps, dist_disco, dist_sht  # noqa: E402


def _mesh() -> Mesh:
    devs = np.asarray(jax.devices()[:8]).reshape(2, 2, 2)
    return Mesh(devs, ("ens", "lat", "lon"))


def check_dist_sht(mesh: Mesh) -> None:
    g = grids.make_grid(32, 64, "gauss")
    t = sht.SHT.create(g, lmax=32, mmax=32)
    bufs = t.buffers()
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (2, 8, 32, 64))  # (B, C, H, W)

    fwd = shard_map(
        functools.partial(dist_sht.dist_sht_forward, mmax=t.mmax,
                          lat_axis="lat", lon_axis="lon"),
        mesh=mesh,
        in_specs=(P(None, None, "lat", "lon"), P("lon", None, None)),
        out_specs=P(None, None, "lat", "lon"),
    )
    c_dist = jax.jit(fwd)(x, bufs["wpct"])
    c_ref = t.forward(x)
    err = float(jnp.abs(c_dist - c_ref).max())
    assert err < 1e-4, f"dist SHT forward mismatch: {err}"

    inv = shard_map(
        functools.partial(dist_sht.dist_sht_inverse, nlon=64,
                          lat_axis="lat", lon_axis="lon"),
        mesh=mesh,
        in_specs=(P(None, None, "lat", "lon"), P("lon", None, None)),
        out_specs=P(None, None, "lat", "lon"),
    )
    x_dist = jax.jit(inv)(c_ref, bufs["pct"])
    x_ref = t.inverse(c_ref)
    err = float(jnp.abs(x_dist - x_ref).max())
    assert err < 1e-4, f"dist SHT inverse mismatch: {err}"
    print("dist_sht: OK")


def check_dist_disco(mesh: Mesh) -> None:
    gi = grids.make_grid(32, 64, "equiangular")
    go = grids.make_grid(32, 64, "equiangular")
    plan = dlib.make_disco_plan(gi, go, cutoff_factor=3.0)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 32, 64))
    ref = dlib.disco_conv(x, jnp.asarray(plan.psi),
                          jnp.asarray(plan.lat_idx), plan.stride)

    blocks, _ = dist_disco.local_psi_blocks(plan, n_lat_ranks=2)
    psi_stacked = jnp.asarray(blocks)  # (R, K, H_out, loc, W)
    psi_flat = psi_stacked.reshape((-1,) + psi_stacked.shape[2:])

    conv = shard_map(
        functools.partial(dist_disco.dist_disco_conv, stride=plan.stride,
                          lat_axis="lat", lon_axis="lon"),
        mesh=mesh,
        in_specs=(P(None, None, "lat", "lon"), P("lat", None, None, None)),
        out_specs=P(None, None, None, "lat", "lon"),
    )
    got = jax.jit(conv)(x, psi_flat)
    err = float(jnp.abs(got - ref).max())
    scale = float(jnp.abs(ref).max())
    assert err < 1e-4 * max(scale, 1.0), f"dist DISCO mismatch: {err}"
    print("dist_disco: OK")


def check_dist_crps(mesh: Mesh) -> None:
    g = grids.make_grid(16, 32, "gauss")
    aw = jnp.asarray(g.area_weights_2d(), jnp.float32).reshape(-1)
    ens = jax.random.normal(jax.random.PRNGKey(2), (4, 16 * 32))
    obs = jax.random.normal(jax.random.PRNGKey(3), (16 * 32,))
    ref = float(jnp.sum(crpslib.crps_ensemble(ens, obs, axis=0) * aw))

    fn = shard_map(
        functools.partial(dist_crps.dist_crps, ens_axis="ens", fair=False),
        mesh=mesh,
        in_specs=(P("ens", None), P(None), P(None)),
        out_specs=P(),
    )
    got = float(jax.jit(fn)(ens, obs, aw))
    assert abs(got - ref) < 1e-5 * max(abs(ref), 1.0), (got, ref)
    print("dist_crps: OK")


def main() -> None:
    assert jax.device_count() >= 8, jax.devices()
    mesh = _mesh()
    check_dist_sht(mesh)
    check_dist_disco(mesh)
    check_dist_crps(mesh)
    print("ALL DISTRIBUTED CHECKS PASSED")


if __name__ == "__main__":
    main()
