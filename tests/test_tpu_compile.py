"""Compile rehearsals of the main-path Pallas kernels for a TPU v5e.

Each test lowers and compiles one kernel at the shapes the full-width
model (``fcn3_full``: 721x1440 grid, 360x720 latent, 13 levels) feeds it,
for a chip that is described, not attached: the installed TPU compiler
refuses here what the chip would refuse (a tile off Mosaic's (8, 128)
grid, a strided lane gather, an unsupported shape cast, more VMEM than a
kernel instance has).  A compile that passes says nothing about results
or times -- the interpret-mode parity suites and ``chip_smoke.py`` do.

The topology is described inside a module fixture, never at import, so
every pytest-xdist worker collects the same tests and only the worker
that runs this file loads the TPU compiler library.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.crps.crps import crps_fused
from repro.kernels.disco.disco import disco_band_contract
from repro.kernels.dispatch import (band_run_rows, sht_forward_pallas,
                                    sht_inverse_pallas)

#: full-width plans: (x, psi_band, mix, stride, affine).  x is one
#: member's kernel input as FCN3 builds it (encoder: 13 levels x 5
#: variables folded into the rows; latent: 641 latent + 36 conditioning
#: channels; decoder: one pressure level's 45 channels per call).
DISCO_PLANS = {
    "encoder": ((1, 65, 721, 1440), (7, 360, 13, 423), (7, 585, 65), 2,
                (2, -5)),
    "latent": ((1, 677, 360, 720), (7, 360, 7, 209), (7, 641, 677), 1,
               (1, -3)),
    "decoder": ((1, 45, 721, 1440), (7, 721, 5, 641), (7, 5, 45), 1,
                (1, -2)),
}

#: each plan's row runs (h0, h1, d), as ``DiscoPlan.band_runs`` gives
#: them at full width: dispatch calls the kernel once per run
DISCO_RUNS = {
    "encoder": ((3, 5, 423), (5, 355, 251), (355, 357, 423)),
    "latent": ((3, 5, 209), (5, 355, 125), (355, 357, 209)),
    "decoder": ((3, 4, 641), (4, 6, 385), (6, 11, 239), (11, 710, 125),
                (710, 715, 239), (715, 717, 385), (717, 718, 641)),
}


def _run_call(plan: str, run: int | None):
    """(x, psi_band, mix, stride, affine) of one kernel call: the plan's
    whole band (``run`` None) or one of its runs, as dispatch slices it."""
    x, psi, mix, stride, affine = DISCO_PLANS[plan]
    if run is None:
        return x, psi, mix, stride, affine
    h0, h1, d = DISCO_RUNS[plan][run]
    r0, r1, run_affine = band_run_rows(h0, h1, psi[2], affine, x[2])
    return ((*x[:2], r1 - r0, x[3]), (psi[0], h1 - h0, psi[2], d), mix,
            stride, run_affine)


DISCO_CALLS = [pytest.param(p, None, id=p) for p in sorted(DISCO_PLANS)] + [
    pytest.param(p, i, id=f"{p}-run{i}")
    for p in sorted(DISCO_RUNS) for i in range(len(DISCO_RUNS[p]))]


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A program compiled for a described chip is written to the
    persistent cache but cannot be read back without one: keep these
    compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes, dtypes=None):
    dtypes = dtypes or (jnp.float32,) * len(shapes)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in zip(shapes, dtypes)]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.fixture
def chip_dft(monkeypatch):
    """The longitude transform a TPU backend picks (DFT as GEMMs)."""
    from repro.core.sphere import fourier
    monkeypatch.setattr(fourier, "_MODE", "matmul")


#: the latent global block's two SHTs, through the Pallas wrappers: 641
#: channels on the 360x720 grid against order-major 360^3 tables --
#: forward (M, H, L), inverse (M, L, H) from (L, M) coefficients
LEGENDRE_CASES = {
    "forward": (lambda x, t: sht_forward_pallas(x, t, interpret=False),
                (1, 641, 360, 720), jnp.float32),
    "inverse": (lambda c, t: sht_inverse_pallas(c, t, 720, interpret=False),
                (1, 641, 360, 360), jnp.complex64),
}


@pytest.mark.parametrize("direction", sorted(LEGENDRE_CASES))
def test_legendre_latent_slab(one_chip, chip_dft, direction):
    fn, x, dtype = LEGENDRE_CASES[direction]
    _compile(fn, one_chip, x, (360, 360, 360), dtypes=(dtype, jnp.float32))


#: inverse transforms whose spectra keep fewer orders than the grid's
#: nlon//2+1, through the Pallas wrapper: SFNO's input-grid inverse (384
#: channels, 240 orders onto 721x1440) and the latent inverse of
#: ``LEGENDRE_CASES`` (360 of 361 orders onto 360x720).  Each names the
#: pattern of shapes that only a pad of the orders back to nlon//2+1, or
#: a DFT contraction over that many rows, would give.
SHORT_INVERSES = {
    "sfno_input_grid": (
        lambda c, t: sht_inverse_pallas(c, t, 1440, interpret=False),
        (384, 240, 240), (240, 240, 721),
        r"\[384,721,721\]|\[721,1440\]"),
    "fcn3_latent": (LEGENDRE_CASES["inverse"][0], LEGENDRE_CASES["inverse"][1],
                    (360, 360, 360), r"[\[,]361[\],]"),
}


@pytest.mark.parametrize("case", sorted(SHORT_INVERSES))
def test_inverse_synthesizes_only_the_orders_kept(one_chip, chip_dft, case):
    """The inverse DFT contracts the orders the transform keeps: no
    instruction of the compiled program has the zero-padded spectrum's
    or the full DFT matrix's shape."""
    import re
    fn, c, table, padded = SHORT_INVERSES[case]
    text = _compile(fn, one_chip, c, table,
                    dtypes=(jnp.complex64, jnp.float32)).as_text()
    assert re.search(padded, text) is None, re.search(padded, text).group()


@pytest.mark.parametrize("plan,run", DISCO_CALLS)
def test_disco_band_full_width_plan(one_chip, plan, run):
    x, psi, mix, stride, affine = _run_call(plan, run)
    d = psi[-1]
    compiled = _compile(
        lambda a, p, m: disco_band_contract(
            a, p, m, stride=stride, affine=affine, off0=-(d // 2),
            interpret=False),
        one_chip, x, psi, mix)
    out = compiled.out_info
    assert out.shape == (x[0], mix[1], psi[1], x[-1] // stride)


def test_crps_fused_full_state(one_chip):
    n = 72 * 721 * 1440
    _compile(lambda e, o: crps_fused(e, o, fair=True, interpret=False),
             one_chip, (2, n), (n,))


#: the named scope each Pallas kernel's calls may sit under
KERNEL_SCOPES = {
    "disco_band_contract": {"fcn3.encoder", "fcn3.local_conv",
                            "fcn3.decoder"},
    "legendre_contract": {"fcn3.spectral_conv"},
}


def test_fcn3_step_kernel_calls_carry_scopes(one_chip, chip_dft,
                                             monkeypatch):
    """The smoke-size FCN3 step over two members, compiled with the
    Pallas kernels for a v5e: each kernel call carries the named scope
    of the operator that makes it (``repro.telemetry.SCOPES``) in its
    ``op_name``, which is how a chip trace puts its time down to an
    operator."""
    import re

    from repro import telemetry
    from repro.configs import fcn3 as fcn3cfg
    from repro.core.fcn3 import FCN3
    from repro.kernels import config as kconfig
    monkeypatch.setattr(kconfig, "compiled_backend", lambda: True)
    cfg = fcn3cfg.fcn3_smoke()
    model = FCN3(cfg)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    x = (2, cfg.n_state, cfg.nlat, cfg.nlon)
    c = (2, cfg.n_cond_in, cfg.nlat, cfg.nlon)
    text = jax.jit(jax.vmap(model.apply, in_axes=(None, None, 0, 0))).lower(
        on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0))),
        on_chip(jax.eval_shape(model.make_buffers)),
        jax.ShapeDtypeStruct(x, jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct(c, jnp.float32, sharding=one_chip),
    ).compile().as_text()
    seen = {k: set() for k in KERNEL_SCOPES}
    for line in text.splitlines():
        if "tpu_custom_call" not in line or " = " not in line:
            continue
        kernel = line.split(" = ")[0].strip().lstrip("%").rsplit(".")[0]
        stack = re.search(r'op_name="([^"]*)"', line).group(1)
        scope = next((p for p in (q.rstrip(")").rsplit("(", 1)[-1]
                                  for q in reversed(stack.split("/")))
                      if p in telemetry.SCOPES), None)
        assert scope in KERNEL_SCOPES[kernel], (kernel, stack)
        seen[kernel].add(scope)
    assert seen == KERNEL_SCOPES


def test_sfno_step_kernel_calls_carry_scopes(one_chip, chip_dft,
                                             monkeypatch):
    """The smoke-size SFNO step compiled with the Pallas Legendre kernel
    for a v5e: each of its transforms (two per block, three in the
    first and last blocks, which resample) makes two kernel calls (real
    and imaginary parts), and every call sits under ``sfno.sht``."""
    import re

    from repro import telemetry
    from repro.configs import registry
    from repro.kernels import config as kconfig
    monkeypatch.setattr(kconfig, "compiled_backend", lambda: True)
    model = registry.build_model("sfno_smoke")
    cfg = model.cfg

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    text = jax.jit(model.apply).lower(
        on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0))),
        on_chip(jax.eval_shape(model.make_buffers)),
        jax.ShapeDtypeStruct((cfg.n_state, cfg.nlat, cfg.nlon),
                             jnp.float32, sharding=one_chip),
    ).compile().as_text()
    scopes = []
    for line in text.splitlines():
        if "tpu_custom_call" not in line or " = " not in line:
            continue
        stack = re.search(r'op_name="([^"]*)"', line).group(1)
        scopes.append(next((p for p in (q.rstrip(")").rsplit("(", 1)[-1]
                                        for q in reversed(stack.split("/")))
                            if p in telemetry.SCOPES), None))
    transforms = 2 * cfg.n_blocks + 2
    assert scopes == [telemetry.SCOPE_SFNO_SHT] * 2 * transforms
