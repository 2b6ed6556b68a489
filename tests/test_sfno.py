"""SFNO (``repro.core.sfno``) served through the FCN3 engine, against the
benchmark's plain float32 reference (``bench.reference.sfno``), at the
``sfno_smoke`` size on the CPU and on seeded random weights: one step,
a ``ForecastEngine`` rollout and a request served over HTTP.  Besides:
the resampling transforms of the first and last blocks, the named scopes
of the chunk program, and the engine's member-stepping rule at full
width."""

import dataclasses
import re
import threading
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import sfno as reflib
from repro import telemetry
from repro.configs import registry
from repro.core import sfno as sfnolib
from repro.core.sphere import spectral_conv as speclib
from repro.inference import EngineConfig, ForecastEngine
from repro.inference import engine as englib
from repro.serving import transport
from repro.serving.cache import ExecutableCache
from repro.serving.client import ForecastClient
from repro.serving.scheduler import ForecastScheduler, ModelPool, RequestSpec
from repro.serving.service import ForecastService

#: one float32 step at "highest" against the reference: the two compute
#: the same equations in a different order (FFT vs einsum layout, the
#: complex mix as two stacked real contractions), so they differ by
#: float32 rounding, ~3e-7 at this size; 1e-5 leaves room for the
#: rounding and none for a term left out
STEP_RTOL = 1e-5
#: three steps compound that rounding through the instance norms
ROLLOUT_RTOL = 1e-4


def _rel(got, want) -> float:
    got, want = np.asarray(got), np.asarray(want)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@pytest.fixture(scope="module")
def smoke():
    """The served model and the reference at ``sfno_smoke``, one seeded
    set of weights (the reference's init, the same pytree)."""
    model = registry.build_model("sfno_smoke")
    fields = {f.name: getattr(model.cfg, f.name)
              for f in dataclasses.fields(model.cfg)
              if f.name not in ("dtype", "kernels")}
    ref = reflib.Reference(reflib.Config.from_dict(fields))
    params = jax.jit(ref.model.init)(jax.random.PRNGKey(5))
    return model, ref, params, model.make_buffers()


def test_the_reference_and_the_model_share_a_pytree(smoke):
    model, ref, params, _ = smoke
    mine = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert jax.tree.structure(mine) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(params)):
        assert a.shape == b.shape


def test_one_step_matches_reference(smoke):
    model, ref, params, buffers = smoke
    state = ref._state(3, 0, ref.pct)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(model.apply)(params, buffers, state)
        want = ref._step(params, ref.buffers, state)
    assert got.shape == state.shape
    assert _rel(got, want) <= STEP_RTOL


def test_engine_rollout_matches_reference(smoke):
    model, ref, params, buffers = smoke
    steps = 3
    eng = ForecastEngine(model, EngineConfig(members=1, lead_chunk=2,
                                             spectra=True))
    with jax.default_matmul_precision("highest"):
        res = eng.forecast(params, buffers, ref._state(3, 0, ref.pct),
                           aux=None, key=jax.random.PRNGKey(0), steps=steps)
        want = ref.rollout(params, 3, 0, 1, steps, scored=False,
                           spectra=True)
        s = ref._state(3, 0, ref.pct)
        for _ in range(steps):
            s = ref._step(params, ref.buffers, s)
    assert res.final_noise is None          # deterministic: no noise carry
    assert _rel(res.final_state[0], s) <= ROLLOUT_RTOL
    spec = np.asarray(res.scores["spectrum"])
    assert spec.shape == (steps, model.cfg.n_state, model.cfg.lmax)
    for n in range(steps):
        assert _rel(spec[n], want[n]["spectrum"]) <= ROLLOUT_RTOL


def test_served_request_matches_engine():
    """``sfno_smoke`` over HTTP through the scheduler's pool, cache and
    service: its spectra are the direct engine's on the same bundle, to
    the bit (the same program), and its state is finite."""
    pool = ModelPool()
    sched = ForecastScheduler(pool=pool, cache=ExecutableCache(),
                              max_concurrency=1)
    server = ForecastService(scheduler=sched).make_server(port=0)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    spec = RequestSpec(config="sfno_smoke", members=1, lead_steps=3,
                       lead_chunk=1, scored=False, spectra=True,
                       return_state=True, sample=4)
    try:
        events = list(ForecastClient(port=server.server_address[1]).stream(
            spec.to_dict()))
    finally:
        server.shutdown()
        server.server_close()
        sched.close()
    assert events[-1]["event"] == "done", events[-1]
    served = np.concatenate([np.asarray(e["scores"]["spectrum"], np.float32)
                             for e in events if e["event"] == "chunk"])
    b = pool.get("sfno_smoke")
    res = ForecastEngine(b.model, spec.engine_config()).forecast(
        b.params, b.buffers, b.ds.state(spec.sample, 0), aux=None,
        key=jax.random.PRNGKey(spec.seed), steps=spec.lead_steps)
    np.testing.assert_array_equal(served, np.asarray(res.scores["spectrum"]))
    final = transport.decode_array(events[-1]["final_state"])
    np.testing.assert_array_equal(final, np.asarray(res.final_state))
    assert np.isfinite(final).all()


def test_resampling_blocks_round_trip_a_band_limited_field(smoke):
    """The last block's inverse transform (latent coefficients onto the
    input grid) then the first block's forward one (back to
    coefficients) return a field band-limited to l < lmax.  Synthesis is
    exact; the equiangular grid's trapezoidal quadrature is not, so the
    round trip is good to the quadrature's error at 33 rings (1.7e-3);
    the latent grid's Gauss quadrature is exact to float32 rounding."""
    model, _ref, _params, buffers = smoke
    cfg = model.cfg
    key_re, key_im = jax.random.split(jax.random.PRNGKey(2))
    shape = (3, cfg.lmax, cfg.lmax)
    mask = jnp.asarray(np.tril(np.ones(shape[1:], bool)))
    c = jnp.where(mask, jax.lax.complex(jax.random.normal(key_re, shape),
                                        jax.random.normal(key_im, shape)), 0)
    c = c.at[:, :, 0].set(jnp.real(c[:, :, 0]))   # real fields: m = 0 real
    to_grid = speclib.transforms({"wpct": buffers["latent"]["wpct"],
                                  "pct": buffers["in"]["pct"]}, cfg.nlon)[1]
    to_coeffs = speclib.transforms({"wpct": buffers["in"]["wpct"],
                                    "pct": buffers["latent"]["pct"]},
                                   cfg.latent_nlon)
    x = to_grid(c)
    assert x.shape == (3, cfg.nlat, cfg.nlon)
    back = to_coeffs[0](x)
    assert _rel(back, c) <= 1e-2
    # and onward to the latent grid and back through its exact transforms
    y = to_coeffs[1](back)
    assert y.shape == (3, cfg.latent_nlat, cfg.latent_nlon)
    latent_fwd = speclib.transforms(
        {"wpct": buffers["latent"]["wpct"], "pct": buffers["latent"]["pct"]},
        cfg.latent_nlon)[0]
    assert _rel(latent_fwd(y), back) <= 1e-5


#: instructions that compute nothing, left out of the count
PLUMBING = ("parameter", "constant", "tuple", "get-tuple-element")
INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%(?P<name>\S+) = .*?(?P<op>[a-z][\w-]*)\(.*$", re.M)


def _scope_of(line: str) -> str | None:
    m = re.search(r'op_name="([^"]*)"', line)
    for part in reversed(m.group(1).split("/") if m else []):
        part = part.rstrip(")").rsplit("(", 1)[-1]
        if part in telemetry.SCOPES:
            return part
    return None


def test_chunk_program_ops_carry_sfno_scopes(smoke):
    """Every SFNO scope is in the chunk program, every dot sits under a
    scope, and at most 10% of the instructions are unscoped (the bar of
    ``test_profile_names.py``)."""
    model, _ref, params, buffers = smoke
    eng = ForecastEngine(model, EngineConfig(members=1, lead_chunk=1,
                                             spectra=True))
    text = eng.lower_chunk(False, 1, params, buffers).compile().as_text()
    found, unscoped, total = set(), [], 0
    for m in INSTRUCTION.finditer(text):
        if m.group("op") in PLUMBING:
            continue
        total += 1
        scope = _scope_of(m.group(0))
        if scope is None:
            unscoped.append(m.group("name"))
            assert m.group("op") not in ("dot", "convolution"), m.group(0)
        found.add(scope)
    assert set(telemetry.SFNO_SCOPES) | {telemetry.SCOPE_PRODUCTS} <= found
    assert not found & set(telemetry.FCN3_SCOPES)
    assert telemetry.SCOPE_NOISE not in found
    assert len(unscoped) <= 0.10 * total, (len(unscoped), total)


def test_full_width_members_step_in_sequence():
    """At full width one member's activation passes
    ``SEQUENTIAL_MEMBER_BYTES`` in both families, so an ensemble steps
    its members one after another: SFNO's widest is the last block's
    768-channel MLP on the 721x1440 grid (1.6 GB in bf16), FCN3's its
    641-channel latent."""
    cfg = EngineConfig(members=2, compute_dtype="bfloat16")
    sfno = ForecastEngine(registry.build_model("sfno_full"), cfg)
    assert sfno.model.member_activation_bytes(2) == 768 * 721 * 1440 * 2
    assert sfno._members_in_sequence
    # FCN3 through the same rule, without its 721-degree noise tables
    fcn3 = types.SimpleNamespace(model=registry.build_model("full"), cfg=cfg)
    assert ForecastEngine._members_in_sequence.fget(fcn3)
    smoke = ForecastEngine(registry.build_model("sfno_smoke"), cfg)
    assert not smoke._members_in_sequence
    assert englib.SEQUENTIAL_MEMBER_BYTES == 2**28


def test_config_names_resolve_to_their_family():
    configs = registry.named_configs()
    assert {"smoke", "small", "full", "sfno_smoke", "sfno_full"} <= set(
        configs)
    assert registry.family("sfno_full").MODEL is sfnolib.SFNO
    assert isinstance(registry.config("sfno_full"), sfnolib.SFNOConfig)
    RequestSpec(config="sfno_full", members=1).validate()
    with pytest.raises(ValueError, match="unknown config"):
        RequestSpec(config="sfno_tiny").validate()


@pytest.mark.parametrize("config,baked", [
    ("smoke", True), ("small", True), ("full", False),
    ("sfno_smoke", True), ("sfno_full", False)])
def test_static_buffers_follow_the_legendre_table_bytes(config, baked):
    """A config's engines bake their geometry into the executable unless
    its float32 Legendre tables pass ``STATIC_TABLE_BYTES`` (64 MiB):
    FCN3 full's two latent tables take 373 MB, SFNO full's four 443 MB,
    the 1-degree FCN3's 5.8 MB."""
    from repro.serving import spec as speclib_
    assert RequestSpec(config=config).engine_config().static_buffers is baked
    tables = registry.config(config).legendre_table_bytes()
    assert (tables <= speclib_.STATIC_TABLE_BYTES) is baked
