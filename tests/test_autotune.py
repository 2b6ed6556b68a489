"""Tests for the Pallas block-size autotuner (repro.kernels.autotune).

Four layers, hermetic where it matters:

* **Candidate generation / feasibility** -- pure functions, no device:
  the default tile is always candidate 0, the lattice is deterministic,
  the VMEM budget and padding-waste bound prune, ``max_candidates``
  caps.
* **Sweep + winner selection** -- driven through an injectable fake
  timer (no kernel ever runs): fastest wins, ties prefer the default
  and then the lexicographically smallest dims, cache hits skip the
  sweep entirely.
* **Tuning cache** -- byte-identical files for identical sweeps
  (content addressing holds end to end), corrupt/stale entries read as
  absent, ``best_for`` serves the largest tuned slab and never returns
  a default no-op override.
* **Resolution** -- an installed cache with a non-default winner changes
  ``RequestSpec.engine_key()``; no cache (or explicit blocks) leaves
  keys bit-identical.  Plus padding exactness: every kernel produces
  the same numbers under *any* valid tile shape (property-tested via
  the hypothesis shim).

The bundle-tunings roundtrip (pack -> boot -> zero sweeps) lives in
``test_bundle.py`` alongside the other bundle lifecycle tests.
"""

import hashlib
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.kernels import autotune
from repro.kernels.autotune import TuningCache
from repro.kernels.config import (BLOCK_DEFAULTS, TILE_MULTIPLES, BlockConfig,
                                  KernelConfig, legal_tile)


def fake_timer(us_for):
    """A sweep timer that never runs the kernel: ``us_for(dims)`` -> us."""
    def timer(dims, fn):
        return us_for(dims) * 1e-6
    return timer


@pytest.fixture(autouse=True)
def no_leaked_cache():
    """Every test starts and ends with no process-active tuning cache."""
    previous = autotune.install_tuning_cache(None)
    yield
    autotune.install_tuning_cache(previous)


class TestCandidates:
    @pytest.mark.parametrize("op,shapes", [
        ("legendre", (16, 32, 17, 17)),
        ("disco", (2, 200, 64, 32, 5, 128, 3, 9, 16, 2)),
        ("crps", (4, 4096)),
        ("ssd", (6, 16, 2, 8, 1, 4)),
    ])
    def test_default_first_and_deterministic(self, op, shapes):
        cands = autotune.candidates(op, shapes)
        assert cands[0] == BLOCK_DEFAULTS[op]
        assert cands == autotune.candidates(op, shapes)
        # no duplicates; every non-default candidate is feasible
        seen = [tuple(sorted(d.items())) for d in cands]
        assert len(seen) == len(set(seen))
        for dims in cands[1:]:
            assert autotune.feasible(op, dims, shapes)

    @pytest.mark.parametrize("op", sorted(BLOCK_DEFAULTS))
    def test_defaults_obey_minor_dims_rule(self, op):
        # Mosaic refuses a block whose sublane dim is not a multiple of
        # 8 or whose lane dim is not a multiple of 128
        assert legal_tile(op, BLOCK_DEFAULTS[op])
        for name, mult in TILE_MULTIPLES[op].items():
            assert mult in (8, 128)
            assert BLOCK_DEFAULTS[op][name] % mult == 0

    @settings(max_examples=25, deadline=None)
    @given(op=st.sampled_from(["legendre", "disco", "crps"]),
           a=st.integers(1, 800), b=st.integers(1, 800),
           c=st.integers(1, 800), stride=st.sampled_from([1, 2]))
    def test_every_candidate_obeys_minor_dims_rule(self, op, a, b, c,
                                                   stride):
        shapes = {"legendre": (a, b, c, 1 + a % 17),
                  "disco": (2, a, 2 * b, b, 1 + c % 13, 4 * c, 7,
                            1 + a % 31, 1 + c, stride),
                  "crps": (2 + a % 6, b * c)}[op]
        cands = autotune.candidates(op, shapes, max_candidates=None)
        assert cands
        for dims in cands:
            assert legal_tile(op, dims), (op, shapes, dims)
            for name, mult in TILE_MULTIPLES[op].items():
                assert dims[name] % mult == 0

    def test_max_candidates_caps(self):
        shapes = (16, 200, 200, 17)
        assert len(autotune.candidates("legendre", shapes,
                                       max_candidates=4)) == 4
        unlimited = autotune.candidates("legendre", shapes,
                                        max_candidates=None)
        assert len(unlimited) > 4

    def test_vmem_budget_prunes_to_default(self):
        # a 16-byte budget admits nothing; the default stays sweepable
        cands = autotune.candidates("legendre", (16, 32, 17, 17),
                                    vmem_budget=16)
        assert cands == [BLOCK_DEFAULTS["legendre"]]

    def test_waste_bound_prunes(self):
        # n=100: n_blk=128 pads to 128 (waste 1.28, kept); n_blk=256
        # pads to 256 (waste 2.56 > 2.0, pruned).  The default (1024) is
        # exempt -- it must always be sweepable.
        cands = autotune.candidates("crps", (4, 100))
        assert cands[0] == {"n_blk": 1024}
        assert cands[1:] == [{"n_blk": 128}]


class TestSweepWinner:
    def test_fastest_wins(self, tmp_path):
        # candidates at (4, 300): default 1024 first, then 128/256/512
        entry = autotune.sweep_op(
            "crps", (4, 300), interpret=True,
            timer=fake_timer(lambda d: 5.0 if d["n_blk"] == 256 else 9.0))
        assert entry["dims"] == {"n_blk": 256}
        assert entry["swept"] is True
        assert entry["best_us"] < entry["default_us"]

    def test_tie_prefers_default(self):
        entry = autotune.sweep_op("crps", (4, 300), interpret=True,
                                  timer=fake_timer(lambda d: 7.0))
        assert entry["dims"] == BLOCK_DEFAULTS["crps"]
        assert entry["best_us"] == entry["default_us"]

    def test_tie_among_non_defaults_is_lexicographic(self):
        # 128/256/512 all beat the default equally -> smallest dims win
        entry = autotune.sweep_op(
            "crps", (4, 300), interpret=True,
            timer=fake_timer(
                lambda d: 9.0 if d == BLOCK_DEFAULTS["crps"] else 5.0))
        assert entry["dims"] == {"n_blk": 128}

    def test_best_never_worse_than_default(self):
        # adversarial timer: the default is the fastest candidate
        entry = autotune.sweep_op(
            "crps", (4, 300), interpret=True,
            timer=fake_timer(
                lambda d: 1.0 if d == BLOCK_DEFAULTS["crps"] else 0.5))
        # (a *slower* default still loses, but best <= default holds)
        assert entry["best_us"] <= entry["default_us"]

    def test_cache_hit_skips_sweep(self, tmp_path):
        cache = TuningCache(str(tmp_path))
        calls = []

        def counting(dims, fn):
            calls.append(dims)
            return 1e-6

        first = autotune.sweep_op("crps", (4, 300), interpret=True,
                                  timer=counting, cache=cache)
        assert first["swept"] is True and calls
        calls.clear()
        second = autotune.sweep_op("crps", (4, 300), interpret=True,
                                   timer=counting, cache=cache)
        assert second["swept"] is False
        assert not calls  # zero timer invocations on the hit
        assert second["dims"] == first["dims"]
        # force re-sweeps through the hit
        third = autotune.sweep_op("crps", (4, 300), interpret=True,
                                  timer=counting, cache=cache, force=True)
        assert third["swept"] is True and calls


class TestTuningCache:
    def _sweep_into(self, root) -> TuningCache:
        cache = TuningCache(str(root))
        autotune.sweep_op(
            "crps", (4, 300), interpret=True, cache=cache,
            timer=fake_timer(
                lambda d: 9.0 if d == BLOCK_DEFAULTS["crps"] else 5.0))
        return cache

    def test_identical_sweeps_write_identical_bytes(self, tmp_path):
        a = self._sweep_into(tmp_path / "a")
        b = self._sweep_into(tmp_path / "b")
        (name_a, _), = a.entries()
        (name_b, _), = b.entries()
        assert name_a == name_b  # content-addressed filename
        blob_a = open(os.path.join(a.root, name_a), "rb").read()
        blob_b = open(os.path.join(b.root, name_b), "rb").read()
        assert hashlib.sha256(blob_a).hexdigest() \
            == hashlib.sha256(blob_b).hexdigest()

    def test_corrupt_entry_reads_as_absent(self, tmp_path):
        cache = self._sweep_into(tmp_path)
        path = cache.entry_path("crps", (4, 300))
        with open(path, "w") as f:
            f.write("{not json")
        fresh = TuningCache(cache.root)
        assert fresh.get("crps", (4, 300)) is None
        assert fresh.entries() == []
        assert fresh.best_for("crps") is None
        # and the serve path degrades instead of crashing
        autotune.install_tuning_cache(fresh)
        assert autotune.resolve_kernel_config(None) is None

    def test_stale_jax_version_reads_as_absent(self, tmp_path):
        cache = self._sweep_into(tmp_path)
        path = cache.entry_path("crps", (4, 300))
        entry = json.load(open(path))
        entry["jax"] = "0.0.0-stale"
        with open(path, "w") as f:
            json.dump(entry, f)
        fresh = TuningCache(cache.root)
        assert fresh.get("crps", (4, 300)) is None
        assert fresh.best_for("crps") is None

    def test_invalid_dims_read_as_absent(self, tmp_path):
        cache = self._sweep_into(tmp_path)
        path = cache.entry_path("crps", (4, 300))
        entry = json.load(open(path))
        entry["dims"] = {"n_blk": -8}
        with open(path, "w") as f:
            json.dump(entry, f)
        assert TuningCache(cache.root).get("crps", (4, 300)) is None

    def test_best_for_serves_largest_slab(self, tmp_path):
        cache = TuningCache(str(tmp_path))
        for shapes, fast in (((4, 300), 128), ((4, 70000), 4096)):
            autotune.sweep_op(
                "crps", shapes, interpret=True, cache=cache,
                timer=fake_timer(
                    lambda d, fast=fast: 1.0 if d["n_blk"] == fast else 9.0))
        bc = cache.best_for("crps")
        assert bc == BlockConfig.make("crps", n_blk=4096)

    def test_best_for_default_winner_is_none(self, tmp_path):
        cache = TuningCache(str(tmp_path))
        autotune.sweep_op("crps", (4, 300), interpret=True, cache=cache,
                          timer=fake_timer(lambda d: 3.0))  # tie -> default
        assert cache.get("crps", (4, 300)) is not None
        assert cache.best_for("crps") is None  # no-op override elided


class TestResolution:
    def _tuned_cache(self, root) -> TuningCache:
        cache = TuningCache(str(root))
        autotune.sweep_op(
            "crps", (4, 300), interpret=True, cache=cache,
            timer=fake_timer(
                lambda d: 9.0 if d == BLOCK_DEFAULTS["crps"] else 5.0))
        return cache

    def test_no_cache_is_identity(self):
        assert autotune.resolve_kernel_config(None) is None
        kc = KernelConfig(sht="pallas", disco="pallas", interpret=True)
        assert autotune.resolve_kernel_config(kc) is kc

    def test_installed_cache_attaches_blocks(self, tmp_path):
        autotune.install_tuning_cache(self._tuned_cache(tmp_path))
        resolved = autotune.resolve_kernel_config(None)
        assert isinstance(resolved, KernelConfig)
        assert resolved.blocks_for("crps") \
            == BlockConfig.make("crps", n_blk=128)
        # explicit blocks on the request win over the cache
        pinned = KernelConfig(
            blocks=(BlockConfig.make("crps", n_blk=512),))
        assert autotune.resolve_kernel_config(pinned) is pinned

    def test_engine_key_rides_tunings(self, tmp_path):
        from repro.serving.spec import RequestSpec
        spec = RequestSpec(config="smoke", members=2, lead_steps=2,
                           lead_chunk=2)
        key_untuned = spec.engine_key()
        autotune.install_tuning_cache(self._tuned_cache(tmp_path))
        key_tuned = spec.engine_key()
        assert key_tuned != key_untuned
        autotune.install_tuning_cache(None)
        assert spec.engine_key() == key_untuned  # bit-identical fallback

    def test_install_returns_previous(self, tmp_path):
        cache = TuningCache(str(tmp_path))
        assert autotune.install_tuning_cache(cache) is None
        assert autotune.active_tuning_cache() is cache
        assert autotune.install_tuning_cache(None) is cache


class TestPaddingExactness:
    """Any valid tile shape computes the same numbers as the default:
    every kernel zero-pads its grid and slices the result exactly."""

    @settings(max_examples=5, deadline=None)
    @given(e=st.integers(2, 5), n=st.integers(3, 600),
           n_blk=st.sampled_from([8, 128, 512]))
    def test_crps_any_tile(self, e, n, n_blk):
        from repro.kernels.crps.crps import crps_fused
        rng = np.random.default_rng(e * 1000 + n)
        ens = jnp.asarray(rng.normal(size=(e, n)), jnp.float32)
        obs = jnp.asarray(rng.normal(size=(n,)), jnp.float32)
        got = crps_fused(ens, obs, fair=True, interpret=True,
                         blocks=BlockConfig.make("crps", n_blk=n_blk))
        want = crps_fused(ens, obs, fair=True, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    @settings(max_examples=4, deadline=None)
    @given(b=st.integers(1, 6), k=st.integers(2, 9), n=st.integers(2, 9),
           m=st.integers(1, 6),
           b_blk=st.sampled_from([2, 8]), k_blk=st.sampled_from([2, 8]),
           n_blk=st.sampled_from([2, 8]), m_blk=st.sampled_from([1, 4]))
    def test_legendre_any_tile(self, b, k, n, m, b_blk, k_blk, n_blk,
                               m_blk):
        from repro.kernels.legendre.legendre import legendre_contract
        rng = np.random.default_rng(b * 100 + k * 10 + n + m)
        x = jnp.asarray(rng.normal(size=(b, k, m)), jnp.float32)
        t = jnp.asarray(rng.normal(size=(m, k, n)), jnp.float32)
        bc = BlockConfig.make("legendre", b_blk=b_blk, k_blk=k_blk,
                              n_blk=n_blk, m_blk=m_blk)
        got = legendre_contract(x, t, interpret=True, blocks=bc)
        want = legendre_contract(x, t, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    @settings(max_examples=4, deadline=None)
    @given(r=st.integers(1, 20), h=st.integers(2, 7),
           c_blk=st.sampled_from([8, 16]), w_blk=st.sampled_from([8, 128]))
    def test_disco_any_tile(self, r, h, c_blk, w_blk):
        from repro.kernels.disco.disco import disco_band_contract
        rng = np.random.default_rng(r * 10 + h)
        x = jnp.asarray(rng.normal(size=(1, r, h + 2, 32)), jnp.float32)
        psi = jnp.asarray(rng.normal(size=(2, h, 3, 5)), jnp.float32)
        mix = jnp.asarray(rng.normal(size=(2, 3, r)), jnp.float32)
        bc = BlockConfig.make("disco", c_blk=c_blk, w_blk=w_blk)
        got = disco_band_contract(x, psi, mix, stride=2, off0=-2,
                                  interpret=True, blocks=bc)
        want = disco_band_contract(x, psi, mix, stride=2, off0=-2,
                                   interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    @settings(max_examples=4, deadline=None)
    @given(bc_n=st.integers(1, 5), bc_blk=st.sampled_from([2, 4]))
    def test_ssd_any_tile(self, bc_n, bc_blk):
        from repro.kernels.ssd.ssd import ssd_intra_chunk
        rng = np.random.default_rng(bc_n)
        l, h, p, g, n = 4, 2, 3, 1, 2
        x = jnp.asarray(rng.normal(size=(bc_n, l, h, p)), jnp.float32)
        da = jnp.cumsum(-jnp.abs(jnp.asarray(
            rng.normal(size=(bc_n, l, h)), jnp.float32)) * 0.05, axis=1)
        b = jnp.asarray(rng.normal(size=(bc_n, l, g, n)), jnp.float32)
        c = jnp.asarray(rng.normal(size=(bc_n, l, g, n)), jnp.float32)
        blk = BlockConfig.make("ssd", bc_blk=bc_blk)
        got_y, got_st = ssd_intra_chunk(x, da, b, c, n_groups=g,
                                        interpret=True, blocks=blk)
        want_y, want_st = ssd_intra_chunk(x, da, b, c, n_groups=g,
                                          interpret=True)
        np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(got_st), np.asarray(want_st),
                                   rtol=2e-5, atol=2e-5)
