"""Tests for content-addressed warm-start bundles (zero-cold-start
replica boot).

The load-bearing guarantees:

* ``pack`` produces a content-addressed bundle whose manifest hash is
  reproducible and whose ``verify`` passes in the building process;
* a "fresh process" (geometry caches cleared, new pool/scheduler) booted
  via ``boot_scheduler`` serves the packed shape **bit-identically** to
  a direct engine forecast with *zero* compiles: every chunk program
  comes from the bundle's blobs, the jit dispatch counter stays 0 and
  the readonly cache records no misses;
* any mismatch -- tampered blob, edited manifest, foreign environment,
  unbundled request shape -- refuses with a diagnostic instead of
  silently recompiling.
"""

import hashlib
import json
import os
import shutil
import tarfile

import jax
import numpy as np
import pytest

from repro import compile_cache
from repro.inference import ForecastEngine
from repro.serving import transport
from repro.serving.bundle import (BundleError, WarmStartBundle, _canonical,
                                  boot_scheduler, pack)
from repro.serving.cache import ReadOnlyCacheMiss
from repro.serving.scheduler import ModelPool, RequestSpec

SPEC = RequestSpec(config="smoke", members=2, lead_steps=2, lead_chunk=2,
                   scored=True, return_state=True)


@pytest.fixture(scope="module", autouse=True)
def xla_cache_dir(tmp_path_factory):
    """This module's compilation cache, placed from outside the program
    the way a deployment places it: ``JAX_COMPILATION_CACHE_DIR``."""
    path = str(tmp_path_factory.mktemp("xla-cache"))
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv(compile_cache.ENV_VAR, path)
        yield path


@pytest.fixture(scope="module")
def bundle_dir(tmp_path_factory):
    out = str(tmp_path_factory.mktemp("bundles") / "smoke-bundle")
    return pack([SPEC], out=out)


@pytest.fixture(scope="module")
def pool():
    return ModelPool()


@pytest.fixture(scope="module")
def booted(bundle_dir, pool):
    # Simulate a fresh replica process: drop every memoized geometry
    # cache so the bundle's installed plans are the only warm state the
    # new scheduler can draw on.
    from repro.core.sphere import disco as discolib
    from repro.core.sphere import legendre as leg
    discolib._cached_plan.cache_clear()
    discolib._PLAN_OVERRIDES.clear()
    leg._cached_table.cache_clear()
    leg._TABLE_OVERRIDES.clear()
    sched = boot_scheduler(bundle_dir, pool=pool, max_concurrency=1)
    yield sched
    sched.close()


@pytest.fixture(scope="module")
def direct(booted, pool):
    """Direct engine forecast for SPEC -- the bundle-served path must
    reproduce it bit-for-bit.  Depends on ``booted`` so the direct
    engine also runs over the bundle-installed geometry plans."""
    b = pool.get("smoke")
    eng = ForecastEngine(b.model, SPEC.engine_config())
    return eng.forecast(b.params, b.buffers, b.ds.state(SPEC.sample, 0),
                        lambda n: b.ds.aux_fields(6.0 * (n + 1)),
                        jax.random.PRNGKey(SPEC.seed),
                        steps=SPEC.lead_steps,
                        truth=lambda n: b.ds.state(SPEC.sample, n + 1))


class TestPackAndManifest:
    def test_bundle_is_content_addressed(self, bundle_dir):
        b = WarmStartBundle.load(bundle_dir)
        want = hashlib.sha256(_canonical(b.manifest)).hexdigest()
        assert b.bundle_id == want
        b.verify()  # building process: must be servable as packed

    def test_manifest_declares_engines_blobs_and_plans(self, bundle_dir):
        m = WarmStartBundle.load(bundle_dir).manifest
        assert m["format"] == "fcn3-warm-bundle/1"
        assert [e["spec"] for e in m["engines"]] == [SPEC.to_dict()]
        prog = m["engines"][0]["programs"][0]
        assert prog["batch"] is None and prog["chunk_lengths"] == [2]
        blobs = [f"blobs/chunk_{t}.stablehlo" for t in prog["tokens"]]
        for rel in blobs + list(m["plans"]):
            assert rel in m["files"]
            assert os.path.getsize(os.path.join(bundle_dir, rel)) \
                == m["files"][rel]["bytes"]
        kinds = {os.path.basename(p).split("_")[-1] for p in m["plans"]}
        assert kinds == {"disco.npz", "legendre.npz"}

    def test_specs_roundtrip(self, bundle_dir):
        assert WarmStartBundle.load(bundle_dir).specs() == [SPEC]

    def test_tar_archive_loads_and_verifies(self, bundle_dir, tmp_path):
        t = str(tmp_path / "bundle.tar")
        with tarfile.open(t, "w") as tf:
            for dirpath, dirnames, filenames in os.walk(bundle_dir):
                dirnames.sort()
                for name in sorted(filenames):
                    path = os.path.join(dirpath, name)
                    tf.add(path, recursive=False, arcname=os.path.relpath(
                        path, bundle_dir).replace(os.sep, "/"))
        b = WarmStartBundle.load(t)
        assert b.root != bundle_dir  # extracted to a temp dir
        b.verify()


class TestZeroColdStartBoot:
    def test_every_program_served_from_blobs(self, booted):
        info = booted.bundle_info
        assert info["programs"] >= 1
        assert info["disk_hits"] == info["programs"]
        stats = booted.cache.stats()
        assert stats["readonly"] is True
        # compile_s only accrues blob-import time here; nothing compiled
        assert stats["misses"] == 0
        assert stats["disk_hits"] == info["disk_hits"]

    def test_plans_installed_from_bundle(self, booted):
        from repro.core.sphere import disco as discolib
        from repro.core.sphere import legendre as leg
        assert discolib._PLAN_OVERRIDES and leg._TABLE_OVERRIDES
        # the model build drew from the overrides, not the lru caches
        assert discolib._cached_plan.cache_info().currsize == 0
        assert leg._cached_table.cache_info().currsize == 0

    def test_served_bit_identical_with_zero_compiles(self, booted, direct):
        raw = booted.submit(SPEC).events()
        events = [json.loads(transport.dump_event(ev)) for ev in raw]
        res = transport.collect(iter(events))
        assert res.timing["compile_s"] == 0.0
        assert res.cache["misses"] == 0
        for name, arr in direct.scores.items():
            np.testing.assert_array_equal(res.scores[name],
                                          np.asarray(arr), err_msg=name)
        np.testing.assert_array_equal(res.final_state,
                                      np.asarray(direct.final_state))
        eng = booted._engines.snapshot()[SPEC.engine_key()]
        assert eng.dispatch_counts["jit"] == 0
        assert eng.dispatch_counts["aot"] > 0

    def test_stats_carry_bundle_provenance(self, booted, bundle_dir):
        stats = booted.stats()
        b = WarmStartBundle.load(bundle_dir)
        assert stats["bundle"]["bundle_id"] == b.bundle_id
        assert stats["bundle"]["disk_hits"] == stats["bundle"]["programs"]

    def test_unbundled_shape_refuses_not_recompiles(self, booted):
        # lead_steps=4 would reuse the bundled chunk-length-2 program;
        # lead_steps=3 needs an uneven final chunk the bundle lacks
        other = RequestSpec(**{**SPEC.to_dict(), "lead_steps": 3})
        with pytest.raises(ReadOnlyCacheMiss, match="refusing"):
            booted.warmup(other)
        assert booted.cache.stats()["misses"] == 0


class TestRefusal:
    def _copy(self, bundle_dir, tmp_path, name):
        dst = str(tmp_path / name)
        shutil.copytree(bundle_dir, dst)
        return dst

    def _rewrite_manifest(self, root, mutate, readdress=False):
        """Apply ``mutate`` to the manifest; with ``readdress`` the
        bundle_id is recomputed, isolating the non-hash checks."""
        mpath = os.path.join(root, "manifest.json")
        with open(mpath) as f:
            m = json.load(f)
        mutate(m)
        if readdress:
            m["bundle_id"] = hashlib.sha256(_canonical(m)).hexdigest()
        with open(mpath, "w") as f:
            json.dump(m, f, indent=2, sort_keys=True)

    def test_tampered_blob_refused(self, bundle_dir, tmp_path):
        root = self._copy(bundle_dir, tmp_path, "tampered")
        rel = next(r for r in WarmStartBundle.load(root).manifest["files"]
                   if r.startswith("blobs/"))
        with open(os.path.join(root, rel), "ab") as f:
            f.write(b"x")
        with pytest.raises(BundleError, match="sha256 mismatch"):
            WarmStartBundle.load(root).verify()

    def test_foreign_environment_refused(self, bundle_dir, tmp_path):
        root = self._copy(bundle_dir, tmp_path, "foreign")
        self._rewrite_manifest(
            root, lambda m: m["environment"].update(backend="tpu"),
            readdress=True)
        with pytest.raises(BundleError,
                           match="environment mismatch on 'backend'"):
            WarmStartBundle.load(root).verify(deep=False)

    def test_edited_manifest_breaks_content_address(self, bundle_dir,
                                                    tmp_path):
        root = self._copy(bundle_dir, tmp_path, "edited")
        self._rewrite_manifest(
            root, lambda m: m["environment"].update(jax="99.0"))
        with pytest.raises(BundleError, match="content address"):
            WarmStartBundle.load(root).verify(deep=False)

    def test_verify_reports_every_problem_at_once(self, bundle_dir,
                                                  tmp_path):
        root = self._copy(bundle_dir, tmp_path, "multi")
        self._rewrite_manifest(
            root, lambda m: m["environment"].update(backend="tpu",
                                                    jaxlib="0.0.1"))
        with pytest.raises(BundleError) as e:
            WarmStartBundle.load(root).verify(deep=False)
        msg = str(e.value)
        for frag in ("content address", "'backend'", "'jaxlib'"):
            assert frag in msg

    def test_unsupported_format_refused(self, bundle_dir, tmp_path):
        root = self._copy(bundle_dir, tmp_path, "fmt")
        self._rewrite_manifest(root,
                               lambda m: m.update(format="bogus/9"))
        with pytest.raises(BundleError, match="format"):
            WarmStartBundle.load(root)

    def test_missing_manifest_refused(self, tmp_path):
        empty = tmp_path / "not-a-bundle"
        empty.mkdir()
        with pytest.raises(BundleError, match="manifest.json"):
            WarmStartBundle.load(str(empty))
        with pytest.raises(BundleError, match="does not exist"):
            WarmStartBundle.load(str(tmp_path / "nope"))


class TestLauncherCli:
    def test_inspect_and_verify(self, bundle_dir, capsys):
        from repro.launch import bundle as cli
        with pytest.raises(SystemExit) as e:
            cli.main(["inspect", bundle_dir])
        assert e.value.code == 0
        summary = json.loads(capsys.readouterr().out)
        assert summary["bundle_id"] \
            == WarmStartBundle.load(bundle_dir).bundle_id
        assert summary["files"] > 0 and summary["total_bytes"] > 0
        with pytest.raises(SystemExit) as e:
            cli.main(["verify", bundle_dir])
        assert e.value.code == 0
        assert "[bundle] OK" in capsys.readouterr().out

    def test_verify_exit_1_on_refusal(self, bundle_dir, tmp_path, capsys):
        from repro.launch import bundle as cli
        root = str(tmp_path / "bad")
        shutil.copytree(bundle_dir, root)
        rel = next(r for r in WarmStartBundle.load(root).manifest["files"]
                   if r.startswith("blobs/"))
        with open(os.path.join(root, rel), "ab") as f:
            f.write(b"x")
        with pytest.raises(SystemExit) as e:
            cli.main(["verify", root])
        assert e.value.code == 1
        assert "REFUSED" in capsys.readouterr().out


class TestServiceIntegration:
    def test_healthz_advertises_bundle_id(self, booted, bundle_dir):
        from repro.serving.client import ForecastClient
        from repro.serving.service import ForecastService
        service = ForecastService(scheduler=booted)
        server = service.make_server("127.0.0.1", 0)
        import threading
        t = threading.Thread(target=server.serve_forever, daemon=True)
        t.start()
        try:
            client = ForecastClient(port=server.server_address[1])
            health = client.health()
            assert health["ok"] is True
            assert health["bundle_id"] \
                == WarmStartBundle.load(bundle_dir).bundle_id
            assert client.stats()["bundle"]["bundle_id"] \
                == health["bundle_id"]
        finally:
            server.shutdown()
            server.server_close()
            t.join(timeout=5)


class TestBundleTunings:
    """Tunings ride the bundle: a replica booted from a bundle packed
    under an installed ``TuningCache`` resolves the same ``BlockConfig``
    the executables were compiled for -- zero sweeps, zero compiles."""

    def test_pack_boot_roundtrip_zero_sweeps(self, tmp_path):
        from repro.kernels import autotune
        from repro.kernels.config import BLOCK_DEFAULTS

        # a real cache entry with a non-default winner, built hermetically
        # (fake timer: the sweep never runs a kernel)
        cache = autotune.TuningCache(str(tmp_path / "tuning"))
        def timer(dims, fn):
            return (9.0 if dims == BLOCK_DEFAULTS["crps"] else 5.0) * 1e-6
        autotune.sweep_op("crps", (4, 300), interpret=True, cache=cache,
                          timer=timer)
        assert cache.best_for("crps") is not None

        spec = RequestSpec(config="smoke", members=2, lead_steps=1,
                           lead_chunk=1, scored=True)
        prev = autotune.install_tuning_cache(cache)
        try:
            out = pack([spec], out=str(tmp_path / "tuned-bundle"))
            manifest = WarmStartBundle.load(out).manifest
            assert manifest["tunings"], "pack dropped the active tunings"
            # fresh replica: no local cache -- the bundle is the source
            autotune.install_tuning_cache(None)
            sched = boot_scheduler(out, max_concurrency=1)
            try:
                active = autotune.active_tuning_cache()
                assert active is not None
                assert active.root.startswith(str(out))
                # zero sweeps: the packed entry is a cache hit
                resweep = autotune.sweep_op(
                    "crps", (4, 300), interpret=True, cache=active,
                    timer=timer)
                assert resweep["swept"] is False
                # zero compiles: the tuned engine key matches the
                # bundle's executables exactly
                res = sched.submit(spec).result()
                assert res.timing["compile_s"] == 0.0
                eng = sched._engines.snapshot()[spec.engine_key()]
                assert eng.dispatch_counts["jit"] == 0
                kc = spec.engine_config().kernels
                assert kc is not None and kc.blocks_for("crps") is not None
            finally:
                sched.close()
        finally:
            autotune.install_tuning_cache(prev)


class TestCompileCachePlacement:
    """One compilation cache per process, placed from outside: with
    ``JAX_COMPILATION_CACHE_DIR`` set nothing points JAX anywhere else
    (not the bundle build, not the replica boot, not the launcher);
    without it the cache sits at one fixed path in the checkout."""

    def test_pack_and_boot_keep_the_env_dir(self, bundle_dir, booted,
                                            xla_cache_dir):
        assert jax.config.jax_compilation_cache_dir == xla_cache_dir
        # the bundle carries the programs the build compiled, and boot
        # copied them into the one cache directory
        packed = compile_cache.entries(os.path.join(bundle_dir, "xla"))
        assert packed
        assert set(packed) <= set(compile_cache.entries(xla_cache_dir))

    def test_pack_carries_only_the_programs_it_used(self, bundle_dir,
                                                    xla_cache_dir,
                                                    tmp_path):
        # an unrelated program in the shared cache (another shape, an
        # earlier build) must neither ship nor change the packed content
        stray = os.path.join(xla_cache_dir, "jit_unrelated-0123abcd-cache")
        with open(stray, "wb") as f:
            f.write(b"not this bundle's program")
        try:
            again = pack([SPEC], out=str(tmp_path / "again"))
        finally:
            os.remove(stray)
        first = WarmStartBundle.load(bundle_dir).manifest["files"]
        second = WarmStartBundle.load(again).manifest["files"]
        xla = {k: v for k, v in second.items() if k.startswith("xla/")}
        assert xla, "the rebuild packed no compiled program"
        assert "xla/" + os.path.basename(stray) not in second
        # the same programs, byte for byte (the rebuild may skip a few
        # eager conversions whose inputs this process already cached)
        assert all(first.get(k) == v for k, v in xla.items())

    def test_build_service_keeps_the_env_dir(self, xla_cache_dir):
        from repro.launch import service as launcher
        args = launcher.build_parser().parse_args(
            ["--config", "smoke", "--port", "0", "--log-level", "WARNING"])
        service, startup = launcher.build_service(args)
        try:
            assert jax.config.jax_compilation_cache_dir == xla_cache_dir
            assert set(startup["preload"]["smoke"]) == {"plans_s",
                                                        "calibrate_s"}
        finally:
            service.close()

    def test_default_is_one_fixed_path_in_the_checkout(self, monkeypatch,
                                                       xla_cache_dir):
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        monkeypatch.delenv(compile_cache.ENV_VAR)
        try:
            assert compile_cache.cache_dir() == os.path.join(repo,
                                                             ".jax_cache")
            assert compile_cache.configure() == compile_cache.DEFAULT_DIR
            assert jax.config.jax_compilation_cache_dir \
                == compile_cache.DEFAULT_DIR
        finally:
            monkeypatch.undo()
            assert compile_cache.configure() == xla_cache_dir
