"""Content-addressed warm-start bundles: zero-cold-start replicas.

A fresh serving replica normally pays the full trace + compile for every
chunk program before its first forecast.  A **bundle** packs everything
a warm process accumulated so a new replica boots by *fetching* instead
of *compiling*:

* ``blobs/chunk_<token>.stablehlo`` -- the ``jax.export`` StableHLO
  blobs from the executable cache (skip Python tracing/lowering);
* ``xla/`` -- the compiled programs the build looked up in the XLA
  compilation cache (``repro.compile_cache``), copied into the booting
  replica's cache (skip the backend compile of the restored modules);
* ``plans/*.npz`` -- precomputed geometry: DISCO psi tensors with their
  memoized banded splits and the SHT Legendre tables (skip the host-side
  plan construction);
* ``manifest.json`` -- the engine-pool manifest: which request shapes
  (``RequestSpec``), coalesced batch sizes, chunk lengths and executable
  tokens the bundle serves, plus per-file sha256 hashes and the
  environment the bundle was built in.

**Key hygiene.**  A bundle is only valid for the exact (jax version,
backend platform, ``repro`` source fingerprint, ``EngineConfig`` set) it
was built for -- the same scoping ``ExecutableKey.token`` bakes into
every blob filename.  ``bundle_id`` is the sha256 of the canonical
manifest (content addressing: two builds of identical content agree on
the id; any edit changes it).

**Refusal semantics.**  A replica booting from a bundle must never
silently recompile: ``WarmStartBundle.verify`` refuses on any
environment or hash mismatch with a diagnostic naming the exact field,
and the boot path uses ``ExecutableCache(readonly=True)``, which raises
``ReadOnlyCacheMiss`` instead of compiling.  See docs/deployment.md for
the build -> publish -> boot lifecycle.

This module stays importable without jax (like the rest of the client
surface); jax and the scheduler stack are imported inside the functions
that need them.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import logging
import os
import shutil
import tarfile
import tempfile

import jax
import numpy as np

from repro import compile_cache
from repro.serving.cache import (ExecutableKey, ReadOnlyCacheMiss,
                                 _code_fingerprint)
from repro.serving.spec import RequestSpec

_logger = logging.getLogger("repro.serving.bundle")

#: manifest schema version; bump on any incompatible layout change
BUNDLE_FORMAT = "fcn3-warm-bundle/1"

#: environment fields that must match exactly for a bundle to be usable
#: (each one invalidates either the StableHLO blobs or the XLA cache)
_STRICT_ENV = ("jax", "jaxlib", "backend", "source_fingerprint")


class BundleError(RuntimeError):
    """A bundle cannot be built, verified or booted; the message says
    exactly which manifest field, file or executable key failed."""


def environment() -> dict:
    """The environment fingerprint a bundle is keyed by.

    ``jax``/``jaxlib``/``backend``/``source_fingerprint`` must match
    exactly between build and boot (they scope the StableHLO blobs and
    the XLA cache); ``python`` is recorded for diagnostics only.
    """
    import platform

    import jaxlib
    return {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "backend": jax.default_backend(),
        "source_fingerprint": _code_fingerprint(),
        "python": platform.python_version(),
    }


def _sha256_file(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


def _canonical(manifest: dict) -> bytes:
    """Canonical manifest bytes for content addressing: sorted keys,
    compact separators, ``bundle_id`` itself excluded."""
    trimmed = {k: v for k, v in manifest.items() if k != "bundle_id"}
    return json.dumps(trimmed, sort_keys=True,
                      separators=(",", ":")).encode("utf-8")


def _save_plan_npz(path: str, payload: dict) -> None:
    """One plan payload -> npz: arrays as entries, scalars as a JSON
    ``__meta__`` byte array (npz has no native scalar metadata)."""
    arrays = {k: v for k, v in payload.items() if isinstance(v, np.ndarray)}
    meta = {k: v for k, v in payload.items() if k not in arrays}
    blob = json.dumps(meta).encode("utf-8")
    np.savez(path, __meta__=np.frombuffer(blob, np.uint8), **arrays)


def _load_plan_npz(path: str) -> dict:
    with np.load(path) as z:
        meta = json.loads(bytes(z["__meta__"].tobytes()).decode("utf-8"))
        arrays = {k: z[k] for k in z.files if k != "__meta__"}
    return {**meta, **arrays}


def _install_plan_payload(payload: dict) -> None:
    """Install one deserialized plan payload into the matching
    geometry-cache override registry."""
    kind = payload.get("kind")
    if kind == "disco":
        from repro.core.sphere import disco as discolib
        discolib.install_plan(payload)
    elif kind == "legendre":
        from repro.core.sphere import legendre as leg
        leg.install_legendre_table(
            int(payload["lmax"]), int(payload["mmax"]),
            np.asarray(payload["colat"], np.float64),
            np.asarray(payload["table"], np.float64))
    else:
        raise BundleError(f"unknown plan payload kind {kind!r}")


# ---------------------------------------------------------------------------
# Packing
# ---------------------------------------------------------------------------

def pack(specs: list[RequestSpec], out: str | None = None,
         max_batch: int = 1, ckpts: dict[str, str] | None = None,
         tar: bool = False, out_dir: str = "bundles",
         verbose: bool = False) -> str:
    """Build a warm-start bundle for ``specs`` and return its path.

    Builds the model pool and compiles the serial chunk programs for
    every spec (plus the coalesced ``max_batch``-request programs when
    ``max_batch`` > 1) with persistence on, then packs the resulting
    StableHLO blobs, the compiled programs, the geometry plans and the
    engine-pool manifest.  With ``out=None`` the bundle is written
    to ``<out_dir>/fcn3-bundle-<bundle_id[:12]>`` (content-addressed
    name); ``tar=True`` produces a single ``.tar`` archive instead of a
    directory.

    The bundle's ``xla/`` carries the compiled programs this build
    looked up in the process's compilation cache (``repro.compile_cache``),
    whether it compiled them or found them there.
    """

    def _log(msg: str) -> None:
        # verbose promotes build progress to INFO; it always remains
        # visible at DEBUG for anyone wiring up repro.serving.* logging
        _logger.log(logging.INFO if verbose else logging.DEBUG, msg)

    # staging lives next to the final path so the finalizing rename is
    # atomic (same filesystem)
    if out is not None:
        base = os.path.dirname(os.path.abspath(out))
    else:
        base = out_dir
    os.makedirs(base, exist_ok=True)
    staging = tempfile.mkdtemp(prefix=".fcn3-bundle-build-", dir=base)
    try:
        blobs_dir = os.path.join(staging, "blobs")
        xla_dir = compile_cache.configure()

        from repro.serving.cache import ExecutableCache
        from repro.serving.scheduler import ForecastScheduler, ModelPool
        pool = ModelPool(ckpts)
        sched = ForecastScheduler(
            pool=pool, cache=ExecutableCache(persist_dir=blobs_dir))
        engines: list[dict] = []
        plan_payloads: list[dict] = []
        plan_seen: set = set()
        # every program the build needs goes through the persistent
        # cache, where it is recorded, not through a jit cache in memory
        jax.clear_caches()
        with compile_cache.recording() as used:
            try:
                for spec in specs:
                    spec.validate()
                    _log(f"warming {spec.to_dict()}")
                    batches = [None] + ([max_batch] if max_batch > 1 else [])
                    programs = []
                    for b in batches:
                        out_warm = sched.warmup(spec, batch=b)
                        engine, _ = sched.engine_for(spec)
                        lens = engine.chunk_lengths(spec.lead_steps)
                        tokens = [ExecutableKey.for_engine(
                            spec.config, engine, spec.scored, k,
                            batch=b).token() for k in lens]
                        programs.append({
                            "batch": b, "chunk_lengths": lens,
                            "tokens": tokens,
                            "compile_s": round(out_warm["compile_s"], 3)})
                    engine, _ = sched.engine_for(spec)
                    engines.append({
                        "spec": spec.to_dict(), "programs": programs,
                        "estimated_bytes": engine.estimated_bytes()})
                    for payload in engine.plan_exports():
                        pk = (payload["kind"],
                              json.dumps(payload.get("key",
                                                     [payload.get("lmax"),
                                                      payload.get("mmax")])))
                        if pk in plan_seen:
                            continue
                        plan_seen.add(pk)
                        plan_payloads.append(payload)
            finally:
                sched.close()

        # the compiled programs this build looked up: new entries and
        # hits alike (a program the cache already held must ship too),
        # and nothing else the shared cache holds
        os.makedirs(os.path.join(staging, "xla"), exist_ok=True)
        for name in compile_cache.entries(xla_dir):
            if name.removesuffix("-cache") in used:
                shutil.copyfile(os.path.join(xla_dir, name),
                                os.path.join(staging, "xla", name))

        plans_dir = os.path.join(staging, "plans")
        os.makedirs(plans_dir, exist_ok=True)
        plan_files = []
        for i, payload in enumerate(plan_payloads):
            name = f"plan_{i:02d}_{payload['kind']}.npz"
            _save_plan_npz(os.path.join(plans_dir, name), payload)
            plan_files.append(f"plans/{name}")
        _log(f"exported {len(plan_files)} geometry plan(s)")

        # Pack the active tuning cache: the executables above were
        # compiled for whatever BlockConfig the installed tunings
        # resolved into engine_config, so the booting replica must
        # resolve the *same* tunings to derive matching keys -- shipping
        # the entries is what makes that zero-sweep.
        from repro.kernels import autotune
        tuning_files = []
        active = autotune.active_tuning_cache()
        if active is not None:
            tunings_dir = os.path.join(staging, "tunings")
            os.makedirs(tunings_dir, exist_ok=True)
            for name, _entry in active.entries():
                shutil.copyfile(os.path.join(active.root, name),
                                os.path.join(tunings_dir, name))
                tuning_files.append(f"tunings/{name}")
            _log(f"packed {len(tuning_files)} kernel tuning(s)")

        files = {}
        for dirpath, dirnames, filenames in os.walk(staging):
            dirnames.sort()
            for name in sorted(filenames):
                path = os.path.join(dirpath, name)
                rel = os.path.relpath(path, staging).replace(os.sep, "/")
                files[rel] = {"sha256": _sha256_file(path),
                              "bytes": os.path.getsize(path)}

        manifest = {
            "format": BUNDLE_FORMAT,
            "environment": environment(),
            "engines": engines,
            "plans": plan_files,
            "tunings": tuning_files,
            "files": files,
        }
        bundle_id = hashlib.sha256(_canonical(manifest)).hexdigest()
        manifest["bundle_id"] = bundle_id
        with open(os.path.join(staging, "manifest.json"), "w") as f:
            json.dump(manifest, f, indent=2, sort_keys=True)

        if out is None:
            os.makedirs(out_dir, exist_ok=True)
            out = os.path.join(out_dir, f"fcn3-bundle-{bundle_id[:12]}")
            if tar:
                out += ".tar"
        if os.path.exists(out):
            raise BundleError(f"bundle path {out!r} already exists; "
                              f"refusing to overwrite")
        if tar or out.endswith(".tar"):
            parent = os.path.dirname(out)
            if parent:
                os.makedirs(parent, exist_ok=True)
            tmp = f"{out}.tmp.{os.getpid()}"
            with tarfile.open(tmp, "w") as tf:
                for rel in sorted([*files, "manifest.json"]):
                    tf.add(os.path.join(staging, rel), arcname=rel,
                           recursive=False)
            os.replace(tmp, out)
            shutil.rmtree(staging, ignore_errors=True)
        else:
            parent = os.path.dirname(out)
            if parent:
                os.makedirs(parent, exist_ok=True)
            os.replace(staging, out)
        _log(f"bundle {bundle_id[:12]} -> {out}")
        return out
    except BaseException:
        shutil.rmtree(staging, ignore_errors=True)
        raise


# ---------------------------------------------------------------------------
# Loading / booting
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class WarmStartBundle:
    """A loaded bundle: the manifest plus the on-disk root directory.

    ``load`` -> ``verify`` -> ``install_plans`` + ``install_xla_cache``
    -> ``boot(scheduler)`` is the replica boot sequence
    (``boot_scheduler`` runs all of it).  Every step refuses with a
    ``BundleError`` naming the mismatched field rather than falling
    back to compilation.
    """

    root: str
    manifest: dict

    @classmethod
    def load(cls, path: str) -> "WarmStartBundle":
        """Load a bundle directory or ``.tar`` archive (extracted to a
        temp directory that lives as long as the process)."""
        if not os.path.exists(path):
            raise BundleError(f"bundle path {path!r} does not exist")
        root = path
        if os.path.isfile(path):
            root = tempfile.mkdtemp(prefix="fcn3-bundle-")
            with tarfile.open(path) as tf:
                tf.extractall(root, filter="data")
        mpath = os.path.join(root, "manifest.json")
        if not os.path.exists(mpath):
            raise BundleError(f"{path!r} has no manifest.json -- not a "
                              f"warm-start bundle")
        with open(mpath) as f:
            manifest = json.load(f)
        fmt = manifest.get("format")
        if fmt != BUNDLE_FORMAT:
            raise BundleError(
                f"bundle format {fmt!r} is not supported (expected "
                f"{BUNDLE_FORMAT!r}); rebuild the bundle with this "
                f"version of the code")
        return cls(root=root, manifest=manifest)

    # -- identity ------------------------------------------------------
    @property
    def bundle_id(self) -> str:
        """Content address: sha256 of the canonical manifest."""
        return self.manifest.get("bundle_id", "")

    @property
    def blobs_dir(self) -> str:
        """Directory holding the ``chunk_<token>.stablehlo`` blobs."""
        return os.path.join(self.root, "blobs")

    def specs(self) -> list[RequestSpec]:
        """The request shapes this bundle has warm executables for."""
        return [RequestSpec.from_dict(e["spec"])
                for e in self.manifest.get("engines", [])]

    # -- verification --------------------------------------------------
    def verify(self, deep: bool = True) -> None:
        """Refuse (BundleError) unless this process can serve the bundle
        with zero compiles.

        Checks, in order: the content address (manifest integrity), the
        strict environment fields (jax/jaxlib versions, backend
        platform, ``repro`` source fingerprint -- each one invalidates
        the blobs or the XLA cache), and with ``deep=True`` the sha256
        of every packed file (a tampered or truncated blob is refused
        here, not discovered mid-boot).  Every failure is reported, not
        just the first.
        """
        problems: list[str] = []
        want_id = hashlib.sha256(_canonical(self.manifest)).hexdigest()
        if want_id != self.bundle_id:
            problems.append(
                f"manifest does not match its content address: "
                f"bundle_id={self.bundle_id!r} but canonical manifest "
                f"hashes to {want_id!r} (manifest edited after build?)")
        env_here = environment()
        env_bundle = self.manifest.get("environment", {})
        for field in _STRICT_ENV:
            if env_bundle.get(field) != env_here.get(field):
                problems.append(
                    f"environment mismatch on {field!r}: bundle has "
                    f"{env_bundle.get(field)!r}, this process has "
                    f"{env_here.get(field)!r}")
        if deep:
            for rel, meta in sorted(self.manifest.get("files", {}).items()):
                path = os.path.join(self.root, rel)
                if not os.path.exists(path):
                    problems.append(f"missing bundle file {rel!r}")
                    continue
                got = _sha256_file(path)
                if got != meta["sha256"]:
                    problems.append(
                        f"sha256 mismatch for {rel!r}: manifest says "
                        f"{meta['sha256']}, file hashes to {got} "
                        f"(corrupt or tampered)")
        if problems:
            raise BundleError(
                "refusing to boot from bundle "
                f"{self.bundle_id[:12] or '<no id>'}: "
                + "; ".join(problems))

    # -- installation --------------------------------------------------
    def install_plans(self) -> int:
        """Install the packed geometry plans (DISCO psi + banded splits,
        Legendre tables) into the process-wide plan caches; returns how
        many were installed."""
        n = 0
        for rel in self.manifest.get("plans", []):
            _install_plan_payload(_load_plan_npz(
                os.path.join(self.root, rel)))
            n += 1
        return n

    def install_tunings(self) -> int:
        """Install the packed kernel tunings as the process-active
        ``TuningCache`` (``repro.kernels.autotune``), so every engine
        key this replica derives resolves the same ``BlockConfig`` the
        bundle's executables were compiled for -- with zero sweeps.
        Bundles without tunings uninstall any active cache (the packed
        executables were built with default tiles; a leftover local
        cache would derive mismatching keys).  Returns the entry count.
        """
        from repro.kernels import autotune
        packed = self.manifest.get("tunings", [])
        if not packed:
            autotune.install_tuning_cache(None)
            return 0
        autotune.install_tuning_cache(os.path.join(self.root, "tunings"))
        return len(packed)

    def install_xla_cache(self) -> int:
        """Copy the bundle's compiled programs into the process's XLA
        compilation cache (``repro.compile_cache``), so importing the
        StableHLO blobs skips the backend compile too.  Entries already
        present are kept.  Returns the number copied."""
        dest = compile_cache.configure()
        src = os.path.join(self.root, "xla")
        copied = 0
        for name in compile_cache.entries(src):
            target = os.path.join(dest, name)
            if not os.path.exists(target):
                shutil.copyfile(os.path.join(src, name), target)
                copied += 1
        return copied

    def boot(self, scheduler) -> dict:
        """Pre-warm ``scheduler`` with every engine in the manifest.

        Every chunk program must come from the bundle's blobs ("disk")
        or already be installed ("memory"); anything else -- including a
        ``ReadOnlyCacheMiss`` from the readonly cache -- is a refusal.
        Returns the ``bundle`` stats block the scheduler reports
        (bundle id, engines/programs warmed, disk hits, boot seconds).
        """
        import time
        t0 = time.perf_counter()
        programs = 0
        disk_hits = 0
        for entry in self.manifest.get("engines", []):
            spec = RequestSpec.from_dict(entry["spec"])
            for prog in entry["programs"]:
                try:
                    out = scheduler.warmup(spec, batch=prog["batch"])
                except ReadOnlyCacheMiss as e:
                    raise BundleError(
                        f"bundle {self.bundle_id[:12]} cannot serve "
                        f"spec {entry['spec']} "
                        f"(batch={prog['batch']}): {e}") from e
                for o in out["outcomes"]:
                    if o["source"] not in ("disk", "memory"):
                        raise BundleError(
                            f"chunk_len={o['chunk_len']} for spec "
                            f"{entry['spec']} was {o['source']!r}, not "
                            f"served from the bundle -- refusing a "
                            f"silently-compiling boot")
                    programs += 1
                    disk_hits += o["source"] == "disk"
        info = {
            "bundle_id": self.bundle_id,
            "path": self.root,
            "engines": len(self.manifest.get("engines", [])),
            "programs": programs,
            "disk_hits": disk_hits,
            "boot_s": round(time.perf_counter() - t0, 3),
        }
        if hasattr(scheduler, "set_bundle_info"):
            scheduler.set_bundle_info(info)
        return info


def boot_scheduler(bundle: "WarmStartBundle | str", pool=None,
                   **scheduler_kwargs):
    """One-call replica boot: verify, install plans, enable the XLA
    cache, build a scheduler over a readonly executable cache and
    pre-warm every bundled engine.  Returns the ready scheduler.

    ``bundle`` may be a loaded ``WarmStartBundle`` or a path.  The
    scheduler's cache is ``ExecutableCache(blobs_dir, readonly=True)``:
    any request shape the bundle does not cover raises
    ``ReadOnlyCacheMiss`` instead of compiling.
    """
    if isinstance(bundle, str):
        bundle = WarmStartBundle.load(bundle)
    bundle.verify()
    bundle.install_xla_cache()
    bundle.install_plans()
    bundle.install_tunings()
    from repro.serving.cache import ExecutableCache
    from repro.serving.scheduler import ForecastScheduler, ModelPool
    scheduler = ForecastScheduler(
        pool=pool if pool is not None else ModelPool(),
        cache=ExecutableCache(persist_dir=bundle.blobs_dir, readonly=True),
        **scheduler_kwargs)
    try:
        bundle.boot(scheduler)
    except BaseException:
        scheduler.close()
        raise
    return scheduler
