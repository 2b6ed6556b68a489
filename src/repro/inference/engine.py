"""Scan-compiled ensemble forecast engine (paper Section 5 / Appendix G.4).

The paper's operational claim is a 60-day, 0.25-degree, 6-hourly global
ensemble forecast in minutes on a single device.  That requires the whole
autoregressive rollout -- FCN3 step, AR(1) spherical-noise transition
(eq. 27), antithetic noise centering (E.3) and in-situ skill scoring (D) --
to live inside one compiled program instead of a Python loop that
re-dispatches a jitted step per lead time.

``ForecastEngine`` compiles exactly that: a ``jax.lax.scan`` over lead
times whose carry is the ensemble state and the noise coefficients.

Design points:

* **Chunked scan.**  The rollout is split into ``lead_chunk``-step scan
  calls so a 240-step (60-day) forecast neither inflates compile time nor
  materializes 240 lead times of per-step outputs at once.  Chunks reuse
  the same compiled executable (the last, shorter chunk compiles once
  more at most).
* **Donated carries.**  The ensemble state and noise coefficients are
  donated to each chunk call, so XLA updates them in place; a forecast
  holds one ensemble state, not one per lead time.
* **Precision policy.**  ``compute_dtype="bfloat16"`` casts parameters,
  geometry buffers and the stepped state to bf16 while all skill metrics
  accumulate in fp32 (the noise process always stays fp32/complex64).
* **Member sharding.**  ``member_axes`` applies the same mesh-axis
  convention as ``train.trainer.TrainConfig.member_axes``: the leading
  ensemble dim of the state/conditioning is sharding-constrained to those
  axes, so a large ensemble spreads across devices with no code change.
* **In-situ scoring.**  When truth states are supplied, fair CRPS,
  ensemble-mean RMSE, spread, spread-skill ratio and the per-channel rank
  histogram (paper D.2/D.5/F.3) are computed inside the scan, per channel
  and lead time; raw member fields never leave the device.  The scan
  reductions are assembled per config by ``_score_fns`` -- one registry,
  not ad-hoc branches -- and the rank histogram uses a latitude-banded
  integer bincount that stays O(E) in memory per grid point (no E x H x W
  sort is ever materialized).  ``spectra=True`` adds per-degree energy
  spectra (member mean, and truth when given).  An optional
  ``diagnostics`` callable is traced into the scan for custom per-step
  reductions (e.g. per-member wind maxima) -- the paper's "online
  scoring" generalized.
* **Initial-condition perturbations.**  ``EngineConfig.perturb`` selects
  obs-error sampling or cycled bred vectors
  (``repro.inference.perturbations``, paper App. E); ``init_carry``
  generates the perturbed members on device inside a compiled program.
  The default ("none") replicates the analysis state exactly as before.
* **AOT executables.**  ``lower_chunk`` / ``compile_chunk`` expose the
  chunk function's explicit lower-then-compile stages (the serving
  layer's executable cache, ``repro.serving.cache``, drives them), and
  ``export_chunk`` / ``import_chunk`` round-trip the lowered program
  through ``jax.export`` so a fresh process skips Python tracing.
  ``stream`` dispatches to an installed executable whenever one matches
  the chunk length, falling back to the implicit jit path otherwise;
  both paths run the same lowering, so results are bit-identical.
* **Coalesced request batching.**  ``stream_batched`` /
  ``forecast_batched`` roll B same-shape requests -- a leading request
  axis over ``(state0, key, aux, truth)`` -- through **one** batched
  chunk program (``jax.vmap`` of the serial chunk function, so the
  noise streams, scores and carries stay per-request and bit-identical
  to B serial rollouts).  Batched executables join the AOT hooks via
  ``batch=``; the serving scheduler coalesces same-shape requests onto
  this path so N concurrent requests pay one rollout, not N.
* **Overlapped host transfers.**  Aux/truth staging is double-buffered:
  while chunk k computes, chunk k+1's host slices are materialized on a
  background thread, and each (request, step) is staged exactly once
  per rollout (the ``h2d_chunks``/``h2d_steps`` dispatch counters make
  duplicate copies detectable).  Retired-chunk score fetches are the
  caller's half of the overlap -- the serving scheduler moves its
  ``device_get`` off the dispatch thread so streaming never stalls the
  scan.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Callable, Iterator, Protocol

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.core.sphere import noise as noiselib
from repro.evaluation import metrics
from repro.inference import perturbations as perturblib
from repro.kernels.config import KernelConfig

# fold_in salt separating the perturbation stream from the noise-process
# stream (which folds in the 0-based lead index).
_PERTURB_SALT = 0x5EED

#: score names an engine forecast can emit, in emission order.
SCORE_NAMES = ("crps", "ens_rmse", "spread", "ssr", "rank_hist",
               "spectrum", "spectrum_truth")


#: one member's activation (bytes, ``member_activation_bytes``) above
#: which the engine steps ensemble members sequentially (see
#: ``ForecastEngine._members_in_sequence``)
SEQUENTIAL_MEMBER_BYTES = 2**28


class ForecastModel(Protocol):
    """What the engine asks of a model family (``repro.core.fcn3.FCN3``,
    ``repro.core.sfno.SFNO``; ``repro.configs.registry`` builds them).

    * ``apply(params, buffers, state, cond)``: one step of one member,
      (C, H, W) -> (C, H, W); ``cond`` is the member's auxiliary and
      noise fields, or None for a model without a noise process.
    * ``noise``: the carry init and transition of the noise process
      (``repro.core.sphere.noise.SphericalDiffusion``), or None for a
      deterministic model, which then carries no noise and is given no
      conditioning.
    * ``in_sht``: the input grid's transform; the spectra product takes
      its forward table.
    * ``member_activation_bytes(itemsize)``: one member's activation,
      which decides whether members step side by side.
    * ``default_params(ds, buffers, state0)``: the params of a service
      started without a checkpoint (``repro.inference.params``).

    Besides: ``cfg`` (``n_state``, ``kernels``; ``n_aux`` and ``n_noise``
    with a noise process), ``grid_in``, ``init``, ``make_buffers`` and
    ``disco_plans`` (the DISCO plans a warm-start bundle exports).
    """

    cfg: Any
    grid_in: Any
    in_sht: Any
    noise: Any
    disco_plans: tuple

    def apply(self, params, buffers, state: jax.Array,
              cond: jax.Array | None) -> jax.Array: ...

    def member_activation_bytes(self, itemsize: int) -> int: ...

    def default_params(self, ds, buffers, state0: jax.Array) -> dict: ...

    def init(self, key: jax.Array) -> dict: ...

    def make_buffers(self) -> dict: ...


def in_scan_rank_histogram(ens: jax.Array, target: jax.Array,
                           area_weights: jax.Array) -> jax.Array:
    """(C, E+1) area-weighted rank histogram for the scan body.

    Ranks are comparison counts, binned by an integer segment-sum per
    (channel, latitude ring) -- peak memory stays O(E) per grid point and
    no E x H x W sort or (H, W, E+1) float one-hot is materialized, which
    is what makes rank histograms affordable inside the scan at 0.25
    degrees.  Integer counts are exact, and the final float contraction is
    shared with the reference (``metrics.ring_contract``), so the result
    is bit-identical to ``metrics.rank_histogram_per_channel``.
    """
    e = ens.shape[0]
    rank = jnp.sum((ens < target[None]).astype(jnp.int32), axis=0)  # (C,H,W)
    c, h, w = rank.shape
    seg = rank + (e + 1) * jnp.arange(c * h, dtype=jnp.int32).reshape(c, h, 1)
    counts = jax.ops.segment_sum(
        jnp.ones((c * h * w,), jnp.int32), seg.reshape(-1),
        num_segments=c * h * (e + 1))
    return metrics.ring_contract(counts.reshape(c, h, e + 1), area_weights)


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Forecast-engine hyperparameters.

    members:        ensemble size E (antithetic pairs when ``centered``).
    lead_chunk:     scan length per compiled chunk call.
    centered:       antithetic noise centering (paper E.3).
    compute_dtype:  dtype for the model step ("float32" or "bfloat16");
                    metrics always accumulate in fp32.
    member_axes:    mesh axes for the leading ensemble dim (paper G.1),
                    e.g. ("model",); several axes all shard dim 0
                    (engine states carry no batch dim, unlike the
                    trainer's (E, B) convention).  The model step then
                    runs under ``shard_map`` over those axes, each
                    device stepping its own members (Pallas kernels
                    cannot be partitioned automatically).  None applies
                    no member sharding.
    donate:         donate state/noise carries to each chunk call.
    static_buffers: close over the geometry buffers instead of passing
                    them as jit arguments.  Baked buffers constant-fold
                    into the executable (measurably faster single-host
                    serving) but cannot be sharded or swapped without a
                    recompile -- keep False for multi-device runs and for
                    full-resolution Legendre tables (~GB-scale constants).
    perturb:        initial-condition perturbation of the members (paper
                    App. E), generated on device in ``init_carry``; the
                    default "none" replicates the analysis state.  Pass
                    a data-derived ``InitialConditionPerturbation`` to
                    the engine for climatological per-channel scaling --
                    the auto-built fallback sampler uses channel_std=1
                    (amplitude becomes absolute normalized units) and
                    the generic power-law spectrum.
    spectra:        add per-degree energy spectra ("spectrum", member
                    mean; "spectrum_truth" when truth is given) to the
                    in-scan score set -- one extra SHT per member, channel
                    and lead, so opt-in.
    kernels:        kernel substrate for the model's hot contractions
                    (``repro.kernels.config.KernelConfig``).  ``None``
                    inherits the model's own ``cfg.kernels``;
                    an explicit config makes the engine rebuild its
                    model view (and its buffer layout) around that
                    substrate.  Part of the engine identity, so the
                    serving AOT executable-cache key distinguishes
                    programs compiled for different substrates.
    """

    members: int = 4
    lead_chunk: int = 8
    centered: bool = True
    compute_dtype: str = "float32"
    member_axes: tuple | None = None
    donate: bool = True
    static_buffers: bool = False
    perturb: perturblib.PerturbationConfig = perturblib.PerturbationConfig()
    spectra: bool = False
    kernels: KernelConfig | None = None

    @property
    def jdtype(self):
        return jnp.dtype(self.compute_dtype)


@dataclasses.dataclass
class ForecastResult:
    """Scores for a contiguous block of lead times.

    lead_steps: (T,) 0-based global lead indices; lead i verifies at
                t0 + 6h * (i + 1).
    scores:     fp32 accumulators keyed by name (see ``SCORE_NAMES``):
                per-channel (T, C) "crps" / "ens_rmse" / "spread" / "ssr"
                and the (T, C, E+1) "rank_hist" when truth is given;
                (T, C, L) per-degree "spectrum" (member mean) and
                "spectrum_truth" when the engine runs with
                ``spectra=True``.  Empty when neither applies.
    diagnostics: stacked pytree from the engine's ``diagnostics`` fn.
    final_state / final_noise: ensemble carry after the last lead in this
                block; only set on the final block (earlier blocks' carries
                are donated to the next chunk call).
    """

    lead_steps: np.ndarray
    scores: dict[str, jax.Array]
    diagnostics: Any | None = None
    final_state: jax.Array | None = None
    final_noise: jax.Array | None = None


def _concat_results(parts: list[ForecastResult]) -> ForecastResult:
    scores = {k: jnp.concatenate([p.scores[k] for p in parts])
              for k in parts[0].scores}
    diag = None
    if parts[0].diagnostics is not None:
        diag = jax.tree.map(lambda *xs: jnp.concatenate(xs),
                            *[p.diagnostics for p in parts])
    return ForecastResult(
        lead_steps=np.concatenate([p.lead_steps for p in parts]),
        scores=scores, diagnostics=diag,
        final_state=parts[-1].final_state,
        final_noise=parts[-1].final_noise)


def _no_span(name: str, args: dict) -> contextlib.AbstractContextManager:
    """The ``on_span`` hook where none is given: brackets nothing."""
    return contextlib.nullcontext()


def _cast_floats(tree, dtype):
    return jax.tree.map(
        lambda a: a.astype(dtype)
        if jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating) else a, tree)


def _tree_nbytes(tree) -> int:
    """Total leaf bytes of a pytree without copying any leaf."""
    total = 0
    for leaf in jax.tree.leaves(tree):
        n = getattr(leaf, "nbytes", None)
        total += int(n if n is not None else np.asarray(leaf).nbytes)
    return total


class _ChunkStager:
    """Double-buffered host->device staging of per-chunk scan inputs.

    ``get(i)`` hands back the staged xs for the i-th chunk boundary and
    immediately schedules chunk i+1 on a background thread, so the host
    slicing / ``jnp.asarray`` work (an H2D copy on accelerators)
    overlaps chunk i's device compute instead of serializing with it.
    Staged chunks are cached until consumed, so no (source, step) is
    ever materialized twice in one rollout -- bred-vector init ``peek``s
    chunk 0 for its aux fields instead of re-staging step 0, and the
    engine's ``h2d_chunks``/``h2d_steps`` dispatch counters (ticked by
    the stage functions) prove the no-duplicate invariant.
    """

    def __init__(self, bounds: list[tuple],
                 stage_fn: Callable[[int, int], dict]):
        self._bounds = bounds
        self._stage_fn = stage_fn
        self._ready: dict[int, dict] = {}
        self._futures: dict[int, Future] = {}
        self._ex = ThreadPoolExecutor(max_workers=1,
                                      thread_name_prefix="h2d-stager")

    def _materialize(self, i: int) -> dict:
        start, k = self._bounds[i]
        return self._stage_fn(start, k)

    def _take(self, i: int) -> dict:
        xs = self._ready.pop(i, None)
        if xs is not None:
            return xs
        fut = self._futures.pop(i, None)
        return fut.result() if fut is not None else self._materialize(i)

    def peek(self, i: int) -> dict:
        """Stage chunk i now and keep it for the coming ``get(i)``."""
        self._ready.setdefault(i, self._take(i))
        return self._ready[i]

    def get(self, i: int) -> dict:
        """Staged xs for chunk i; prefetches chunk i+1 in the background."""
        xs = self._take(i)
        j = i + 1
        if j < len(self._bounds) and j not in self._ready \
                and j not in self._futures:
            self._futures[j] = self._ex.submit(self._materialize, j)
        return xs

    def close(self) -> None:
        self._ex.shutdown(wait=False)


class ForecastEngine:
    """Compiled autoregressive ensemble forecaster for a
    ``ForecastModel`` (FCN3, or the deterministic SFNO).

    Typical use::

        eng = ForecastEngine(model, EngineConfig(members=8, lead_chunk=20))
        res = eng.forecast(params, buffers, state0, aux, key, truth=truth)
        res.scores["crps"]          # (T, C) fair CRPS per lead/channel

    ``aux``/``truth`` may be stacked arrays or ``fn(step) -> (.,H,W)``
    callables (with ``steps=``), so long rollouts stage host data one
    chunk at a time.
    """

    def __init__(self, model: ForecastModel, cfg: EngineConfig,
                 diagnostics: Callable[[jax.Array], Any] | None = None,
                 perturbation: perturblib.InitialConditionPerturbation
                 | None = None):
        # An explicit EngineConfig.kernels re-homes the model on that
        # substrate (geometry plans and Legendre tables are lru-cached
        # by grid, so this costs a config object, not a rebuild of the
        # static geometry).
        if cfg.kernels is not None and cfg.kernels != model.cfg.kernels:
            model = type(model)(dataclasses.replace(model.cfg,
                                                    kernels=cfg.kernels))
        self.model = model
        self.cfg = cfg
        self.diagnostics = diagnostics
        #: the noise process; None for a deterministic model, whose
        #: rollout carries no noise and stages no conditioning
        self.noise = model.noise
        self.noise_buffers = ({} if self.noise is None
                              else self.noise.buffers())
        if cfg.spectra:
            # spectra run a forward SHT on the IO grid (the noise's)
            self.noise_buffers["wpct"] = model.in_sht.table("wpct")
        self.area_weights = jnp.asarray(model.grid_in.area_weights_2d(),
                                        jnp.float32)
        # IC perturbation sampler: EngineConfig.perturb is the single
        # source of truth for *whether/how* members are perturbed; an
        # explicit sampler only contributes the data-derived
        # spectrum/std, so its config must match exactly -- anything
        # else (including an active sampler next to the default
        # kind="none") is a config bug, refused rather than silently
        # resolved.
        if perturbation is not None and perturbation.cfg != cfg.perturb:
            raise ValueError(
                "EngineConfig.perturb and the explicit perturbation "
                "sampler's config disagree; build both from the same "
                "PerturbationConfig")
        if perturbation is None and cfg.perturb.active:
            perturbation = perturblib.InitialConditionPerturbation(
                model.in_sht, cfg.perturb, model.grid_in.area_weights_2d())
        self.perturbation = perturbation
        self._compiled: dict[Any, Any] = {}
        self._cast_cache: dict[str, tuple] = {}
        # AOT executables installed by compile_chunk/import_chunk, keyed
        # (scored, baked, chunk_len, batch); dispatch_counts records
        # which path served each chunk call ("aot" must stay exclusive
        # on a warm serving engine -- a "jit" tick there is a
        # recompilation) and how much aux/truth host staging ran
        # ("h2d_chunks"/"h2d_steps" -- exactly one tick per staged chunk
        # and per (distinct source, step) per rollout, or staging is
        # duplicating copies).
        self._aot: dict[Any, tuple] = {}
        self.dispatch_counts = {"aot": 0, "jit": 0,
                                "h2d_chunks": 0, "h2d_steps": 0,
                                "shrinks": 0}
        # chunk dispatches are one per lead_chunk, so a lock here is
        # noise next to the device work -- but it keeps the counts exact
        # when a serving scheduler runs concurrent rollouts on one engine
        self._dispatch_lock = threading.Lock()
        # guards the identity-keyed caches (_cast_cache, _compiled):
        # concurrent workers warming one engine must agree on a single
        # cast params/buffers object, or AOT entries pinned to the loser
        # would silently fall back to the recompiling jit path
        self._cache_lock = threading.RLock()

    @property
    def _perturb_cfg(self) -> perturblib.PerturbationConfig:
        return self.cfg.perturb

    # ------------------------------------------------------------------
    @property
    def _members_in_sequence(self) -> bool:
        """Step the members one after another instead of side by side
        (``vmap``) when one member's activation
        (``model.member_activation_bytes``) passes
        ``SEQUENTIAL_MEMBER_BYTES``: at 721x1440 the step's transient
        activations then scale with one member, not E, which is what
        lets an ensemble fit one chip.  The sequence is unrolled, not a
        loop: out of a loop over members XLA hoists the rounded copies
        of loop-invariant weights and tables and holds all of them for
        the whole loop (2 GB at full width, compile rehearsal)."""
        return (self.model.member_activation_bytes(self.cfg.jdtype.itemsize)
                > SEQUENTIAL_MEMBER_BYTES)

    def _step_members(self, params, buffers, s: jax.Array,
                      cond: jax.Array) -> jax.Array:
        """One model step of every member: (E, C, H, W) -> (E, C, H, W);
        ``cond`` is (E, ...) or None (a model without conditioning).

        With ``member_axes`` the step runs under ``shard_map`` over those
        mesh axes -- each device steps its own members with replicated
        params and geometry, because a Pallas kernel cannot be
        partitioned automatically (the TPU compiler refuses it)."""
        m = self.model

        def step(params, buffers, s, cond):
            if self._members_in_sequence:
                return jnp.stack([
                    m.apply(params, buffers, s[i],
                            None if cond is None else cond[i])
                    for i in range(s.shape[0])])
            return jax.vmap(
                lambda se, ce: m.apply(params, buffers, se, ce))(s, cond)

        if self.cfg.member_axes is None:
            return step(params, buffers, s, cond)
        from jax.sharding import PartitionSpec
        ax = PartitionSpec(tuple(self.cfg.member_axes))
        # check_vma=False: Pallas out_shapes carry no varying-axes info
        return jax.shard_map(
            step, in_specs=(PartitionSpec(), PartitionSpec(), ax, ax),
            out_specs=ax, check_vma=False)(params, buffers, s, cond)

    def _constrain(self, x: jax.Array) -> jax.Array:
        if self.cfg.member_axes is None:
            return x
        from jax.sharding import PartitionSpec
        # All member_axes map onto dim 0: engine states are (E, C, H, W)
        # with no batch dim, so a trainer-style ("model", "data") tuple
        # shards the ensemble over both axes rather than spilling the
        # second axis onto the channel dim.
        spec = PartitionSpec(tuple(self.cfg.member_axes),
                             *([None] * (x.ndim - 1)))
        return jax.lax.with_sharding_constraint(x, spec)

    def init_carry(self, state0: jax.Array, key: jax.Array,
                   params=None, buffers=None, aux0: jax.Array | None = None
                   ) -> tuple[jax.Array, jax.Array]:
        """Ensemble-state / noise-coefficient carry from one (C,H,W) state.

        With an active perturbation config the members are perturbed on
        device inside a compiled program (obs-error sampling needs nothing
        extra; bred vectors additionally need ``params``/``buffers`` and
        ``aux0``, the frozen conditioning fields the breeding rollouts run
        under, where the model takes conditioning).  A deterministic
        model's carry holds no noise (None).  The perturbation key
        stream is salted away from the noise process, so kind="none"
        stays bit-identical to the unperturbed engine.
        """
        e = self.cfg.members
        z_hat = (None if self.noise is None else
                 self.noise.init_state(key, (e,), self.noise_buffers))
        if self._perturb_cfg.active:
            if self._perturb_cfg.kind == "bred" and (
                    params is None or buffers is None
                    or (aux0 is None and self.noise is not None)):
                raise ValueError(
                    "bred perturbations need params=, buffers= and aux0=")
            s = self._get_init_fn()(state0, key, params, buffers, aux0)
        else:
            s = jnp.broadcast_to(state0, (e,) + state0.shape)
        return self._constrain(s.astype(self.cfg.jdtype)), z_hat

    def _get_init_fn(self) -> Callable:
        """Compiled perturbed-member sampler, cached per engine.

        The sampler's Legendre tables travel as jit arguments (shardable,
        never GB-scale HLO constants at full resolution); unlike the
        per-step chunk functions there is no ``static_buffers`` baking --
        init runs once per forecast, so constant folding buys nothing.
        """
        with self._cache_lock:
            return self._init_fn_locked()

    def _init_fn_locked(self) -> Callable:
        fn = self._compiled.get("init")
        if fn is not None:
            return fn
        pert, e, m = self.perturbation, self.cfg.members, self.model
        # The noise process runs on in_sht, so when the sampler shares
        # that SHT (every current construction path) its Legendre tables
        # already live in noise_buffers -- reuse them instead of holding
        # a second device copy.
        pbufs = (self.noise_buffers if pert.sht is m.in_sht
                 else pert.buffers)

        if pert.cfg.kind == "obs":
            @jax.jit
            def obs_init(state0, key, pb):
                return pert.members(jax.random.fold_in(key, _PERTURB_SALT),
                                    state0, e, sht_buffers=pb)

            def fn(state0, key, params, buffers, aux0):
                return obs_init(state0, key, pbufs)
        else:
            @jax.jit
            def bred_init(params, buffers, state0, aux0, key, pb):
                # Breeding runs the deterministic control dynamics: frozen
                # aux conditioning, zero noise channels, fp32 carries.
                cond = None if aux0 is None else jnp.concatenate(
                    [aux0, jnp.zeros((m.cfg.n_noise,) + state0.shape[-2:],
                                     aux0.dtype)], axis=0)

                def step_fn(s):
                    return m.apply(params, buffers, s,
                                   cond).astype(jnp.float32)

                return pert.members(jax.random.fold_in(key, _PERTURB_SALT),
                                    state0, e, step_fn, sht_buffers=pb)

            def fn(state0, key, params, buffers, aux0):
                return bred_init(params, buffers, state0, aux0, key, pbufs)

        self._compiled["init"] = fn
        return fn

    def noise_fields(self, z_hat: jax.Array) -> jax.Array:
        """Grid-space conditioning noise exactly as the scan body sees it
        (antithetically centered when the engine is configured so)."""
        z = self.noise.to_grid(z_hat, self.noise_buffers)
        if self.cfg.centered:
            z = noiselib.center_noise(z, axis=0)
        return z

    # ------------------------------------------------------------------
    def _score_fns(self, scored: bool, nbufs, aw
                   ) -> dict[str, Callable]:
        """Assemble the in-scan reduction registry from the config.

        One place decides what the scan accumulates: each entry maps the
        fp32 ensemble state and the per-step inputs to a per-lead
        accumulator.  ``nbufs``/``aw`` arrive as traced values so the
        non-baked chunk path keeps them as jit arguments (shardable), not
        closed-over constants.
        """
        fns: dict[str, Callable] = {}
        if scored:
            fns["crps"] = lambda sf, x: metrics.crps(sf, x["truth"], aw)
            fns["ens_rmse"] = (
                lambda sf, x: metrics.ensemble_skill(sf, x["truth"], aw))
            fns["spread"] = lambda sf, x: metrics.ensemble_spread(sf, aw)
            fns["ssr"] = (
                lambda sf, x: metrics.spread_skill_ratio(sf, x["truth"], aw))
            fns["rank_hist"] = (
                lambda sf, x: in_scan_rank_histogram(sf, x["truth"], aw))
        if self.cfg.spectra:
            wpct = nbufs["wpct"]  # noise shares the IO-resolution SHT
            fns["spectrum"] = lambda sf, x: metrics.ensemble_spectrum(sf,
                                                                      wpct)
            if scored:
                fns["spectrum_truth"] = (
                    lambda sf, x: metrics.angular_psd(x["truth"], wpct))
        return fns

    def _run_chunk(self, scored, params, buffers, nbufs, aw, s, z_hat,
                   key, xs):
        """Scan body shared by both chunk calling conventions; the
        noise field and transition, and the in-scan products, run under
        the named scopes ``telemetry.SCOPE_NOISE`` and
        ``telemetry.SCOPE_PRODUCTS``.  A deterministic model (no noise
        process) carries ``z_hat`` None and steps without conditioning."""
        c, noise = self.cfg, self.noise
        e, dt = c.members, c.jdtype
        diag = self.diagnostics
        score_fns = self._score_fns(scored, nbufs, aw)

        def body(carry, x):
            s, z_hat = carry
            cond = None
            if noise is not None:
                with jax.named_scope(telemetry.SCOPE_NOISE):
                    z = noise.to_grid(z_hat, nbufs)
                    if c.centered:
                        z = noiselib.center_noise(z, axis=0)
                cond = jnp.concatenate(
                    [jnp.broadcast_to(x["aux"], (e,) + x["aux"].shape), z],
                    axis=1)
                cond = self._constrain(cond.astype(dt))
            # The spectral path promotes to fp32 through the FFT; pin the
            # carry back to the compute dtype so the scan carry
            # shape/dtype is invariant (no-op in fp32).
            s = self._constrain(
                self._step_members(params, buffers, s, cond).astype(dt))
            if noise is not None:
                with jax.named_scope(telemetry.SCOPE_NOISE):
                    z_hat = noise.step(jax.random.fold_in(key, x["n"]),
                                       z_hat, nbufs)
            with jax.named_scope(telemetry.SCOPE_PRODUCTS):
                sf = s.astype(jnp.float32)
                out = {name: fn(sf, x) for name, fn in score_fns.items()}
                if diag is not None:
                    out["diag"] = diag(sf)
            return (s, z_hat), out

        return jax.lax.scan(body, (s, z_hat), xs)

    def _run_chunk_batched(self, scored, params, buffers, nbufs, aw, s,
                           z_hat, key, xs):
        """``_run_chunk`` vmapped over a leading request axis.

        ``s``/``z_hat``/``key`` carry one entry per coalesced request;
        ``xs["aux"]``/``xs["truth"]`` a leading (B, k, ...) request axis
        (``xs["n"]`` -- the global lead indices -- is shared, all
        coalesced requests roll the same leads).  Params and buffers
        broadcast.  vmap of the *same* chunk function keeps every
        request's math element-wise identical to its serial rollout, so
        coalescing is a pure throughput move, never a numerics one.
        """
        n = xs["n"]
        per_request = {name: v for name, v in xs.items() if name != "n"}

        def one(s_i, z_i, key_i, xs_i):
            return self._run_chunk(scored, params, buffers, nbufs, aw,
                                   s_i, z_i, key_i, {**xs_i, "n": n})

        return jax.vmap(one)(s, z_hat, key, per_request)

    def _cast_cached(self, slot: str, tree, dt):
        """Float-cast a pytree once per input object (identity-keyed).

        Serving loops pass the same params/buffers objects every call;
        recasting GB-scale trees per forecast would dominate.  A *new*
        tree object (e.g. updated params) recasts and replaces the entry.
        """
        with self._cache_lock:
            entry = self._cast_cache.get(slot)
            if entry is not None and entry[0] is tree:
                return entry[1]
            cast = _cast_floats(tree, dt)
            self._cast_cache[slot] = (tree, cast)
            return cast

    def _count_dispatch(self, path: str) -> None:
        with self._dispatch_lock:
            self.dispatch_counts[path] += 1

    def _count_staged(self, steps: int) -> None:
        with self._dispatch_lock:
            self.dispatch_counts["h2d_chunks"] += 1
            self.dispatch_counts["h2d_steps"] += steps

    def dispatch_stats(self) -> dict:
        """Copy of the chunk-dispatch counters ("aot" vs "jit", plus the
        "h2d_chunks"/"h2d_steps" staging counters); on a warm serving
        engine "jit" staying 0 is the no-recompilation invariant the
        tests and /v1/stats assert, and "h2d_steps" growing by exactly
        (distinct aux sources x steps) per rollout is the
        no-duplicate-H2D one."""
        with self._dispatch_lock:
            return dict(self.dispatch_counts)

    def _lookup_aot(self, scored: bool, baked: bool, k: int,
                    params, prepared_buffers,
                    batch: int | None = None) -> Callable | None:
        """Installed executable for a k-step chunk (serial when ``batch``
        is None, else the ``batch``-request coalesced program), or None.

        Entries are pinned to the params/buffers *objects* they were
        compiled against: an AOT executable hard-codes shapes and
        shardings, so a different object falls back to the (gracefully
        retracing) jit path instead of crashing mid-request.
        """
        ent = self._aot.get((scored, baked, k, batch))
        if ent is None:
            return None
        pin_params, pin_bufs, call = ent
        if pin_params is not params or pin_bufs is not prepared_buffers:
            return None
        return call

    def _get_chunk_entry(self, scored: bool, buffers=None,
                         baked_buffers=None,
                         batch: int | None = None) -> tuple:
        """(pin, fn, jitted) for one (scored, baked, batch) chunk variant.

        ``fn(params, buffers, s, z_hat, key, xs)`` is the dispatching
        callable ``stream`` uses: it prefers an installed AOT executable
        for the chunk length and falls back to ``jitted`` (the raw
        ``jax.jit`` object the lower/compile/export hooks operate on).
        ``batch=None`` is the serial per-request program; an integer B
        selects the coalesced program whose carries/keys/xs carry a
        leading B-request axis (``_run_chunk_batched``).

        With ``static_buffers``, ``baked_buffers`` (the possibly
        precision-cast copy) is closed over -- constant-folded into the
        executable -- and the cache entry pins ``buffers`` (the caller's
        original object) so a recompile triggers exactly when a different
        buffers object is supplied.  Otherwise buffers travel as jit
        arguments (shardable / swappable).  XLA caches per chunk length
        underneath either way.
        """
        baked = baked_buffers is not None
        cache_key = (scored, baked, batch)
        with self._cache_lock:
            return self._chunk_entry_locked(scored, baked, cache_key,
                                            buffers, baked_buffers, batch)

    def _chunk_entry_locked(self, scored, baked, cache_key, buffers,
                            baked_buffers, batch=None) -> tuple:
        entry = self._compiled.get(cache_key)
        if entry is not None and (not baked or entry[0] is buffers):
            return entry
        donate = self.cfg.donate
        nbufs, aw = self.noise_buffers, self.area_weights
        run = self._run_chunk if batch is None else self._run_chunk_batched

        if baked:
            def chunk(params, s, z_hat, key, xs):
                return run(scored, params, baked_buffers,
                           nbufs, aw, s, z_hat, key, xs)

            jitted = jax.jit(chunk, donate_argnums=(1, 2) if donate else ())

            def fn(params, _buffers, s, z_hat, key, xs):
                k = int(xs["n"].shape[0])
                aot = self._lookup_aot(scored, True, k, params,
                                       baked_buffers, batch)
                if aot is not None:
                    self._count_dispatch("aot")
                    return aot(params, s, z_hat, key, xs)
                self._count_dispatch("jit")
                return jitted(params, s, z_hat, key, xs)
        else:
            def chunk(params, bufs, nb, w, s, z_hat, key, xs):
                return run(scored, params, bufs, nb, w,
                           s, z_hat, key, xs)

            jitted = jax.jit(chunk, donate_argnums=(4, 5) if donate else ())

            def fn(params, bufs, s, z_hat, key, xs):
                k = int(xs["n"].shape[0])
                aot = self._lookup_aot(scored, False, k, params, bufs,
                                       batch)
                if aot is not None:
                    self._count_dispatch("aot")
                    return aot(params, bufs, nbufs, aw, s, z_hat, key, xs)
                self._count_dispatch("jit")
                return jitted(params, bufs, nbufs, aw, s, z_hat, key, xs)

        entry = (buffers if baked else None, fn, jitted)
        self._compiled[cache_key] = entry
        return entry

    def _get_chunk_fn(self, scored: bool, buffers=None,
                      baked_buffers=None,
                      batch: int | None = None) -> Callable:
        """The compiled scan over one chunk of lead times, as a callable
        ``fn(params, buffers, s, z_hat, key, xs)``."""
        return self._get_chunk_entry(scored, buffers, baked_buffers,
                                     batch)[1]

    # ------------------------------------------------------------------
    # AOT hooks: explicit lower/compile (and jax.export persistence) of
    # the chunk function, instead of relying on implicit jit.  Driven by
    # the serving layer's executable cache (repro.serving.cache).
    def _adapt_buffers(self, buffers):
        """Convert caller buffers to the model's kernel-dispatch layout.

        Callers (serving scheduler, CLIs) hold one buffers object per
        named config, built under that config's default substrate; an
        engine re-homed on a different ``EngineConfig.kernels`` needs
        the matching layout (banded psi for pallas DISCO, full psi for
        the reference FFT path).  Geometry is deterministic from the
        config, so rebuilding via ``make_buffers`` is exact; the result
        is identity-cached per incoming object, like the precision
        casts.
        """
        if not self.model.disco_plans:
            return buffers      # no DISCO geometry to lay out
        disco_bufs = buffers.get("enc") or buffers.get("latent") or {}
        want = self.model.cfg.kernels.resolve("disco")[0] == "pallas"
        if ("psi_band" in disco_bufs) == want:
            return buffers
        with self._cache_lock:
            entry = self._cast_cache.get("layout")
            if entry is not None and entry[0] is buffers:
                return entry[1]
            rebuilt = self.model.make_buffers()
            self._cast_cache["layout"] = (buffers, rebuilt)
            return rebuilt

    def _prepare_inputs(self, params, buffers) -> tuple:
        """Apply the kernel-layout and precision policies to
        params/buffers (identity-cached, so warm serving loops hand back
        the same prepared objects)."""
        buffers = self._adapt_buffers(buffers)
        dt = self.cfg.jdtype
        if dt != jnp.float32:
            params = self._cast_cached("params", params, dt)
            buffers = self._cast_cached("buffers", buffers, dt)
        return params, buffers

    def chunk_lengths(self, steps: int) -> list[int]:
        """Distinct scan lengths a ``steps``-long rollout dispatches: the
        full ``lead_chunk`` plus the shorter final chunk when uneven.
        Warming executables for exactly these keys makes the rollout pay
        zero compile time inside ``stream``."""
        lens: list[int] = []
        start = 0
        while start < steps:
            k = min(self.cfg.lead_chunk, steps - start)
            if k not in lens:
                lens.append(k)
            start += k
        return lens

    def _chunk_avals(self, scored: bool, k: int, params, buffers,
                     batch: int | None = None) -> tuple:
        """Abstract arguments of the k-step chunk jit, in its calling
        convention: ``(params, s, z_hat, key, xs)`` when buffers are
        baked, else ``(params, buffers, nbufs, aw, s, z_hat, key, xs)``.
        With ``batch`` the carries/key and per-request xs entries grow a
        leading B-request axis (``xs["n"]`` stays shared).
        ``params``/``buffers`` must already be precision-prepared."""
        def avals(tree):
            return jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(jnp.shape(a),
                                               jnp.asarray(a).dtype), tree)

        m, cfg = self.model, self.cfg
        h, w = m.grid_in.nlat, m.grid_in.nlon
        lead = () if batch is None else (batch,)
        s_av = jax.ShapeDtypeStruct(
            lead + (cfg.members, m.cfg.n_state, h, w), cfg.jdtype)
        k0 = jax.random.PRNGKey(0)
        key_av = jax.ShapeDtypeStruct(lead + k0.shape, k0.dtype)
        xs_av = {"n": jax.ShapeDtypeStruct((k,), jnp.int32)}
        z_av = None
        if self.noise is not None:
            z_av = jax.ShapeDtypeStruct(
                lead + (cfg.members, self.noise.n_proc, m.in_sht.lmax,
                        m.in_sht.mmax), jnp.complex64)
            xs_av["aux"] = jax.ShapeDtypeStruct(
                lead + (k, m.cfg.n_aux, h, w), jnp.float32)
        if scored:
            xs_av["truth"] = jax.ShapeDtypeStruct(
                lead + (k, m.cfg.n_state, h, w), jnp.float32)
        if cfg.static_buffers:
            return (avals(params), s_av, z_av, key_av, xs_av)
        return (avals(params), avals(buffers), avals(self.noise_buffers),
                avals(self.area_weights), s_av, z_av, key_av, xs_av)

    def _chunk_jitted_and_prepared(self, scored: bool, params, buffers,
                                   batch: int | None = None) -> tuple:
        pc, bc = self._prepare_inputs(params, buffers)
        entry = self._get_chunk_entry(
            scored, buffers, bc if self.cfg.static_buffers else None,
            batch)
        return entry[2], pc, bc

    def lower_chunk(self, scored: bool, k: int, params, buffers,
                    batch: int | None = None) -> jax.stages.Lowered:
        """Explicitly lower the k-step chunk function (``jax.jit(...)
        .lower``) against this engine's shapes (``batch`` selects the
        coalesced B-request program).  ``.compile()`` on the result is
        what ``compile_chunk`` installs."""
        jitted, pc, bc = self._chunk_jitted_and_prepared(scored, params,
                                                         buffers, batch)
        return jitted.lower(*self._chunk_avals(scored, k, pc, bc, batch))

    def compile_chunk(self, scored: bool, k: int, params, buffers,
                      batch: int | None = None):
        """AOT-compile the k-step chunk and install it so ``stream``
        (or ``stream_batched`` when ``batch`` is set) dispatches to it
        (bit-identical to the implicit jit path -- same lowering, same
        compiler).  Returns the ``jax.stages.Compiled``."""
        compiled = self.lower_chunk(scored, k, params, buffers,
                                    batch).compile()
        pc, bc = self._prepare_inputs(params, buffers)
        self._aot[(scored, self.cfg.static_buffers, k, batch)] = (
            pc, bc, compiled)
        return compiled

    def has_chunk_executable(self, scored: bool, k: int, params, buffers,
                             batch: int | None = None) -> bool:
        """True when a warm executable is installed for this chunk length
        and would actually be dispatched for these params/buffers."""
        pc, bc = self._prepare_inputs(params, buffers)
        return self._lookup_aot(scored, self.cfg.static_buffers, k, pc,
                                bc, batch) is not None

    def export_chunk(self, scored: bool, k: int, params, buffers,
                     batch: int | None = None) -> bytes:
        """Serialize the lowered k-step chunk program via ``jax.export``
        (StableHLO).  A fresh process imports the blob with
        ``import_chunk`` and skips Python tracing/lowering entirely; the
        XLA backend compile of the restored module still runs once (pair
        with a persistent XLA compilation cache to also skip that)."""
        from jax import export as jexport
        jitted, pc, bc = self._chunk_jitted_and_prepared(scored, params,
                                                         buffers, batch)
        exp = jexport.export(jitted)(*self._chunk_avals(scored, k, pc, bc,
                                                        batch))
        return bytes(exp.serialize())

    def import_chunk(self, scored: bool, k: int, blob: bytes, params,
                     buffers, batch: int | None = None) -> None:
        """Deserialize an ``export_chunk`` blob, compile it eagerly and
        install it like ``compile_chunk``.  Carry donation is not
        re-declared on imported programs (jax.export drops it); the jit
        path's donation only saves a state-sized copy per chunk."""
        from jax import export as jexport
        exp = jexport.deserialize(bytearray(blob))
        pc, bc = self._prepare_inputs(params, buffers)
        avals = self._chunk_avals(scored, k, pc, bc, batch)
        compiled = jax.jit(exp.call).lower(*avals).compile()
        self._aot[(scored, self.cfg.static_buffers, k, batch)] = (
            pc, bc, compiled)

    def estimated_bytes(self) -> int:
        """Estimated device-memory footprint of this engine's warm state.

        Per installed executable, prefers XLA's compiled-memory analysis
        (temp + output + generated code); on backends whose analysis
        reports zeros for those (CPU), falls back to an analytic
        estimate from the chunk calling convention -- double-buffered
        carries, staged per-step inputs, and (with ``static_buffers``)
        the geometry constants folded into each executable.  Engine-held
        buffers (noise tables, area weights, precision/layout cast
        copies) are counted once; bundle params/buffers are shared
        across engines and are not.  The serving scheduler's engine-pool
        budget evicts least-recently-used engines on this number.
        """
        total = _tree_nbytes(self.noise_buffers) + int(
            self.area_weights.nbytes)
        with self._cache_lock:
            casts = [entry[1] for entry in self._cast_cache.values()]
            aot = dict(self._aot)
        for cast in casts:
            total += _tree_nbytes(cast)
        m, cfg = self.model, self.cfg
        h, w = m.grid_in.nlat, m.grid_in.nlon
        for (scored, baked, k, batch), (_pp, bb, call) in aot.items():
            try:
                ma = call.memory_analysis()
                est = int((getattr(ma, "temp_size_in_bytes", 0) or 0)
                          + (getattr(ma, "output_size_in_bytes", 0) or 0)
                          + (getattr(ma, "generated_code_size_in_bytes", 0)
                             or 0))
            except Exception:  # noqa: BLE001 -- analysis is best-effort
                est = 0
            if est <= 0:
                b = batch or 1
                state = (b * cfg.members * m.cfg.n_state * h * w
                         * cfg.jdtype.itemsize)
                noise = n_aux = 0
                if self.noise is not None:
                    noise = (b * cfg.members * self.noise.n_proc
                             * m.in_sht.lmax * m.in_sht.mmax * 8)
                    n_aux = m.cfg.n_aux
                xs = (b * k * (n_aux + (m.cfg.n_state if scored else 0))
                      * h * w * 4)
                est = 2 * (state + noise) + xs
                if baked:
                    est += _tree_nbytes(bb)
            total += est
        return int(total)

    def plan_exports(self) -> list[dict]:
        """Serializable geometry-plan payloads for warm-start bundles.

        One payload per distinct precomputed plan this engine's model
        dispatches: its DISCO plans (FCN3's encoder, latent and decoder
        plans -- deduplicated by ``DiscoPlan.plan_key``, the 9-tuple
        grid + filter-hyperparameter identity) and the Legendre tables
        of the IO and latent SHTs (keyed (lmax, mmax, colat)).  A fresh
        replica installs these via
        ``repro.core.sphere.disco.install_plan`` /
        ``legendre.install_legendre_table`` and skips the psi-tensor and
        Legendre-recurrence construction entirely (seconds at smoke
        scale, minutes at 721x1440).  Payloads are plain scalars + numpy
        arrays, written to npz files by ``repro.serving.bundle``.
        """
        from repro.core.sphere import disco as discolib
        from repro.core.sphere import legendre as leg
        m = self.model
        payloads: list[dict] = []
        seen: set = set()
        for plan in m.disco_plans:
            key = ("disco",) + plan.plan_key()
            if key in seen:
                continue
            seen.add(key)
            payloads.append({"kind": "disco", **discolib.export_plan(plan)})
        for sht in (m.in_sht, m.latent_sht):
            colat = np.ascontiguousarray(sht.grid.colat, np.float64)
            key = ("legendre", sht.lmax, sht.mmax, colat.tobytes())
            if key in seen:
                continue
            seen.add(key)
            payloads.append({
                "kind": "legendre", "lmax": sht.lmax, "mmax": sht.mmax,
                "colat": colat,
                "table": leg.cached_legendre_table(sht.lmax, sht.mmax,
                                                   colat)})
        return payloads

    # ------------------------------------------------------------------
    @staticmethod
    def _stage(src, start: int, k: int) -> jax.Array:
        """Host-stage one chunk of aux/truth from an array or a callable."""
        if callable(src):
            return jnp.stack(
                [jnp.asarray(src(n)) for n in range(start, start + k)])
        return jnp.asarray(src[start:start + k])

    def _chunk_bounds(self, steps: int) -> list[tuple]:
        """(start, k) boundaries of a ``steps``-long rollout, after
        validating the rollout/chunk lengths."""
        if steps < 1:
            raise ValueError(f"need at least one lead step, got {steps}")
        if self.cfg.lead_chunk < 1:
            raise ValueError(
                f"lead_chunk must be >= 1, got {self.cfg.lead_chunk}")
        bounds, start = [], 0
        while start < steps:
            k = min(self.cfg.lead_chunk, steps - start)
            bounds.append((start, k))
            start += k
        return bounds

    def stream(self, params, buffers, state0: jax.Array, aux, key: jax.Array,
               steps: int | None = None, truth=None, on_span=None
               ) -> Iterator[ForecastResult]:
        """Roll the forecast, yielding one ForecastResult per chunk.

        aux:   (T, n_aux, H, W) array or ``fn(step) -> (n_aux, H, W)``.
        truth: optional (T, C, H, W) array or ``fn(step) -> (C, H, W)``
               giving the verifying state for lead ``step``; enables
               in-scan scoring.
        steps: total lead steps; required when ``aux`` is a callable.
        on_span: optional ``fn(name, args)`` observability hook that
               returns a context manager; each chunk's host->device
               staging runs inside it, on the stager thread.  None (the
               default) brackets nothing -- the hook only reads clocks,
               never touches the staged values.

        Host staging is double-buffered through ``_ChunkStager``: chunk
        k+1's aux/truth materialize on a background thread while chunk k
        computes, and no step is staged twice per rollout.
        """
        if steps is None:
            if callable(aux):
                raise ValueError("steps= is required when aux is a callable")
            steps = len(aux)
        bounds = self._chunk_bounds(steps)
        orig_buffers = buffers
        params, buffers = self._prepare_inputs(params, buffers)
        scored = truth is not None
        fn = self._get_chunk_fn(
            scored, orig_buffers,
            buffers if self.cfg.static_buffers else None)

        span = on_span or _no_span

        def stage(start: int, k: int) -> dict:
            with span("stage_h2d", {"start": start, "steps": k}):
                xs = {"n": jnp.arange(start, start + k, dtype=jnp.int32)}
                if self.noise is not None:
                    xs["aux"] = self._stage(aux, start, k)
                if scored:
                    xs["truth"] = self._stage(truth, start, k)
                self._count_staged(k)
            return xs

        stager = _ChunkStager(bounds, stage)
        try:
            # Bred vectors cycle the model at init time: freeze the
            # first lead's conditioning fields for the breeding rollouts
            # -- taken from the already-staged first chunk, never a
            # second H2D copy of step 0.
            aux0 = (jnp.asarray(stager.peek(0)["aux"][0], jnp.float32)
                    if self._perturb_cfg.kind == "bred"
                    and self.noise is not None else None)
            s, z_hat = self.init_carry(jnp.asarray(state0), key,
                                       params=params, buffers=buffers,
                                       aux0=aux0)
            for i, (start, k) in enumerate(bounds):
                xs = stager.get(i)
                (s, z_hat), out = fn(params, buffers, s, z_hat, key, xs)
                last = i + 1 == len(bounds)
                yield ForecastResult(
                    lead_steps=np.arange(start, start + k),
                    scores={n: out[n] for n in SCORE_NAMES if n in out},
                    diagnostics=out.get("diag"),
                    final_state=s if last else None,
                    final_noise=z_hat if last else None)
        finally:
            stager.close()

    def forecast(self, params, buffers, state0: jax.Array, aux,
                 key: jax.Array, steps: int | None = None, truth=None
                 ) -> ForecastResult:
        """Run the whole rollout and concatenate per-chunk results."""
        parts = list(self.stream(params, buffers, state0, aux, key,
                                 steps=steps, truth=truth))
        return _concat_results(parts)

    # ------------------------------------------------------------------
    # Coalesced request batching: B same-shape requests, one rollout.
    def stream_batched(self, params, buffers, state0s, auxs, keys,
                       steps: int | None = None, truths=None,
                       survivors: Callable[[], list[int]] | None = None,
                       on_span=None
                       ) -> Iterator[list[ForecastResult]]:
        """Roll B same-shape requests through one batched chunk program.

        state0s / auxs / keys (and truths when scoring): one entry per
        request, each in the exact form ``stream`` accepts.  Yields one
        ``list[ForecastResult]`` (request-ordered) per chunk.  Because
        the batched program is ``jax.vmap`` of the serial chunk function
        and member init runs per request, every request's scores and
        final state are **bit-identical** to its own serial ``stream``
        rollout -- coalescing buys throughput (one compiled dispatch, one
        set of params reads for B requests), never changed numerics.

        All requests share the engine's shape (members, chunk, scores)
        and the rollout length; per-request initial conditions, noise
        keys, aux/truth sources may differ freely.

        ``survivors`` (optional) is polled at every chunk boundary with
        no arguments and returns the original request indices that still
        want results (the scheduler passes the non-cancelled members of
        a coalesced batch).  When it reports a strict non-empty subset
        AND warm executables are already installed for every remaining
        chunk length at the smaller batch size (serial when one request
        survives), the rollout **shrinks**: surviving carries are sliced
        out and remaining chunks dispatch through the already-compiled
        smaller program -- no new compile, per-request numerics unchanged
        (the batched program is a vmap of the serial one).  Without a
        warm smaller program the rollout continues masked at full width,
        exactly as before.  After a shrink the yielded lists keep length
        B with ``None`` in dropped slots; ``dispatch_counts["shrinks"]``
        ticks once per shrink.

        ``on_span`` is the same clock-only observability hook as
        ``stream``'s: ``fn(name, args)``, a context manager around each
        chunk's staging, never touching staged values.
        """
        b = len(state0s)
        if b < 1:
            raise ValueError("need at least one request to batch")
        if len(auxs) != b or len(keys) != b or (
                truths is not None and len(truths) != b):
            raise ValueError(
                f"state0s/auxs/keys{'/truths' if truths is not None else ''} "
                f"must all have one entry per request (got {b} states, "
                f"{len(auxs)} aux, {len(keys)} keys)")
        if steps is None:
            if any(callable(a) for a in auxs):
                raise ValueError("steps= is required when aux is a callable")
            steps = len(auxs[0])
        bounds = self._chunk_bounds(steps)
        orig_params, orig_buffers = params, buffers
        params, buffers = self._prepare_inputs(params, buffers)
        scored = truths is not None
        fn = self._get_chunk_fn(
            scored, orig_buffers,
            buffers if self.cfg.static_buffers else None, batch=b)

        span = on_span or _no_span

        def stage(start: int, k: int) -> dict:
            # Coalesced requests often share sources (the scheduler
            # hands every member the same aux callable): stage each
            # *distinct* source once and let jnp.stack broadcast it
            # device-side, instead of recomputing and re-copying B
            # identical host chunks.
            staged: dict[int, jax.Array] = {}

            def once(src):
                out = staged.get(id(src))
                if out is None:
                    out = self._stage(src, start, k)
                    staged[id(src)] = out
                return out

            with span("stage_h2d", {"start": start, "steps": k,
                                    "batch": b}):
                xs = {"n": jnp.arange(start, start + k, dtype=jnp.int32)}
                if self.noise is not None:
                    xs["aux"] = jnp.stack([once(a) for a in auxs])
                if scored:
                    xs["truth"] = jnp.stack([once(t) for t in truths])
                self._count_staged(k * len({id(a) for a in auxs}))
            return xs

        stager = _ChunkStager(bounds, stage)
        try:
            aux0s = [None] * b
            if self._perturb_cfg.kind == "bred" and self.noise is not None:
                xs0 = stager.peek(0)
                aux0s = [jnp.asarray(xs0["aux"][i, 0], jnp.float32)
                         for i in range(b)]
            # Member init runs per request through the same compiled
            # sampler as the serial path (once per forecast -- cheap next
            # to the rollout), which keeps perturbed members bitwise
            # equal to serial by construction.
            carries = [self.init_carry(jnp.asarray(s0), k_i, params=params,
                                       buffers=buffers, aux0=a0)
                       for s0, k_i, a0 in zip(state0s, keys, aux0s)]
            s = jnp.stack([c[0] for c in carries])
            # None for a deterministic model (no noise carry)
            z_hat = jax.tree.map(lambda *zs: jnp.stack(zs),
                                 *[c[1] for c in carries])
            key_b = jnp.stack([jnp.asarray(k_i) for k_i in keys])
            diag = self.diagnostics
            # original request indices the rollout still carries, in
            # submit order; ``serial`` flips once a shrink lands on the
            # un-vmapped serial program (one survivor, no leading axis)
            active = list(range(b))
            serial = False
            for i, (start, k) in enumerate(bounds):
                if survivors is not None and not serial:
                    want = set(survivors())
                    alive = [j for j in active if j in want]
                    if alive and len(alive) < len(active):
                        nb = len(alive) if len(alive) > 1 else None
                        rem = {kk for (_s2, kk) in bounds[i:]}
                        if all(self.has_chunk_executable(
                                scored, kk, orig_params, orig_buffers,
                                batch=nb) for kk in rem):
                            pos = [active.index(j) for j in alive]
                            if nb is None:
                                s, z_hat, key_b = jax.tree.map(
                                    lambda a: a[pos[0]], (s, z_hat, key_b))
                                serial = True
                            else:
                                idx = jnp.asarray(pos)
                                s, z_hat, key_b = jax.tree.map(
                                    lambda a: a[idx], (s, z_hat, key_b))
                            fn = self._get_chunk_fn(
                                scored, orig_buffers,
                                (buffers if self.cfg.static_buffers
                                 else None), batch=nb)
                            active = alive
                            self._count_dispatch("shrinks")
                xs = stager.get(i)
                if len(active) < b:
                    # staging always materializes the full-B chunk (the
                    # stager may have pre-staged it before the shrink);
                    # slice the survivors out device-side
                    if serial:
                        sel = (lambda a: a[active[0]])
                    else:
                        idx = jnp.asarray(active)
                        sel = (lambda a: a[idx])
                    xs = {kk: (v if kk == "n" else sel(v))
                          for kk, v in xs.items()}
                (s, z_hat), out = fn(params, buffers, s, z_hat, key_b, xs)
                last = i + 1 == len(bounds)
                block: list = [None] * b
                for p, j in enumerate(active):
                    pick = ((lambda a: a) if serial
                            else (lambda a, p=p: a[p]))
                    block[j] = ForecastResult(
                        lead_steps=np.arange(start, start + k),
                        scores={n: pick(out[n])
                                for n in SCORE_NAMES if n in out},
                        diagnostics=(jax.tree.map(pick, out["diag"])
                                     if diag is not None else None),
                        final_state=pick(s) if last else None,
                        final_noise=(jax.tree.map(pick, z_hat) if last
                                     else None))
                yield block
        finally:
            stager.close()

    def forecast_batched(self, params, buffers, state0s, auxs, keys,
                         steps: int | None = None, truths=None
                         ) -> list[ForecastResult]:
        """Run the whole coalesced rollout; one concatenated
        ``ForecastResult`` per request, in request order."""
        per_request: list[list[ForecastResult]] = None
        for block in self.stream_batched(params, buffers, state0s, auxs,
                                         keys, steps=steps, truths=truths):
            if per_request is None:
                per_request = [[] for _ in block]
            for parts, res in zip(per_request, block):
                parts.append(res)
        return [_concat_results(parts) for parts in per_request]
