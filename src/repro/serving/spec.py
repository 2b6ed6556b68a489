"""Request schema + validation -- the wire contract of the service.

Kept dependency-light on purpose: the thin client imports this module
(plus ``transport``) to build and validate requests, so constructing a
``RequestSpec`` must not drag jax or the model stack into the process.
The heavier imports (configs, perturbation rules, engine config) happen
lazily inside the methods that need them.
"""

from __future__ import annotations

import dataclasses

PRECISIONS = ("float32", "bfloat16")
KERNEL_MODES = ("auto", "reference", "pallas")
PRIORITIES = ("interactive", "batch")
#: float32 bytes of a config's Legendre tables up to which its engines
#: bake the geometry into the executable: the 1-degree FCN3 (5.8 MB)
#: bakes, FCN3 at 721x1440 (373 MB) and SFNO's four tables (443 MB) do
#: not
STATIC_TABLE_BYTES = 2**26


@dataclasses.dataclass(frozen=True)
class RequestSpec:
    """One forecast request -- also the JSON schema of POST /v1/forecast.

    The **shape key** (``engine_key``) is every field that selects a
    different compiled program: config, members, lead_chunk, precision,
    the perturbation settings, spectra and the kernel substrate.
    ``sample``/``seed`` pick the initial condition and noise stream
    within a warm engine; ``scored``/``return_state`` select what the
    stream carries.

    ``kernels`` selects the substrate for the model's hot contractions:
    "auto" (backend default: Pallas on TPU/GPU, reference on CPU),
    "reference" or "pallas".  It flows through ``EngineConfig.kernels``
    into the AOT executable-cache key, so warm requests dispatch the
    executables compiled for their substrate.

    ``coalesce`` (default True) lets the scheduler batch this request
    with queued same-shape requests into one shared rollout dispatch
    (``batch_key``: the compiled program plus rollout length and score
    set).  Coalescing never changes results -- the batched program is a
    vmap of the serial one, bit-identical per request -- but a member
    does wait up to the server's ``batch_window_ms`` for companions;
    ``coalesce: false`` opts a latency-critical request out.

    **QoS fields** -- ``priority`` ("interactive" beats "batch" at
    pickup, subject to the scheduler's aging knob), ``deadline_ms``
    (wall-clock budget from submit; an expired request is shed with a
    terminal ``error`` carrying ``reason: "deadline"`` instead of
    burning a rollout) and ``degrade`` (opt-in: near the deadline the
    scheduler may serve ``degraded_members()`` members instead of
    missing it, reported honestly in start/done events).  None of the
    three enters ``engine_key``/``batch_key`` -- QoS must route traffic,
    never fragment the compiled-program cache.

    ``profile`` (default False) opts this request's rollout into a
    ``jax.profiler`` trace when the server was launched with
    ``--profile-dir`` (inert otherwise); the XLA trace path is linked
    into the request's span tree and ``done`` event.  Like the QoS
    fields it never enters ``engine_key``/``batch_key`` -- a profiled
    request dispatches the same warm executables and stays bit-identical.

    ``max_retries`` (default 0) is the fault-tolerance budget: how many
    times the scheduler may re-dispatch this request after a
    *transient* failure (see ``faults.classify_error``) with bounded
    exponential backoff before giving up.  Retries are reported in the
    ``done`` event (``retries`` field, only when > 0) and metered.
    Like the QoS fields it rides the wire but never enters
    ``engine_key``/``batch_key`` -- a retried request re-dispatches the
    same warm executables, and determinism makes the replayed chunks
    bit-identical.
    """

    config: str = "smoke"
    members: int = 2
    lead_steps: int = 4
    lead_chunk: int = 2
    precision: str = "float32"
    kernels: str = "auto"
    perturb: str = "none"
    perturb_amplitude: float = 0.05
    bred_cycles: int = 3
    ensemble_transform: bool = False
    spectra: bool = False
    scored: bool = True
    sample: int = 0
    seed: int = 7
    return_state: bool = False
    coalesce: bool = True
    priority: str = "batch"
    deadline_ms: float | None = None
    degrade: bool = False
    profile: bool = False
    max_retries: int = 0

    @classmethod
    def from_dict(cls, d: dict) -> "RequestSpec":
        """Build a spec from a JSON object, rejecting unknown fields by
        name (a typo must 400, not silently take a default)."""
        names = {f.name for f in dataclasses.fields(cls)}
        unknown = sorted(set(d) - names)
        if unknown:
            raise ValueError(
                f"unknown request field(s) {unknown}; "
                f"expected a subset of {sorted(names)}")
        return cls(**d)

    def to_dict(self) -> dict:
        """The spec as a JSON-ready dict (the POST body, exactly)."""
        return dataclasses.asdict(self)

    def perturbation_config(self):
        """The ``PerturbationConfig`` this spec's perturb fields select."""
        from repro.inference import PerturbationConfig
        return PerturbationConfig(kind=self.perturb,
                                  amplitude=self.perturb_amplitude,
                                  bred_cycles=self.bred_cycles,
                                  ensemble_transform=self.ensemble_transform)

    def engine_config(self):
        """The ``EngineConfig`` a warm engine for this spec runs with.

        Single-host service: the geometry is baked into the executable
        (``static_buffers``) unless the config's Legendre tables pass
        ``STATIC_TABLE_BYTES``; larger tables stay jit arguments, where
        a compile does not fold them into constants."""
        from repro.configs import registry
        from repro.inference import EngineConfig
        from repro.kernels import autotune
        from repro.kernels.config import KernelConfig
        kernels = (None if self.kernels == "auto"
                   else KernelConfig(sht=self.kernels, disco=self.kernels))
        # Installed tunings (repro.kernels.autotune.install_tuning_cache)
        # resolve here -- upstream of engine_key/batch_key and the AOT
        # executable token, so a tuned engine can never collide with the
        # default-tile one.  With no cache installed this is a no-op and
        # keys stay bit-identical to the untuned build.
        kernels = autotune.resolve_kernel_config(kernels)
        return EngineConfig(members=self.members,
                            lead_chunk=self.lead_chunk,
                            compute_dtype=self.precision,
                            static_buffers=(
                                registry.config(self.config)
                                .legendre_table_bytes()
                                <= STATIC_TABLE_BYTES),
                            perturb=self.perturbation_config(),
                            spectra=self.spectra,
                            kernels=kernels)

    def engine_key(self) -> tuple:
        """The warm-engine (shape) key: every field that selects a
        different compiled program."""
        return (self.config, self.engine_config())

    def batch_key(self) -> tuple:
        """Requests that may share one coalesced rollout dispatch: same
        warm engine (compiled program), same rollout length, same score
        set.  ``sample``/``seed``/``return_state`` stay free -- they are
        per-member inputs of the shared batched program."""
        return (self.engine_key(), self.lead_steps, self.scored)

    def degraded_members(self) -> int:
        """The validated floor of the member count -- what an opted-in
        near-deadline request is served with instead of missing.  The
        smallest count >= 2 that still passes the perturbation rules
        (centered noise needs an even count, ensemble transform needs
        enough independent draws); >= 2 keeps the forecast a real
        ensemble, so scores stay probabilistic.  Falls back to the
        requested count when nothing smaller validates."""
        from repro.inference import perturbations as perturblib
        pcfg = self.perturbation_config()
        for m in range(2, self.members):
            if not perturblib.validate_member_count(m, centered=True,
                                                    cfg=pcfg):
                return m
        return self.members

    _INT_FIELDS = ("members", "lead_steps", "lead_chunk", "bred_cycles",
                   "sample", "seed", "max_retries")
    _BOOL_FIELDS = ("ensemble_transform", "spectra", "scored",
                    "return_state", "coalesce", "degrade", "profile")
    _STR_FIELDS = ("config", "precision", "perturb", "kernels", "priority")

    def _type_problems(self) -> list[str]:
        """JSON is typed; the spec must be too -- members=2.0 or
        lead_steps=true would otherwise survive until mid-rollout."""
        problems = []
        for name in self._INT_FIELDS:
            v = getattr(self, name)
            if isinstance(v, bool) or not isinstance(v, int):
                problems.append(f"{name} must be an integer, got {v!r}")
        for name in self._BOOL_FIELDS:
            if not isinstance(getattr(self, name), bool):
                problems.append(f"{name} must be a boolean, "
                                f"got {getattr(self, name)!r}")
        for name in self._STR_FIELDS:
            if not isinstance(getattr(self, name), str):
                problems.append(f"{name} must be a string, "
                                f"got {getattr(self, name)!r}")
        v = self.perturb_amplitude
        if isinstance(v, bool) or not isinstance(v, (int, float)):
            problems.append(f"perturb_amplitude must be a number, got {v!r}")
        v = self.deadline_ms
        if v is not None and (isinstance(v, bool)
                              or not isinstance(v, (int, float))):
            problems.append(
                f"deadline_ms must be a number or null, got {v!r}")
        return problems

    def validate(self) -> None:
        """Raise ValueError listing every problem (nothing traced yet)."""
        problems = self._type_problems()
        if problems:
            # type errors first; the value checks below assume them
            raise ValueError("; ".join(problems))
        from repro.configs import registry
        from repro.inference import perturbations as perturblib
        configs = registry.named_configs()
        if self.config not in configs:
            problems.append(
                f"unknown config {self.config!r}; expected one of "
                f"{sorted(configs)}")
        if self.lead_steps < 1:
            problems.append(f"lead_steps must be >= 1, got {self.lead_steps}")
        if self.lead_chunk < 1:
            problems.append(f"lead_chunk must be >= 1, got {self.lead_chunk}")
        if self.precision not in PRECISIONS:
            problems.append(
                f"precision must be one of {PRECISIONS}, "
                f"got {self.precision!r}")
        if self.kernels not in KERNEL_MODES:
            problems.append(
                f"kernels must be one of {KERNEL_MODES}, "
                f"got {self.kernels!r}")
        if self.priority not in PRIORITIES:
            problems.append(
                f"priority must be one of {PRIORITIES}, "
                f"got {self.priority!r}")
        if self.deadline_ms is not None and self.deadline_ms <= 0:
            problems.append(
                f"deadline_ms must be positive, got {self.deadline_ms}")
        if not 0 <= self.max_retries <= 8:
            problems.append(
                f"max_retries must be in [0, 8], got {self.max_retries}")
        try:
            pcfg = self.perturbation_config()
        except ValueError as e:
            problems.append(str(e))
        else:
            # the engine always centers the conditioning noise
            problems += perturblib.validate_member_count(
                self.members, centered=True, cfg=pcfg)
        if problems:
            raise ValueError("; ".join(problems))
