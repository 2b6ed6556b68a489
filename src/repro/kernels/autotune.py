"""Per-backend block-size autotuner for the Pallas kernels.

The kernels in ``repro.kernels`` tile their grids with block shapes that
were hand-picked once for one MXU shape (see ``BLOCK_DEFAULTS``).  The
right tile depends on the backend, the problem shape and the VMEM
budget, so this module adds the missing measurement loop:

* **Candidate lattice** -- per op family, the cross product of
  power-of-two tile values, filtered down to VMEM-feasible shapes whose
  padding waste stays bounded (padding exactness itself holds for *any*
  positive tile -- every kernel zero-pads and slices exactly -- so
  feasibility is purely a performance/VMEM filter).  Every candidate
  obeys Mosaic's (8, 128) minor-dims tiling rule (``legal_tile``), so
  a sweep never proposes a tile the TPU compiler refuses.  The default
  tile is always a candidate: a sweep can never pick something slower
  than the defaults.
* **Sweep** -- ``sweep_op`` times every candidate with warmup +
  ``block_until_ready`` (best-of-``iters``), picks the winner
  (ties prefer the default, then the lexicographically smallest dims)
  and records the full timing table.
* **Tuning cache** -- winners persist as one JSON file per
  (op, shapes, dtype) in a ``TuningCache`` directory, content-addressed
  by sha1 over (lattice version, op, shapes, dtype, backend, jax
  version) -- the same scoping discipline as the AOT executable cache:
  a jax upgrade or a backend move re-tunes instead of serving a stale
  winner.  Corrupt or stale entries read as *absent* (the serve path
  falls back to defaults, never crashes).
* **Serving resolution** -- ``install_tuning_cache`` makes a cache
  process-active; ``resolve_kernel_config`` (called inside
  ``RequestSpec.engine_config``) attaches each op's best tuning as
  ``KernelConfig.blocks``, upstream of ``engine_key``/``batch_key`` and
  the ``ExecutableKey`` token -- so tuned engines are distinct cache
  entries and warm requests dispatch the executables compiled for their
  tile shapes.  ``serving.bundle`` packs the active entries so a
  bundle-booted replica serves tuned kernels with zero sweeps.

See docs/kernels.md#autotuning for the cache layout and re-tune policy.
"""

from __future__ import annotations

import dataclasses
import hashlib
import itertools
import json
import math
import os
import time

from repro.kernels.config import (BLOCK_DEFAULTS, BLOCK_OPS, BlockConfig,
                                  KernelConfig, default_interpret,
                                  legal_tile)

#: bump when the candidate lattice or entry schema changes incompatibly;
#: part of every entry token, so old caches read as stale, not wrong
LATTICE_VERSION = "2"

#: VMEM budget one kernel instance may plan for (half of the ~16 MB/core
#: so double buffering still fits)
VMEM_BUDGET_BYTES = 8 * 2**20

#: per-dim padded-extent waste bound: a candidate whose padded extent
#: exceeds this multiple of the true extent is pruned (the default tile
#: is exempt -- it must always be sweepable)
WASTE_BOUND = 2.0

#: shape-tuple field names per op, in order (the ``shapes`` argument of
#: ``sweep_op`` and the ``shapes`` list in every cache entry)
OP_SHAPE_FIELDS = {
    "legendre": ("b", "k", "n", "m"),
    "disco": ("n", "r", "h_in", "h", "s", "w_in", "k", "d", "q", "stride"),
    "crps": ("e", "n"),
    "ssd": ("bc", "l", "h", "p", "g", "n"),
}

#: candidate values per block dim (cross product, then feasibility)
_LATTICE = {
    "legendre": {"b_blk": (8, 16, 32, 64, 128, 256),
                 "k_blk": (128, 256, 512),
                 "n_blk": (128, 256, 512),
                 "m_blk": (1, 2, 4, 8, 16)},
    "disco": {"c_blk": (128, 256, 512),
              "w_blk": (128, 256)},
    "crps": {"n_blk": (128, 256, 512, 1024, 2048, 4096, 8192)},
    "ssd": {"bc_blk": (1, 2, 4, 8)},
}

#: which shape field each block dim tiles (for waste estimation)
_DIM_EXTENT = {
    "legendre": {"b_blk": "b", "k_blk": "k", "n_blk": "n", "m_blk": "m"},
    "disco": {"c_blk": "r", "w_blk": "w_out"},
    "crps": {"n_blk": "n"},
    "ssd": {"bc_blk": "bc"},
}


def _shape_dict(op: str, shapes) -> dict:
    fields = OP_SHAPE_FIELDS[op]
    shapes = tuple(int(s) for s in shapes)
    if len(shapes) != len(fields):
        raise ValueError(f"op {op!r} expects shapes {fields}, "
                         f"got {shapes}")
    return dict(zip(fields, shapes))


def _pad_up(extent: int, blk: int) -> int:
    return -(-extent // blk) * blk


# ---------------------------------------------------------------------------
# Candidate generation + feasibility
# ---------------------------------------------------------------------------

def vmem_bytes(op: str, dims: dict, shapes) -> int:
    """Float32 bytes one kernel instance keeps resident in VMEM
    (operand blocks + output block + the dominant intermediate)."""
    s = _shape_dict(op, shapes)
    if op == "legendre":
        b, k, n, m = dims["b_blk"], dims["k_blk"], dims["n_blk"], \
            dims["m_blk"]
        return 4 * (b * k * m + k * n * m + 2 * b * n * m)
    if op == "disco":
        from repro.kernels.disco import disco
        stride = s["stride"]
        g = disco.tile_geometry(s["r"], s["d"], s["w_in"] // stride, stride,
                                BlockConfig.make("disco", **dims))
        return disco.vmem_bytes(g, s["k"], s["q"], stride)
    if op == "crps":
        return 4 * (s["e"] + 4) * dims["n_blk"]
    if op == "ssd":
        bc = dims["bc_blk"]
        per_row = (2 * s["l"] * s["p"] + s["l"] + 2 * s["l"] * s["n"]
                   + s["p"] * s["n"])
        return 4 * (bc * per_row + 2 * s["l"] * s["l"])
    raise ValueError(f"unknown op {op!r}")


def _extents(op: str, shapes) -> dict:
    s = _shape_dict(op, shapes)
    if op == "disco":
        s["w_out"] = s["w_in"] // s["stride"]
    return s


def padding_waste(op: str, dims: dict, shapes) -> float:
    """Product over tiled dims of padded_extent / extent (>= 1.0)."""
    s = _extents(op, shapes)
    w = 1.0
    for name, value in dims.items():
        extent = s[_DIM_EXTENT[op][name]]
        w *= _pad_up(extent, value) / max(extent, 1)
    return w


def feasible(op: str, dims: dict, shapes,
             vmem_budget: int = VMEM_BUDGET_BYTES) -> bool:
    """Legal tile + VMEM fit + bounded padding waste for every tiled dim."""
    if not legal_tile(op, dims):
        return False
    if vmem_bytes(op, dims, shapes) > vmem_budget:
        return False
    s = _extents(op, shapes)
    for name, value in dims.items():
        extent = s[_DIM_EXTENT[op][name]]
        if _pad_up(extent, value) > WASTE_BOUND * max(extent, 1):
            return False
    return True


def candidates(op: str, shapes, max_candidates: int | None = 8,
               vmem_budget: int = VMEM_BUDGET_BYTES) -> list[dict]:
    """Feasible tile candidates for ``op`` at ``shapes``, default first.

    Deterministic: the cross product of ``_LATTICE[op]`` is filtered by
    ``feasible`` and sorted by (padding waste, VMEM footprint, dims);
    the default tile is always candidate 0 even when infeasible by the
    waste bound (it must be sweepable so tuning can never lose to it),
    and ``max_candidates`` (None = unlimited) caps the rest.
    """
    if op not in BLOCK_OPS:
        raise ValueError(f"unknown op {op!r}; expected {BLOCK_OPS}")
    default = dict(BLOCK_DEFAULTS[op])
    names = sorted(_LATTICE[op])
    pool = []
    for values in itertools.product(*(_LATTICE[op][n] for n in names)):
        dims = dict(zip(names, values))
        if dims == default:
            continue
        if feasible(op, dims, shapes, vmem_budget):
            pool.append(dims)
    pool.sort(key=lambda d: (padding_waste(op, d, shapes),
                             vmem_bytes(op, d, shapes),
                             tuple(sorted(d.items()))))
    if max_candidates is not None:
        pool = pool[:max(max_candidates - 1, 0)]
    return [default] + pool


# ---------------------------------------------------------------------------
# Op runners + timing
# ---------------------------------------------------------------------------

def _op_call(op: str, shapes, dtype: str, interpret: bool,
             blocks: BlockConfig | None):
    """A zero-arg callable running one kernel invocation at ``shapes``
    with ``blocks`` (deterministic inputs, dtype-cast before the call)."""
    import jax.numpy as jnp
    import numpy as np
    s = _shape_dict(op, shapes)
    rng = np.random.default_rng(0)

    def arr(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.dtype(dtype))

    if op == "legendre":
        from repro.kernels.legendre.legendre import legendre_contract
        x = arr(s["b"], s["k"], s["m"])
        t = arr(s["m"], s["k"], s["n"])
        return lambda: legendre_contract(x, t, interpret=interpret,
                                         blocks=blocks)
    if op == "disco":
        from repro.kernels.disco.disco import disco_band_contract
        x = arr(s["n"], s["r"], s["h_in"], s["w_in"])
        psi = arr(s["k"], s["h"], s["s"], s["d"])
        mix = arr(s["k"], s["q"], s["r"])
        stride = s["stride"]
        affine = (max(1, s["h_in"] // s["h"]), -(s["s"] // 2))
        return lambda: disco_band_contract(x, psi, mix, stride=stride,
                                           affine=affine,
                                           off0=-(s["d"] // 2),
                                           interpret=interpret,
                                           blocks=blocks)
    if op == "crps":
        from repro.kernels.crps.crps import crps_fused
        ens = arr(s["e"], s["n"])
        obs = arr(s["n"])
        return lambda: crps_fused(ens, obs, fair=True, interpret=interpret,
                                  blocks=blocks)
    if op == "ssd":
        from repro.kernels.ssd.ssd import ssd_intra_chunk
        x = arr(s["bc"], s["l"], s["h"], s["p"])
        da = jnp.cumsum(
            -jnp.abs(arr(s["bc"], s["l"], s["h"])) * 0.05, axis=1)
        b = arr(s["bc"], s["l"], s["g"], s["n"])
        c = arr(s["bc"], s["l"], s["g"], s["n"])
        g = s["g"]
        return lambda: ssd_intra_chunk(x, da, b, c, n_groups=g,
                                       interpret=interpret, blocks=blocks)
    raise ValueError(f"unknown op {op!r}")


def device_timer(warmup: int = 1, iters: int = 3):
    """The default ``sweep_op`` timer: best-of-``iters`` seconds after
    ``warmup`` compile-absorbing calls, fully ``block_until_ready``."""
    import jax

    def timer(dims: dict, fn) -> float:
        for _ in range(warmup):
            jax.block_until_ready(fn())
        best = math.inf
        for _ in range(iters):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            best = min(best, time.perf_counter() - t0)
        return best

    return timer


def sweep_op(op: str, shapes, *, dtype: str = "float32",
             interpret: bool | None = None, timer=None,
             max_candidates: int | None = 8,
             cache: "TuningCache | None" = None, force: bool = False,
             warmup: int = 1, iters: int = 3) -> dict:
    """Tune ``op`` at ``shapes``: sweep the candidate lattice, pick the
    winner, optionally persist it.

    Returns the tuning entry (also what ``TuningCache`` stores)::

        {op, shapes, dtype, backend, jax, lattice, mode, dims,
         default_us, best_us, candidates: [{dims, us}, ...], swept}

    ``swept`` is False when ``cache`` already held a valid entry (no
    timing ran).  ``timer(dims, fn) -> seconds`` is injectable so sweep
    logic is testable without a device; the default times on the real
    backend with warmup + ``block_until_ready``.  The winner is the
    fastest candidate; ties prefer the default tile, then the
    lexicographically smallest dims.  The default is always in the
    sweep, so ``best_us <= default_us`` by construction.
    """
    import jax
    if cache is not None and not force:
        hit = cache.get(op, shapes, dtype)
        if hit is not None:
            return {**hit, "swept": False}
    if interpret is None:
        interpret = default_interpret()
    if timer is None:
        timer = device_timer(warmup=warmup, iters=iters)
    default = dict(BLOCK_DEFAULTS[op])
    table = []
    for dims in candidates(op, shapes, max_candidates=max_candidates):
        blocks = None if dims == default else BlockConfig.make(op, **dims)
        fn = _op_call(op, shapes, dtype, interpret, blocks)
        seconds = float(timer(dims, fn))
        table.append({"dims": dims, "us": round(seconds * 1e6, 3)})
    winner = min(table, key=lambda r: (r["us"], r["dims"] != default,
                                       tuple(sorted(r["dims"].items()))))
    entry = {
        "op": op,
        "shapes": [int(v) for v in shapes],
        "dtype": dtype,
        "backend": jax.default_backend(),
        "jax": jax.__version__,
        "lattice": LATTICE_VERSION,
        "mode": "interpret" if interpret else "compiled",
        "dims": winner["dims"],
        "default_us": table[0]["us"],
        "best_us": winner["us"],
        "candidates": table,
    }
    if cache is not None:
        cache.put(entry)
    return {**entry, "swept": True}


# ---------------------------------------------------------------------------
# Persistent tuning cache
# ---------------------------------------------------------------------------

_ENTRY_KEYS = ("op", "shapes", "dtype", "backend", "jax", "lattice",
               "mode", "dims", "default_us", "best_us", "candidates")


class TuningCache:
    """Content-addressed on-disk winners: one JSON file per
    (op, shapes, dtype), scoped by backend + jax version + lattice
    version through the filename token.

    Reads are forgiving -- a corrupt, truncated or stale (wrong
    backend/jax/lattice) entry is treated as absent, so the serve path
    degrades to default tiles instead of crashing.  Writes are atomic
    (tmp + rename) with canonical JSON, so identical sweeps produce
    byte-identical files (content addressing holds end to end).
    """

    def __init__(self, root: str):
        self.root = str(root)
        os.makedirs(self.root, exist_ok=True)
        self._memo: list[tuple[str, dict]] | None = None

    # -- keying --------------------------------------------------------
    @staticmethod
    def entry_token(op: str, shapes, dtype: str, backend: str,
                    jax_version: str) -> str:
        shape_s = ",".join(str(int(v)) for v in shapes)
        tag = (f"v{LATTICE_VERSION}|{op}|{shape_s}|{dtype}"
               f"|{backend}|jax={jax_version}")
        return hashlib.sha1(tag.encode("utf-8")).hexdigest()[:16]

    def entry_path(self, op: str, shapes, dtype: str = "float32") -> str:
        import jax
        token = self.entry_token(op, shapes, dtype, jax.default_backend(),
                                 jax.__version__)
        return os.path.join(self.root, f"tune_{token}.json")

    # -- IO ------------------------------------------------------------
    def _load(self, path: str) -> dict | None:
        """One entry, or None for anything unusable (corrupt JSON,
        missing fields, invalid dims, stale backend/jax/lattice)."""
        import jax
        try:
            with open(path) as f:
                entry = json.load(f)
            if not isinstance(entry, dict):
                return None
            if any(k not in entry for k in _ENTRY_KEYS):
                return None
            if entry["op"] not in BLOCK_OPS:
                return None
            if (entry["backend"] != jax.default_backend()
                    or entry["jax"] != jax.__version__
                    or entry["lattice"] != LATTICE_VERSION):
                return None
            BlockConfig.make(entry["op"], **entry["dims"])  # validates
            return entry
        except (OSError, ValueError, TypeError, KeyError):
            return None

    def get(self, op: str, shapes, dtype: str = "float32") -> dict | None:
        path = self.entry_path(op, shapes, dtype)
        if not os.path.exists(path):
            return None
        return self._load(path)

    def put(self, entry: dict) -> str:
        """Persist one entry (atomic, canonical bytes); returns path."""
        entry = {k: entry[k] for k in _ENTRY_KEYS}
        token = self.entry_token(entry["op"], entry["shapes"],
                                 entry["dtype"], entry["backend"],
                                 entry["jax"])
        path = os.path.join(self.root, f"tune_{token}.json")
        blob = json.dumps(entry, sort_keys=True, indent=1)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            f.write(blob)
        os.replace(tmp, path)
        self._memo = None
        return path

    def entries(self) -> list[tuple[str, dict]]:
        """All usable (filename, entry) pairs, sorted by filename.
        Scanned once per instance; ``put`` invalidates the memo."""
        if self._memo is None:
            out = []
            try:
                names = sorted(os.listdir(self.root))
            except OSError:
                names = []
            for name in names:
                if not (name.startswith("tune_")
                        and name.endswith(".json")):
                    continue
                entry = self._load(os.path.join(self.root, name))
                if entry is not None:
                    out.append((name, entry))
            self._memo = out
        return list(self._memo)

    def best_for(self, op: str) -> BlockConfig | None:
        """The tuning that rides serving for ``op``: the entry tuned at
        the largest problem (by shape-element product -- the dominant
        slab wins), None when nothing usable exists.  Returns None too
        when the winner *is* the default tile (no need to fragment the
        executable cache for a no-op override)."""
        best = None
        best_rank = None
        for name, entry in self.entries():
            if entry["op"] != op:
                continue
            rank = (math.prod(entry["shapes"]), name)
            if best_rank is None or rank > best_rank:
                best, best_rank = entry, rank
        if best is None:
            return None
        bc = BlockConfig.make(op, **best["dims"])
        return None if bc.is_default() else bc

    def stats(self) -> dict:
        ops: dict[str, int] = {}
        for _, entry in self.entries():
            ops[entry["op"]] = ops.get(entry["op"], 0) + 1
        return {"dir": self.root, "entries": sum(ops.values()), "ops": ops}


# ---------------------------------------------------------------------------
# Process-active cache + KernelConfig resolution
# ---------------------------------------------------------------------------

_ACTIVE: TuningCache | None = None


def install_tuning_cache(cache: "TuningCache | str | None"
                         ) -> TuningCache | None:
    """Make ``cache`` (a ``TuningCache`` or directory path; None
    uninstalls) the process-active tuning source and return the previous
    one.  Installed tunings resolve into every subsequently built
    ``RequestSpec.engine_config`` -- upstream of ``engine_key`` and the
    AOT executable token, so tuned and default engines never collide."""
    global _ACTIVE
    previous = _ACTIVE
    if isinstance(cache, str):
        cache = TuningCache(cache)
    _ACTIVE = cache
    return previous


def active_tuning_cache() -> TuningCache | None:
    return _ACTIVE


def resolve_kernel_config(kernels: KernelConfig | None
                          ) -> KernelConfig | None:
    """Attach the active tuning cache's winners to ``kernels``.

    No active cache, no usable entries, or an explicit ``blocks`` on
    ``kernels`` -> returned unchanged (``None`` stays ``None``), keeping
    untuned keys and behavior bit-identical.  Otherwise returns a config
    carrying one ``BlockConfig`` per tuned op (``None`` becomes a
    default ``KernelConfig`` with tunings -- an installed cache must
    reach engines built for "auto" requests too).
    """
    if _ACTIVE is None:
        return kernels
    if kernels is not None and kernels.blocks:
        return kernels
    blocks = []
    for op in BLOCK_OPS:
        bc = _ACTIVE.best_for(op)
        if bc is not None:
            blocks.append(bc)
    if not blocks:
        return kernels
    base = kernels if kernels is not None else KernelConfig()
    return dataclasses.replace(base, blocks=tuple(blocks))


# ---------------------------------------------------------------------------
# Model-derived shapes, roofline terms, display helpers
# ---------------------------------------------------------------------------

def model_op_shapes(model, members: int = 2) -> dict:
    """Concrete tuning shapes for a live model's hot ops (FCN3 or SFNO),
    as a list of shapes per op family.

    legendre: the latent-grid SHT slab batched over ``members`` member
    channels (the spectral-convolution hot spot); disco (FCN3's): the
    local blocks' latent-plan contraction with its channel mix, one
    shape per distinct row run (``DiscoPlan.band_runs``: the rows, taps
    and input rows of each kernel call dispatch makes); crps: the
    pointwise score over the full state.  ``TuningCache.best_for``
    serves the largest tuned slab, so the dominant run's winner rides
    serving.
    """
    from repro.kernels.dispatch import band_run_rows
    cfg = model.cfg
    m, h, l = model.latent_sht.buffer_specs()["wpct"].shape
    out = {"legendre": [(members * cfg.c_latent, h, l, m)]}
    if model.disco_plans:
        plan = model.latent_plan
        k, _, s, w_in = plan.psi.shape
        c_in = cfg.c_latent + cfg.cond_embed
        disco = []
        for h0, h1, d in plan.band_runs:
            r0, r1, _ = band_run_rows(h0, h1, s, plan.affine,
                                      model.grid_latent.nlat)
            shape = (members, c_in, r1 - r0, h1 - h0, s, w_in, k, d,
                     cfg.c_latent, plan.stride)
            if shape not in disco:
                disco.append(shape)
        out["disco"] = disco
    out["crps"] = [(members, cfg.n_state * cfg.nlat * cfg.nlon)]
    return out


def op_flops_bytes(op: str, shapes) -> tuple[float, float]:
    """(flops, float32 HBM bytes) of one kernel invocation -- the
    numerator of the achieved-GFLOP/s / GB/s columns in
    ``benchmarks/run.py`` (reusing ``roofline_report.achieved``)."""
    s = _shape_dict(op, shapes)
    if op == "legendre":
        flops = 2.0 * s["b"] * s["k"] * s["n"] * s["m"]
        mem = 4.0 * (s["b"] * s["k"] * s["m"] + s["k"] * s["n"] * s["m"]
                     + s["b"] * s["n"] * s["m"])
    elif op == "disco":
        w_out = s["w_in"] // s["stride"]
        pix = s["n"] * s["h"] * w_out
        flops = 2.0 * pix * s["k"] * s["r"] * (s["s"] * s["d"] + s["q"])
        mem = 4.0 * (s["n"] * s["r"] * s["h_in"] * s["w_in"]
                     + s["k"] * s["h"] * s["s"] * s["d"]
                     + s["k"] * s["q"] * s["r"] + pix * s["q"])
    elif op == "crps":
        flops = 3.0 * s["e"] * s["e"] * s["n"]
        mem = 4.0 * (s["e"] * s["n"] + 2 * s["n"])
    elif op == "ssd":
        per = (2.0 * s["l"] * s["l"] * s["n"] + 2.0 * s["l"] * s["l"] * s["p"]
               + 2.0 * s["l"] * s["p"] * s["n"])
        flops = s["bc"] * s["h"] * per
        mem = 4.0 * s["bc"] * (2 * s["l"] * s["h"] * s["p"]
                               + s["l"] * s["h"]
                               + 2 * s["l"] * s["g"] * s["n"]
                               + s["h"] * s["p"] * s["n"])
    else:
        raise ValueError(f"unknown op {op!r}")
    return flops, mem


def format_blocks(op: str, dims: dict | None = None) -> str:
    """Compact single-token tile spec for CSV derived columns (no commas
    or semicolons): ``b128.k128.m8.n128`` for the legendre default."""
    full = {**BLOCK_DEFAULTS[op], **(dims or {})}
    return ".".join(f"{name[:-4]}{value}"
                    for name, value in sorted(full.items()))
