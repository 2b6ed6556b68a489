"""Kernel-dispatch configuration: which substrate executes each hot op.

FCN3's two dominant contractions -- the Legendre stage of the SHT and the
banded DISCO convolution (paper App. B.5 / C) -- each have two
implementations in this repo:

* ``reference`` -- pure-XLA einsum/FFT paths in ``repro.core.sphere``
  (exact, differentiable, runs anywhere);
* ``pallas``    -- the MXU-shaped Pallas kernels in ``repro.kernels``
  (the TPU analogue of the paper's custom CUDA kernels).

``KernelConfig`` selects the substrate per op.  It lives on
``FCN3Config`` (so ``FCN3.make_buffers`` builds the matching buffer
layout) and on ``EngineConfig`` (so the serving AOT executable-cache key
distinguishes programs compiled for different substrates).

This module is deliberately dependency-light (dataclasses + jax only):
``repro.core`` imports it at module level without pulling the Pallas
kernel implementations; those load lazily inside
``repro.kernels.dispatch`` only when a pallas path is actually resolved.
"""

from __future__ import annotations

import dataclasses

import jax

#: backends where a Pallas kernel compiles to real hardware.  Anything
#: else (cpu, METAL, ...) can only run kernels in interpret mode.
COMPILED_BACKENDS = ("tpu", "gpu", "cuda", "rocm")

_MODES = ("auto", "reference", "pallas")
_OPS = ("sht", "disco")

#: op families whose Pallas kernels take a tunable tile shape.  "legendre"
#: covers both SHT directions (the contraction is the same kernel).
BLOCK_OPS = ("legendre", "disco", "crps", "ssd")

#: the authoritative default tile shapes: an empty/absent
#: ``BlockConfig`` resolves to exactly these values.
BLOCK_DEFAULTS = {
    "legendre": {"b_blk": 128, "k_blk": 128, "m_blk": 8, "n_blk": 128},
    "disco": {"c_blk": 128, "w_blk": 128},
    "crps": {"n_blk": 1024},
    "ssd": {"bc_blk": 1},
}

#: Mosaic's tiling rule for the two minor dims of a block: the
#: second-minor (sublane) dim must be a multiple of 8 and the minor
#: (lane) dim a multiple of 128 (or span the whole array dim, which the
#: zero-padding wrappers never rely on).  Per op, the multiple each tile
#: dim must honour because of the operand blocks it lands in; dims absent
#: here are leading (batch/grid) dims with no constraint.
TILE_MULTIPLES = {
    # x (m, b, k), table (m, k, n), out (m, b, n)
    "legendre": {"b_blk": 8, "k_blk": 128, "n_blk": 128},
    # x (P, c, W) sublane c; mix (K, Q, c) lane c; output tiles of w_blk
    # lanes.  A channel axis no wider than c_blk is one whole-axis tile.
    "disco": {"c_blk": 128, "w_blk": 128},
    # ens (E, n), obs/out (1, n)
    "crps": {"n_blk": 128},
    "ssd": {},
}


#: lanes in a vector register row: the minor-dim multiple of that rule
LANE = 128


def disco_window(d: int, stride: int, w_blk: int) -> int:
    """Input longitudes one banded-DISCO output tile of ``w_blk``
    longitudes reads for a band of ``d`` taps at longitudinal ``stride``:
    ``w_blk + ceil(d / stride) - 1``, rounded up to whole lanes.  The
    kernel issues this many multiply-adds per output, channel and basis
    function (``repro.kernels.disco.disco``), whatever the taps hold."""
    return -(-(w_blk + -(-d // stride) - 1) // LANE) * LANE


def legal_tile(op: str, dims: dict) -> bool:
    """True iff every tile dim of ``dims`` obeys ``TILE_MULTIPLES[op]``."""
    rule = TILE_MULTIPLES[op]
    return all(v % rule.get(name, 1) == 0 for name, v in dims.items())


def compiled_backend() -> bool:
    """True when ``jax.default_backend()`` compiles Pallas kernels."""
    return jax.default_backend() in COMPILED_BACKENDS


def default_interpret() -> bool:
    """Backend-aware interpret default for every kernel wrapper.

    False on TPU/GPU (compile the kernel -- a real accelerator must
    never silently fall into the slow interpreter), True elsewhere
    (interpreting is the only way a Pallas kernel runs on CPU).
    """
    return not compiled_backend()


@dataclasses.dataclass(frozen=True)
class BlockConfig:
    """Tile-shape override for one kernel-op family.

    ``dims`` is a sorted tuple of ``(name, value)`` pairs overriding a
    subset of ``BLOCK_DEFAULTS[op]``; unnamed dims keep their default.
    Frozen + hashable (and ``dataclasses.astuple``-able), so it nests
    inside ``KernelConfig`` and therefore inside every engine-pool and
    AOT executable-cache key -- a tuned tile shape *is* a different
    compiled program and must never collide with the default one.
    """

    op: str
    dims: tuple = ()

    def __post_init__(self):
        if self.op not in BLOCK_OPS:
            raise ValueError(f"BlockConfig.op must be one of {BLOCK_OPS}, "
                             f"got {self.op!r}")
        norm = []
        for pair in self.dims:
            name, value = pair
            if name not in BLOCK_DEFAULTS[self.op]:
                raise ValueError(
                    f"unknown block dim {name!r} for op {self.op!r}; "
                    f"expected a subset of "
                    f"{sorted(BLOCK_DEFAULTS[self.op])}")
            if isinstance(value, bool) or not isinstance(value, int) \
                    or value < 1:
                raise ValueError(
                    f"block dim {name}={value!r} must be a positive int")
            norm.append((name, value))
        norm.sort()
        if len({n for n, _ in norm}) != len(norm):
            raise ValueError(f"duplicate block dims in {self.dims!r}")
        object.__setattr__(self, "dims", tuple(norm))

    @classmethod
    def make(cls, op: str, **dims: int) -> "BlockConfig":
        return cls(op, tuple(sorted(dims.items())))

    def sizes(self) -> dict:
        """Full dim->value mapping: defaults overlaid with this config."""
        return {**BLOCK_DEFAULTS[self.op], **dict(self.dims)}

    def is_default(self) -> bool:
        return self.sizes() == BLOCK_DEFAULTS[self.op]


def block_sizes(op: str, blocks: "BlockConfig | None" = None) -> dict:
    """The tile shape a kernel wrapper should actually use.

    ``blocks=None`` (the untuned path) resolves to ``BLOCK_DEFAULTS[op]``
    exactly; a ``BlockConfig`` must carry the same ``op``.
    """
    if op not in BLOCK_OPS:
        raise ValueError(f"unknown block op {op!r}; expected {BLOCK_OPS}")
    if blocks is None:
        return dict(BLOCK_DEFAULTS[op])
    if blocks.op != op:
        raise ValueError(f"BlockConfig for op {blocks.op!r} passed to a "
                         f"{op!r} kernel")
    return blocks.sizes()


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """Per-op kernel substrate selection with backend-aware defaults.

    sht / disco: "auto" | "reference" | "pallas".
      "auto" resolves to the Pallas kernel on a compiled backend
      (TPU/GPU) and to the reference XLA path on CPU.
    interpret: tri-state Pallas interpret flag.  ``None`` auto-detects
      from the backend (compiled on TPU/GPU).  On CPU an explicit
      ``interpret=True`` is the *only* way to get the Pallas kernels
      (interpret mode exists for parity testing, not speed): a plain
      ``sht="pallas"`` on CPU degrades to the reference path rather
      than silently running the interpreter.  On a compiled backend
      ``interpret=True`` is an error: a chip never runs the
      interpreter, and a "pallas" op there always compiles.

    blocks: tile-shape overrides, a tuple of ``BlockConfig`` (at most
      one per op family, sorted by op).  Empty means the hardcoded
      ``BLOCK_DEFAULTS`` -- bit-identical to the pre-autotuner dispatch.
      Populated by ``repro.kernels.autotune.resolve_kernel_config`` from
      the installed tuning cache, or explicitly.

    Frozen + hashable: nests inside ``FCN3Config`` / ``EngineConfig``
    and therefore inside every engine-pool and AOT executable-cache key.
    """

    sht: str = "auto"
    disco: str = "auto"
    interpret: bool | None = None
    blocks: tuple = ()

    def __post_init__(self):
        for op in _OPS:
            if getattr(self, op) not in _MODES:
                raise ValueError(
                    f"KernelConfig.{op} must be one of {_MODES}, "
                    f"got {getattr(self, op)!r}")
        if self.interpret not in (None, True, False):
            raise ValueError(
                f"KernelConfig.interpret must be None/True/False, "
                f"got {self.interpret!r}")
        blocks = tuple(self.blocks)
        for bc in blocks:
            if not isinstance(bc, BlockConfig):
                raise ValueError(
                    f"KernelConfig.blocks entries must be BlockConfig, "
                    f"got {bc!r}")
        ops = [bc.op for bc in blocks]
        if len(set(ops)) != len(ops):
            raise ValueError(f"duplicate BlockConfig ops in {ops}")
        object.__setattr__(
            self, "blocks", tuple(sorted(blocks, key=lambda b: b.op)))

    def blocks_for(self, op: str) -> BlockConfig | None:
        """This config's tile override for ``op`` (None = defaults)."""
        if op not in BLOCK_OPS:
            raise ValueError(f"unknown block op {op!r}; "
                             f"expected {BLOCK_OPS}")
        for bc in self.blocks:
            if bc.op == op:
                return bc
        return None

    def with_blocks(self, *blocks: BlockConfig) -> "KernelConfig":
        """A copy carrying ``blocks`` (replacing any existing set)."""
        return dataclasses.replace(self, blocks=tuple(blocks))

    def resolve(self, op: str) -> tuple[str, bool]:
        """(path, interpret) actually used for ``op`` on this backend.

        path is "reference" or "pallas"; interpret only matters for
        "pallas".  Resolution consults ``jax.default_backend()`` so the
        same config does the right thing on TPU, GPU and CPU CI.
        """
        if op not in _OPS:
            raise ValueError(f"unknown kernel op {op!r}; expected {_OPS}")
        mode = getattr(self, op)
        compiled = compiled_backend()
        if compiled and self.interpret:
            raise ValueError(
                f"KernelConfig.interpret=True on the compiled "
                f"{jax.default_backend()!r} backend: Pallas kernels "
                f"compile there, they never run interpreted")
        if mode == "auto":
            mode = "pallas" if compiled else "reference"
        if mode == "pallas" and not compiled and self.interpret is not True:
            # CPU interpret mode only on explicit request
            mode = "reference"
        return mode, not compiled

    def effective(self) -> dict[str, str]:
        """Resolved dispatch summary (for stats endpoints / benchmarks)."""
        out = {}
        for op in _OPS:
            path, interpret = self.resolve(op)
            out[op] = ("pallas[interpret]" if path == "pallas" and interpret
                       else path)
        return out
