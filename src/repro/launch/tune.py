"""Offline Pallas kernel autotuning (docs/kernels.md#autotuning).

Sweeps the candidate tile lattice for each hot-op family at concrete
shapes -- either derived from a named model config (for DISCO, one shape
per row run of the latent plan, as dispatch calls the kernel) or given
explicitly, an ``--op`` repeated once per shape --
and persists the winners in a ``TuningCache`` directory.  A second run
over the same shapes reports ``sweeps=0``: everything resolves from the
cache.  Serve with the results via ``repro.launch.service --tuning-dir``
(or pack them into a warm-start bundle; see docs/deployment.md).

Tune the smoke model's hot ops on this backend::

  PYTHONPATH=src python -m repro.launch.tune --config smoke \\
      --tuning-dir .tuning

Explicit shapes (CSV fields per op; see
``repro.kernels.autotune.OP_SHAPE_FIELDS``)::

  PYTHONPATH=src python -m repro.launch.tune --tuning-dir .tuning \\
      --op legendre --shape 90,64,33,33 --op crps --shape 4,65160

Every tuned op prints one CSV row
(``op,shapes,swept,candidates,default_us,best_us,speedup,blocks``); the
final line is the machine-checkable summary
(``sweeps=N entries=M dir=...``).
"""

from __future__ import annotations

import argparse
import logging

_log = logging.getLogger("repro.launch.tune")


def _model_shapes(config: str, members: int) -> dict:
    from repro.configs import registry
    from repro.kernels.autotune import model_op_shapes
    return model_op_shapes(registry.build_model(config), members=members)


def main(argv=None) -> None:
    from repro.kernels import autotune

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--config", default="smoke",
                    help="named model config to derive op shapes from "
                         "(ignored when --op/--shape pairs are given)")
    ap.add_argument("--members", type=int, default=2,
                    help="ensemble size the derived shapes assume")
    ap.add_argument("--op", action="append", default=[],
                    choices=sorted(autotune.OP_SHAPE_FIELDS),
                    help="tune this op at the matching --shape (repeat "
                         "both, in order, to tune several)")
    ap.add_argument("--shape", action="append", default=[],
                    metavar="CSV",
                    help="comma-separated shape for the matching --op, "
                         "e.g. 90,64,33,33 for legendre (b,k,n,m)")
    ap.add_argument("--tuning-dir", default=".tuning",
                    help="TuningCache directory the winners persist in")
    ap.add_argument("--max-candidates", type=int, default=8,
                    help="cap on swept tile candidates per op (the "
                         "default tile is always included)")
    ap.add_argument("--iters", type=int, default=3,
                    help="timing repetitions per candidate (best-of)")
    ap.add_argument("--interpret", action="store_true",
                    help="force Pallas interpret mode (CPU smoke runs; "
                         "default auto-detects from the backend)")
    ap.add_argument("--force", action="store_true",
                    help="re-sweep even when the cache already holds an "
                         "entry for (op, shapes, dtype, backend, jax)")
    args = ap.parse_args(argv)
    from repro import compile_cache
    compile_cache.configure()
    if len(args.op) != len(args.shape):
        ap.error(f"got {len(args.op)} --op but {len(args.shape)} "
                 f"--shape; they pair up in order")

    if args.op:
        ops_shapes = {}
        for op, raw in zip(args.op, args.shape):
            try:
                shape = tuple(int(v) for v in raw.split(","))
            except ValueError:
                ap.error(f"--shape {raw!r} is not a comma-separated "
                         f"integer list")
            ops_shapes.setdefault(op, []).append(shape)
    else:
        ops_shapes = _model_shapes(args.config, args.members)

    cache = autotune.TuningCache(args.tuning_dir)
    interpret = True if args.interpret else None
    sweeps = 0
    print("op,shapes,swept,candidates,default_us,best_us,speedup,blocks")
    for op, all_shapes in ops_shapes.items():
        for shapes in all_shapes:
            entry = autotune.sweep_op(
                op, shapes, cache=cache, force=args.force,
                interpret=interpret, max_candidates=args.max_candidates,
                iters=args.iters)
            sweeps += entry["swept"]
            speedup = entry["default_us"] / max(entry["best_us"], 1e-9)
            print(f"{op},{'x'.join(str(v) for v in shapes)},"
                  f"{int(entry['swept'])},{len(entry['candidates'])},"
                  f"{entry['default_us']:.1f},{entry['best_us']:.1f},"
                  f"{speedup:.2f}x,"
                  f"{autotune.format_blocks(op, entry['dims'])}")
    stats = cache.stats()
    print(f"sweeps={sweeps} entries={stats['entries']} dir={stats['dir']}")


if __name__ == "__main__":
    main()
