import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The dry run compiles for fake host devices only: pin the CPU backend,
# so neither this process nor the per-case children it spawns with
# --all ever claims an attached accelerator.
os.environ["JAX_PLATFORMS"] = "cpu"
# DFT-as-GEMM: XLA SPMD replicates fft operands even when only batch dims
# are sharded (see repro.core.sphere.fourier) -- matmul mode keeps every
# longitudinal transform rank-local and MXU-bound.
os.environ.setdefault("REPRO_DFT_MODE", "matmul")

"""Multi-pod dry-run (deliverable e).

Lowers + compiles every (architecture x input-shape) combination against the
production meshes -- 16x16 = 256 chips single-pod and 2x16x16 = 512 chips
multi-pod -- using ShapeDtypeStruct stand-ins (no allocation), then prints
memory_analysis / cost_analysis and the roofline terms.

Usage:
  python -m repro.launch.dryrun --arch phi3-mini-3.8b --shape train_4k
  python -m repro.launch.dryrun --arch fcn3 --shape train --multi-pod
  python -m repro.launch.dryrun --all --out results.jsonl [--jobs 3]

The 512-device XLA flag above MUST precede any other import that touches
jax (jax locks the device count at first init); the CPU backend is
pinned there too, so on a host with a TPU the orchestrating parent and
its children never contend for the chip.
"""

import argparse      # noqa: E402
import dataclasses   # noqa: E402
import json          # noqa: E402
import subprocess    # noqa: E402
import sys           # noqa: E402
import time          # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np   # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import archs as archlib           # noqa: E402
from repro.configs import fcn3 as fcn3cfg            # noqa: E402
from repro.configs import shapes as shapelib         # noqa: E402
from repro.core.fcn3 import FCN3                     # noqa: E402
from repro.distributed import sharding as shard      # noqa: E402
from repro.launch import mesh as meshlib             # noqa: E402
from repro.launch import roofline as roof            # noqa: E402
from repro.models.transformer import LM              # noqa: E402
from repro.optim import adam as adamlib              # noqa: E402


def _named(mesh, spec_tree, struct_tree=None):
    if struct_tree is not None:
        spec_tree = shard.sanitize_specs(mesh, spec_tree, struct_tree)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def _count(tree) -> float:
    return float(sum(np.prod(l.shape)
                     for l in jax.tree_util.tree_leaves(tree)))


def active_param_count(cfg, params_struct) -> float:
    """Non-embedding active parameters (6*N_active*D convention)."""
    total = _count(params_struct)
    total -= cfg.vocab_size * cfg.d_model * 2  # embed + lm_head
    if cfg.moe:
        e, k = cfg.moe.n_experts, cfg.moe.top_k
        expert = 0.0
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                params_struct)[0]:
            name = str(path[-1])
            if any(n in name for n in ("w_gate", "w_up", "w_down")) \
                    and leaf.ndim >= 3 and e in leaf.shape:
                expert += float(np.prod(leaf.shape))
        total -= expert * (1.0 - k / e)
    return total


# ---------------------------------------------------------------------------
# LM step builders
# ---------------------------------------------------------------------------

def build_lm_case(arch: str, shape_name: str, mesh, dtype=jnp.bfloat16,
                  moe_dispatch: str = "dense"):
    shape = shapelib.INPUT_SHAPES[shape_name]
    cfg = shapelib.adapt_arch_for_shape(archlib.get_arch(arch), shape)
    if cfg.moe and moe_dispatch != "dense":
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(
                cfg.moe, dispatch=moe_dispatch,
                dp_axes=tuple(meshlib.data_axes(mesh))))
    model = LM(cfg, dtype=dtype)
    dp = meshlib.data_axes(mesh)
    params_struct = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pspecs = shard.lm_param_specs(cfg, params_struct)
    specs = shapelib.input_specs(cfg, shape, dtype=dtype)
    n_active = active_param_count(cfg, params_struct)

    if shape.mode == "train":
        opt = adamlib.Adam(lr=1e-4)
        opt_struct = jax.eval_shape(opt.init, params_struct)
        ospecs = shard.lm_opt_specs(pspecs)
        batch_struct = {k: v for k, v in specs.items()}
        bspecs = shard.lm_batch_specs(batch_struct, dp)

        def train_step(params, opt_state, batch):
            (loss, aux), grads = jax.value_and_grad(
                model.loss, has_aux=True)(params, batch)
            params, opt_state = opt.update(params, grads, opt_state)
            return params, opt_state, loss

        psh = _named(mesh, pspecs, params_struct)
        osh = _named(mesh, ospecs, opt_struct)
        fn = jax.jit(
            train_step,
            in_shardings=(psh, osh,
                          _named(mesh, bspecs, batch_struct)),
            out_shardings=(psh, osh, NamedSharding(mesh, P())),
            donate_argnums=(0, 1),
        )
        args = (params_struct, opt_struct, batch_struct)
        mf = roof.model_flops_train(
            n_active, shape.global_batch * shape.seq_len)
        return fn, args, mf

    if shape.mode == "prefill":
        batch_struct = {k: v for k, v in specs.items()
                        if k not in ("labels",)}
        bspecs = shard.lm_batch_specs(batch_struct, dp)

        def prefill(params, batch):
            logits, _ = model.apply_train(
                params, batch["tokens"], patches=batch.get("patches"),
                enc_frames=batch.get("enc_frames"))
            return logits

        s_total = shape.seq_len
        logits_struct = jax.ShapeDtypeStruct(
            (shape.global_batch, s_total, cfg.padded_vocab), dtype)
        lsh = _named(mesh, P(dp, None, "model"), logits_struct)
        fn = jax.jit(
            prefill,
            in_shardings=(_named(mesh, pspecs, params_struct),
                          _named(mesh, bspecs, batch_struct)),
            out_shardings=lsh,
        )
        mf = roof.model_flops_decode(
            n_active, shape.global_batch * shape.seq_len)
        return fn, (params_struct, batch_struct), mf

    # decode
    cache_struct = specs["cache"]
    cspecs = shard.lm_cache_specs(cache_struct, dp, shape.global_batch)
    tok_spec = P(dp, None)
    enc_in = "enc_states" in specs

    def serve_step(params, tokens, cache, pos, enc_states=None):
        return model.decode_step(params, tokens, cache, pos,
                                 enc_states=enc_states)

    csh = _named(mesh, cspecs, cache_struct)
    in_sh = [_named(mesh, pspecs, params_struct),
             _named(mesh, tok_spec, specs["tokens"]),
             csh, NamedSharding(mesh, P())]
    arglist = (params_struct, specs["tokens"], cache_struct, specs["pos"])
    if enc_in:
        in_sh.append(_named(mesh, P(dp, None, None), specs["enc_states"]))
        arglist = arglist + (specs["enc_states"],)
    logits_struct = jax.ShapeDtypeStruct(
        (shape.global_batch, 1, cfg.padded_vocab), dtype)
    fn = jax.jit(
        serve_step,
        in_shardings=tuple(in_sh),
        out_shardings=(_named(mesh, P(dp, None, "model"), logits_struct),
                       csh),
        donate_argnums=(2,),
    )
    mf = roof.model_flops_decode(n_active, shape.global_batch)
    return fn, arglist, mf


# ---------------------------------------------------------------------------
# FCN3 step builder (paper model)
# ---------------------------------------------------------------------------

FCN3_SHAPES = {
    # (batch, ensemble, rollout): Table 3 stage-1 train step and a 16-member
    # inference step at full 721x1440 resolution.
    "train": dict(batch=16, ensemble=16, rollout=1, mode="train"),
    "rollout4": dict(batch=4, ensemble=2, rollout=4, mode="train"),
    "inference": dict(batch=1, ensemble=16, rollout=1, mode="infer"),
}


def build_fcn3_case(shape_name: str, mesh, reduced: bool = False,
                    fcn3_mode: str = "domain", fcn3_dtype: str = "float32"):
    from repro.core import crps as crpslib
    from repro.train import trainer as trlib

    sh = FCN3_SHAPES[shape_name]
    cfg = fcn3cfg.fcn3_small() if reduced else fcn3cfg.fcn3_full()
    if fcn3_dtype != "float32":
        cfg = dataclasses.replace(cfg, dtype=fcn3_dtype)
    model = FCN3(cfg)
    dp = meshlib.data_axes(mesh)
    b, e, t = sh["batch"], sh["ensemble"], sh["rollout"]
    hw = (cfg.nlat, cfg.nlon)
    cw = fcn3cfg.channel_weights(cfg.n_levels)

    params_struct = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    buffers_struct = model.buffer_specs()
    pspecs = shard.fcn3_param_specs(params_struct, mode=fcn3_mode)

    bdt = cfg.jdtype
    batch_struct = {
        "state": jax.ShapeDtypeStruct((b, cfg.n_state) + hw, bdt),
        "targets": jax.ShapeDtypeStruct((b, t, cfg.n_state) + hw, bdt),
        "aux": jax.ShapeDtypeStruct((b, t, cfg.n_aux) + hw, bdt),
    }
    bspecs = shard.fcn3_batch_specs(batch_struct, dp, mode=fcn3_mode)

    member_axes = (("model", tuple(dp)) if fcn3_mode == "ensemble"
                   else None)
    tcfg = trlib.TrainConfig(ensemble_size=e, rollout_steps=t,
                             member_axes=member_axes)
    tr = trlib.EnsembleTrainer(model, tcfg, cw)
    buffers_struct = dict(buffers_struct, **tr.loss_buffer_specs())
    bufspecs = shard.fcn3_buffer_specs(buffers_struct)
    key_struct = jax.ShapeDtypeStruct((2,), jnp.uint32)

    # conv-style FLOP estimate: every weight fires at each latent pixel
    pixels = cfg.latent_nlat * cfg.latent_nlon
    n_params = _count(params_struct)
    mf = 6.0 * n_params * 0.05 * pixels * b * e * t
    # 0.05: weight-reuse factor -- only conv/spectral weights multiply per
    # pixel; pointwise MLP dominates counts (see EXPERIMENTS.md §Roofline).

    if sh["mode"] == "train":
        opt = tr.optimizer
        opt_struct = jax.eval_shape(opt.init, params_struct)
        ospecs = shard.lm_opt_specs(pspecs)

        def train_step(params, opt_state, buffers, batch, key):
            (loss, aux), grads = jax.value_and_grad(
                tr.rollout_loss, has_aux=True)(params, buffers, batch, key)
            params, opt_state = opt.update(params, grads, opt_state)
            return params, opt_state, loss

        psh = _named(mesh, pspecs, params_struct)
        osh = _named(mesh, ospecs, opt_struct)
        fn = jax.jit(
            train_step,
            in_shardings=(psh, osh,
                          _named(mesh, bufspecs, buffers_struct),
                          _named(mesh, bspecs, batch_struct),
                          NamedSharding(mesh, P())),
            out_shardings=(psh, osh, NamedSharding(mesh, P())),
            donate_argnums=(0, 1),
        )
        return fn, (params_struct, opt_struct, buffers_struct, batch_struct,
                    key_struct), mf

    def infer_step(params, buffers, state, cond):
        return jax.vmap(lambda s, c: model.apply(params, buffers, s, c)
                        )(state, cond)

    st = jax.ShapeDtypeStruct((e, b, cfg.n_state) + hw, cfg.jdtype)
    cd = jax.ShapeDtypeStruct((e, b, cfg.n_cond_in) + hw, cfg.jdtype)
    lat = "model" if fcn3_mode == "domain" else None
    if fcn3_mode == "ensemble":
        ens_spec = P("model", dp, None, None, None)
    else:
        # ensemble members over the data axes, latitude over model (domain)
        ens_spec = P(dp, None, None, lat, None)
    fn = jax.jit(
        infer_step,
        in_shardings=(_named(mesh, pspecs, params_struct),
                      _named(mesh, bufspecs, buffers_struct),
                      _named(mesh, ens_spec, st),
                      _named(mesh, ens_spec, cd)),
        out_shardings=_named(mesh, ens_spec, st),
    )
    return fn, (params_struct, buffers_struct, st, cd), mf / 6.0 * 2.0


# ---------------------------------------------------------------------------
# Runner
# ---------------------------------------------------------------------------

def run_case(arch: str, shape_name: str, multi_pod: bool,
             reduced_fcn3: bool = False, fcn3_mode: str = "domain",
             fcn3_dtype: str = "float32",
             moe_dispatch: str = "dense") -> dict:
    mesh = meshlib.make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    t0 = time.time()
    if arch == "fcn3":
        fn, args, mf = build_fcn3_case(shape_name, mesh,
                                       reduced=reduced_fcn3,
                                       fcn3_mode=fcn3_mode,
                                       fcn3_dtype=fcn3_dtype)
    else:
        fn, args, mf = build_lm_case(arch, shape_name, mesh,
                                     moe_dispatch=moe_dispatch)
    jax.set_mesh(mesh)  # context mesh: needed by shard_map-based layers
    with mesh:
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    mem = compiled.memory_analysis()
    rl = roof.analyze(f"{arch}/{shape_name}", compiled, chips, mf)
    rec = rl.to_dict()
    rec.update(
        arch=arch, shape=shape_name,
        mesh="2x16x16" if multi_pod else "16x16",
        lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
        memory_analysis=str(mem),
    )
    return rec


ALL_ARCH_NAMES = sorted(archlib.ARCHS)


def _all_cases(meshes=("single", "multi")) -> list[tuple[str, str, bool]]:
    cases = []
    for arch in ALL_ARCH_NAMES:
        for shape in shapelib.INPUT_SHAPES:
            for m in meshes:
                cases.append((arch, shape, m == "multi"))
    for shape in FCN3_SHAPES:
        for m in meshes:
            cases.append(("fcn3", shape, m == "multi"))
    return cases


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--jobs", type=int, default=2)
    ap.add_argument("--out", default="dryrun_results.jsonl")
    ap.add_argument("--reduced-fcn3", action="store_true",
                    help="use the ~1-degree FCN3 (CI-sized geometry tables)")
    ap.add_argument("--moe-dispatch", default="dense",
                    choices=("dense", "scatter"))
    ap.add_argument("--fcn3-dtype", default="float32",
                    choices=("float32", "bfloat16"))
    ap.add_argument("--fcn3-sharding", default="domain",
                    choices=("domain", "channel", "ensemble"),
                    help="domain = paper-faithful latitude decomposition; "
                         "channel = beyond-paper tensor parallelism")
    args = ap.parse_args()

    if not args.all:
        rec = run_case(args.arch, args.shape, args.multi_pod,
                       args.reduced_fcn3, fcn3_mode=args.fcn3_sharding,
                       fcn3_dtype=args.fcn3_dtype,
                       moe_dispatch=args.moe_dispatch)
        print(json.dumps(rec, indent=1))
        print("RESULT_JSON:" + json.dumps(rec))
        print(f"\nDRYRUN OK: {args.arch}/{args.shape} "
              f"mesh={rec['mesh']} bottleneck={rec['bottleneck']}")
        return

    # orchestrate subprocesses (isolation per compile)
    cases = _all_cases()
    procs: list[tuple[subprocess.Popen, tuple]] = []
    results, failures = [], []
    with open(args.out, "w") as f:
        def drain(block=False):
            for p, case in list(procs):
                if block:
                    p.wait()
                if p.poll() is None:
                    continue
                procs.remove((p, case))
                out, _ = p.communicate()
                tag = f"{case[0]}/{case[1]}/{'multi' if case[2] else 'single'}"
                if p.returncode == 0:
                    line = next(l for l in out.splitlines()
                                if l.startswith("RESULT_JSON:"))
                    rec = json.loads(line[len("RESULT_JSON:"):])
                    f.write(json.dumps(rec) + "\n")
                    f.flush()
                    results.append(tag)
                    print(f"[ok] {tag} bottleneck={rec['bottleneck']} "
                          f"compile={rec['compile_s']}s")
                else:
                    failures.append(tag)
                    print(f"[FAIL] {tag}\n{out[-2000:]}")

        for case in cases:
            while len(procs) >= args.jobs:
                drain(block=True)
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", case[0], "--shape", case[1],
                   "--moe-dispatch", args.moe_dispatch,
                   "--fcn3-sharding", args.fcn3_sharding]
            if case[2]:
                cmd.append("--multi-pod")
            if args.reduced_fcn3:
                cmd.append("--reduced-fcn3")
            procs.append((subprocess.Popen(
                cmd, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True), case))
        while procs:
            drain(block=True)
    print(f"\n{len(results)} ok, {len(failures)} failed")
    if failures:
        print("failures:", failures)
        sys.exit(1)


if __name__ == "__main__":
    main()
