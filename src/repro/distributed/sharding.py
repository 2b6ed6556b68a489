"""PartitionSpec rules for the production mesh (paper §G -> GSPMD).

Axis roles on the assignment-mandated mesh
``("data", "model")`` / ``("pod", "data", "model")``:

* ``pod`` + ``data`` -- pure data parallelism (batch x ensemble in FCN3
  terms), plus FSDP-style weight sharding for the large LMs (beyond-paper:
  the paper replicates weights across data ranks; ZeRO-sharding them is one
  of our §Perf levers and is on by default for the LM zoo).
* ``model`` -- the paper's *domain decomposition* axis: latitude for FCN3,
  sequence/experts/heads for the assigned LM architectures (see DESIGN.md
  §5 for the per-family mapping).

Rules are name/shape-pattern based and return specs for the *trailing*
dimensions of each leaf; leading scan-stack dimensions are padded with
``None`` automatically, so the same rule covers stacked and unstacked
layouts.
"""

from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.models.transformer import ArchConfig

DP = "data"     # FSDP / batch axis (pod handled by the caller)
MP = "model"    # tensor/expert/sequence-parallel axis


def _pad(spec: tuple, ndim: int) -> P:
    pad = ndim - len(spec)
    return P(*([None] * pad + list(spec)))


def sanitize_specs(mesh, spec_tree: Any, struct_tree: Any) -> Any:
    """Drop sharding entries whose mesh-axis product does not divide the
    corresponding dimension (jit in_shardings requires exact divisibility;
    e.g. whisper's vocab 51865 cannot shard 16 ways)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def div(entry) -> int:
        if entry is None:
            return 1
        names = entry if isinstance(entry, tuple) else (entry,)
        n = 1
        for a in names:
            n *= sizes[a]
        return n

    def fix(spec: P, leaf) -> P:
        entries = list(spec) + [None] * (leaf.ndim - len(spec))
        out = [e if leaf.shape[i] % div(e) == 0 else None
               for i, e in enumerate(entries)]
        return P(*out)

    return jax.tree.map(fix, spec_tree, struct_tree,
                        is_leaf=lambda x: isinstance(x, P))


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def lm_param_specs(cfg: ArchConfig, params_struct: Any,
                   data_axis=DP, model_axis=MP) -> Any:
    """PartitionSpec pytree for LM parameters.

    2-D projection weights: (in, out) -> (FSDP over data, TP over model) for
    up-projections and the transpose for down-projections; 3-D MoE expert
    stacks: experts over the model axis (expert parallelism -> all-to-all
    dispatch), plus FSDP on the feature dim.
    """
    n_exp = cfg.moe.n_experts if cfg.moe else -1

    def spec_for(path, leaf) -> P:
        name = _path_str(path).split("/")[-1]
        nd = leaf.ndim
        shape = leaf.shape
        # MoE expert stacks (possibly scan-stacked): (..., E, D, F)/(.., E, F, D)
        if name in ("w_gate", "w_up", "w_down") and nd >= 3 \
                and n_exp in shape[-3:-2]:
            if name == "w_down":
                return _pad((model_axis, None, data_axis), nd)
            return _pad((model_axis, data_axis, None), nd)
        if name in ("wq", "wk", "wv", "w_uq", "w_uk", "w_uv", "w_dkv",
                    "w_dq", "w_gate", "w_up", "in_proj", "w1"):
            return _pad((data_axis, model_axis), nd)
        if name in ("wo", "w_down", "out_proj", "w2"):
            return _pad((model_axis, data_axis), nd)
        if name in ("embed", "lm_head"):
            return _pad((None, model_axis), nd)
        if name == "conv_w":
            return _pad((None, model_axis), nd)
        return _pad((), nd)  # norms, biases, scalars: replicated

    return jax.tree_util.tree_map_with_path(spec_for, params_struct)


def lm_opt_specs(param_specs: Any) -> dict:
    """Adam state mirrors the parameter sharding."""
    return {
        "step": P(),
        "mu": param_specs,
        "nu": param_specs,
    }


def lm_batch_specs(batch_struct: Any, dp_axes: tuple[str, ...],
                   model_axis=MP) -> Any:
    """Training batch: shard the global batch over all data axes."""
    def spec_for(path, leaf) -> P:
        return _pad((dp_axes,) if leaf.ndim else (), leaf.ndim)

    return jax.tree_util.tree_map_with_path(spec_for, batch_struct)


def lm_cache_specs(cache_struct: Any, dp_axes: tuple[str, ...],
                   batch: int, model_axis=MP) -> Any:
    """Decode caches.

    KV/latent caches: batch over the data axes when it divides, sequence
    over the model axis (the paper's domain decomposition applied to the
    cache); SSM states: heads/state dims over the model axis.
    """
    def spec_for(path, leaf) -> P:
        name = _path_str(path).split("/")[-1]
        nd = leaf.ndim
        if name in ("k", "v"):           # (..., B, S, H, D)
            return _pad((dp_axes, model_axis, None, None), nd)
        if name in ("c_kv", "k_rope"):   # (..., B, S, R)
            return _pad((dp_axes, model_axis, None), nd)
        if name == "ssm":                # (..., B, H, P, N)
            return _pad((dp_axes, None, None, model_axis), nd)
        if name == "conv":               # (..., B, K-1, C)
            return _pad((dp_axes, None, model_axis), nd)
        return _pad((), nd)

    return jax.tree_util.tree_map_with_path(spec_for, cache_struct)


# ---------------------------------------------------------------------------
# FCN3 (paper-faithful domain decomposition)
# ---------------------------------------------------------------------------

def fcn3_param_specs(params_struct: Any, data_axis=DP, model_axis=MP,
                     fsdp: bool = False, mode: str = "domain") -> Any:
    """FCN3 weights.

    mode="domain" (paper-faithful): weights *replicated* across the spatial
    (model) axis -- the domain decomposition shards data, not weights
    (paper G.2); gradients are psum-reduced over data axes by GSPMD.

    mode="channel" (beyond-paper, SPerf iteration 1): tensor parallelism on
    the latent-channel dimension instead of latitude. The paper mentions
    this "matmul mode" as supported-but-unused (G.1); under GSPMD it is the
    *better* mapping for the mandated 1-D model axis because every spatial
    op (DISCO band gather, FFT, Legendre GEMM, bilinear interp) stays
    rank-local and only channel contractions communicate. Conv weights
    (C_out, C_in/g, K) shard C_out; MLP w1 (hidden, c) shards hidden, w2
    (c, hidden) contracts it; LayerScale shards its channel vector.

    ``fsdp=True`` additionally shards remaining big leaves over data
    (ZeRO-style).
    """
    def spec_for(path, leaf) -> P:
        name = _path_str(path).split("/")[-1]
        parent = _path_str(path)
        if mode == "channel":
            if name == "weight" and "blocks" in parent and leaf.ndim >= 3:
                # block DISCO conv (C_out, C_in, K): out-channel parallel
                return _pad((model_axis, None, None), leaf.ndim)
            if name in ("w_re", "w_im"):
                # spectral filter (C_out, C_in, L)
                return _pad((model_axis, None, None), leaf.ndim)
            if name == "w1":
                return _pad((model_axis, None), leaf.ndim)
            if name == "b1":
                return _pad((model_axis,), leaf.ndim)
            if name == "w2":
                return _pad((None, model_axis), leaf.ndim)
        if fsdp and leaf.ndim >= 2:
            return _pad((data_axis,) + (None,) * (leaf.ndim - 1), leaf.ndim)
        return _pad((), leaf.ndim)

    return jax.tree_util.tree_map_with_path(spec_for, params_struct)


def fcn3_buffer_specs(buffers_struct: Any, model_axis=MP) -> Any:
    """Geometry buffers: shard along latitude-like dims.

    psi: (K, H_out, S, W) -> H_out over model; the order-major Legendre
    tables stay replicated.
    """
    def spec_for(path, leaf) -> P:
        name = _path_str(path).split("/")[-1]
        nd = leaf.ndim
        if name == "psi":
            return _pad((None, model_axis, None, None), nd)
        if name == "psi_band":
            # banded pallas layout: same H_out sharding as the full psi;
            # the small near-pole psi_wrap/wrap_* buffers stay replicated
            # (every shard may need any wrap row after the scatter).
            return _pad((None, model_axis, None, None), nd)
        if name == "lat_idx":
            return _pad((model_axis, None), nd)
        if name in ("wpct", "pct"):
            return _pad((None, None, None), nd)  # replicated tables
        return _pad((), nd)

    return jax.tree_util.tree_map_with_path(spec_for, buffers_struct)


def fcn3_batch_specs(batch_struct: Any, dp_axes: tuple[str, ...],
                     model_axis=MP, mode: str = "domain") -> Any:
    """FCN3 batches: batch over data axes; latitude over the model axis in
    "domain" mode (paper Fig. 2), unsharded in "channel" mode (the model
    axis then carries latent channels instead)."""
    def spec_for(path, leaf) -> P:
        nd = leaf.ndim
        if nd < 3:
            return _pad((), nd)
        lat = model_axis if mode == "domain" else None
        return _pad((dp_axes,) + (None,) * (nd - 3) + (lat, None), nd)

    return jax.tree_util.tree_map_with_path(spec_for, batch_struct)
