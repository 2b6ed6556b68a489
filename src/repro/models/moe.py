"""Mixture-of-experts FFN with capacity-based dense dispatch.

Switch/GShard-style dispatch: top-k routing with a per-expert capacity
C = ceil(tokens * k / E * capacity_factor).  Dispatch/combine are expressed
as einsums against a (tokens, E, C) one-hot tensor, so under expert-parallel
sharding (experts -> "model" axis) XLA lowers the dispatch to the same
all-to-all pattern the paper uses for its distributed spherical transforms.

Supports shared (always-on) experts (deepseek-v2: 2 shared + 160 routed
top-6; llama4-maverick: 1 shared + 128 routed top-1) and an auxiliary
load-balance loss (Switch Transformer eq. 4).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import common as cm


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    d_ff: int                  # per expert
    n_experts: int
    top_k: int
    n_shared: int = 0
    shared_d_ff: int = 0       # defaults to d_ff * n_shared
    capacity_factor: float = 1.25
    router_jitter: float = 0.0
    # Dispatch strategy:
    #  "dense"   -- Switch-style (tokens, E, C) one-hot einsums. Simple and
    #               fine for small T (decode steps, CPU tests), but the
    #               one-hot tensors are O(T^2 k cf / E): ~2 TB each at
    #               deepseek-v2 train scale (measured; SPerf iteration).
    #  "scatter" -- sort/scatter capacity buffers built rank-locally inside
    #               shard_map (paper-style expert-parallel all-to-all);
    #               O(E C D) total. Requires ``dp_axes`` (mesh axis names
    #               the token batch is sharded over) and an ambient mesh.
    dispatch: str = "dense"
    dp_axes: tuple = ()


def init_moe(key: jax.Array, cfg: MoEConfig, dtype=jnp.float32) -> dict:
    kr, ke, ks = jax.random.split(key, 3)
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    s = float(1.0 / np.sqrt(d))
    keys = jax.random.split(ke, 3)
    p = {
        "router": cm.init_linear(kr, d, e, dtype=dtype),
        # stacked expert weights: (E, D, F) / (E, F, D)
        "w_gate": jax.random.normal(keys[0], (e, d, f), dtype) * s,
        "w_up": jax.random.normal(keys[1], (e, d, f), dtype) * s,
        "w_down": jax.random.normal(keys[2], (e, f, d), dtype) * float(1.0 / np.sqrt(f)),
    }
    if cfg.n_shared:
        sf = cfg.shared_d_ff or cfg.d_ff * cfg.n_shared
        p["shared"] = cm.init_swiglu(ks, d, sf, dtype=dtype)
    return p


def _capacity(tokens: int, cfg: MoEConfig) -> int:
    c = int(np.ceil(tokens * cfg.top_k / cfg.n_experts
                    * cfg.capacity_factor))
    return max(c, 1)


def _local_dispatch(xt: jax.Array, gate_idx: jax.Array, e: int, cap: int
                    ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Rank-local sort/scatter dispatch (single-device semantics).

    xt: (T, D); gate_idx: (T, k). Returns (buffers (E, cap, D),
    flat_e (T*k,), slot (T*k,), keep (T*k,)).
    """
    t, k = gate_idx.shape
    n = t * k
    flat_e = gate_idx.reshape(-1)
    order = jnp.argsort(flat_e)
    inv = jnp.zeros_like(order).at[order].set(jnp.arange(n))
    sorted_e = flat_e[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(e))
    pos = (jnp.arange(n) - starts[sorted_e])[inv]       # rank within expert
    keep = pos < cap
    slot = jnp.where(keep, pos, cap)                    # cap = dump slot
    xrep = jnp.repeat(xt, k, axis=0)                    # (N, D), no gather
    buf = jnp.zeros((e, cap + 1, xt.shape[1]), xt.dtype)
    buf = buf.at[flat_e, slot].add(xrep)                # unique slots => set
    return buf[:, :cap], flat_e, slot, keep


def _local_combine(h: jax.Array, flat_e: jax.Array, slot: jax.Array,
                   weight: jax.Array, k: int) -> jax.Array:
    """h: (E, cap, D) -> (T, D) using the rank-local dispatch metadata."""
    hpad = jnp.pad(h, ((0, 0), (0, 1), (0, 0)))
    y = hpad[flat_e, slot] * weight[:, None]
    return y.reshape(-1, k, h.shape[-1]).sum(axis=1)


def apply_moe_scatter(params: dict, cfg: MoEConfig, x: jax.Array
                      ) -> tuple[jax.Array, dict]:
    """Expert-parallel MoE with shard_map scatter dispatch.

    Token batch sharded over ``cfg.dp_axes``; dispatch/combine run
    rank-locally (each rank owns a capacity block), the expert FFN runs
    under GSPMD with experts sharded over the model axis -- the E <-> C
    resharding between the two is the paper-style expert all-to-all.
    """
    from jax.sharding import PartitionSpec as P

    b, s, d = x.shape
    n_tok = b * s
    e, k = cfg.n_experts, cfg.top_k
    xt = x.reshape(n_tok, d)

    logits = cm.linear(params["router"], xt).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True),
                                        1e-9)

    dp = cfg.dp_axes

    def disp(xt_l, gi_l):
        cap_l = _capacity(xt_l.shape[0], cfg)
        return _local_dispatch(xt_l, gi_l, e, cap_l)

    buf, flat_e, slot, keep = _shard_map(
        disp,
        in_specs=(P(dp, None), P(dp, None)),
        out_specs=(P(None, dp, None), P(dp), P(dp), P(dp)),
    )(xt, gate_idx)
    # buf: (E, C_total, D) with the capacity dim sharded over dp; the FFN
    # below wants experts over the model axis => GSPMD inserts the
    # expert-parallel all-to-all here.
    h = (jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, params["w_gate"]))
         * jnp.einsum("ecd,edf->ecf", buf, params["w_up"]))
    hout = jnp.einsum("ecf,efd->ecd", h, params["w_down"])

    weight = gate_vals.reshape(-1) * keep

    def comb(h_l, fe_l, sl_l, w_l):
        return _local_combine(h_l, fe_l, sl_l, w_l, k)

    y = _shard_map(
        comb,
        in_specs=(P(None, dp, None), P(dp), P(dp), P(dp)),
        out_specs=P(dp, None),
    )(hout.astype(x.dtype), flat_e, slot, weight.astype(x.dtype))

    if "shared" in params:
        y = y + cm.swiglu(params["shared"], xt)

    onehot = jax.nn.one_hot(gate_idx, e, dtype=jnp.float32)
    frac_tokens = jnp.mean(onehot.sum(1), axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    lb = e * jnp.sum(frac_tokens * frac_probs) / k
    ent = -jnp.mean(jnp.sum(probs * jnp.log(probs + 1e-9), axis=-1))
    return y.reshape(b, s, d), {"lb_loss": lb, "router_entropy": ent}


def _ambient_mesh():
    """The active (abstract) context mesh, or None outside one."""
    mesh = jax.sharding.get_abstract_mesh()
    return mesh if mesh.shape else None


def _shard_map(f, *, in_specs, out_specs):
    """shard_map against the ambient mesh."""
    return jax.shard_map(f, in_specs=in_specs, out_specs=out_specs)


def _dp_size(dp_axes) -> int:
    mesh = _ambient_mesh()
    if mesh is None or not mesh.shape:
        return 0
    n = 1
    for a in dp_axes:
        for name in (a if isinstance(a, tuple) else (a,)):
            n *= mesh.shape.get(name, 1)
    return n


def apply_moe(params: dict, cfg: MoEConfig, x: jax.Array
              ) -> tuple[jax.Array, dict]:
    """x: (B, S, D) -> (B, S, D), aux {"lb_loss", "router_entropy"}."""
    if cfg.dispatch == "scatter":
        n_dp = _dp_size(cfg.dp_axes)
        # scatter dispatch needs the token batch to tile the dp axes;
        # single-token decode steps (T < n_dp) use the dense path, whose
        # one-hot tensors are tiny at decode shapes.
        if n_dp > 1 and (x.shape[0] * x.shape[1]) % n_dp == 0 \
                and x.shape[0] % n_dp == 0:
            return apply_moe_scatter(params, cfg, x)
    b, s, d = x.shape
    n_tok = b * s
    xt = x.reshape(n_tok, d)
    e, k = cfg.n_experts, cfg.top_k
    cap = _capacity(n_tok, cfg)

    logits = cm.linear(params["router"], xt).astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)                 # (T, k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)

    # position of each (token, k) within its expert's buffer
    onehot = jax.nn.one_hot(gate_idx, e, dtype=jnp.float32)       # (T, k, E)
    pos_in_expert = (jnp.cumsum(onehot.reshape(-1, e), axis=0)
                     .reshape(n_tok, k, e) - onehot)
    pos = jnp.einsum("tke,tke->tk", pos_in_expert, onehot)        # (T, k)
    keep = pos < cap
    gates = gate_vals * keep

    # dispatch tensor (T, E, C) and combine weights
    pos_oh = jax.nn.one_hot(pos, cap, dtype=jnp.float32) * keep[..., None]
    dispatch = jnp.einsum("tke,tkc->tec", onehot, pos_oh)          # (T, E, C)
    combine = jnp.einsum("tk,tke,tkc->tec", gates, onehot, pos_oh)

    xin = jnp.einsum("tec,td->ecd", dispatch, xt)                  # (E, C, D)
    h = (jax.nn.silu(jnp.einsum("ecd,edf->ecf", xin, params["w_gate"]))
         * jnp.einsum("ecd,edf->ecf", xin, params["w_up"]))
    xout = jnp.einsum("ecf,efd->ecd", h, params["w_down"])
    y = jnp.einsum("tec,ecd->td", combine, xout).astype(x.dtype)

    if "shared" in params:
        y = y + cm.swiglu(params["shared"], xt)

    # Switch load-balance loss: E * sum_e f_e * p_e
    frac_tokens = jnp.mean(onehot.sum(1), axis=0)     # fraction routed to e
    frac_probs = jnp.mean(probs, axis=0)
    lb = e * jnp.sum(frac_tokens * frac_probs) / k
    ent = -jnp.mean(jnp.sum(probs * jnp.log(probs + 1e-9), axis=-1))
    return y.reshape(b, s, d), {"lb_loss": lb, "router_entropy": ent}
