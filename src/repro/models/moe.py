"""Mixture-of-Experts FFN with expert parallelism.

Dispatch strategy (DESIGN.md §5): experts are sharded over the "model"
mesh axis. Inside a shard_map, every model-shard sees the *same* local
tokens (activations are sharded over "data" only), routes them, gathers
the ones destined for its local expert slice into a fixed-capacity
buffer, runs the expert FFNs as one batched einsum, scatters results
back, and a single psum over "model" combines expert contributions.
Communication per MoE layer = one psum of the (tokens, d_model) output —
no all_to_all, no (T, E, C) GShard dispatch tensor.

Fixed capacity C = ceil(T_local * top_k / E * capacity_factor); overflow
tokens are dropped (standard dropping MoE; the router aux loss keeps load
balanced). Experts are zero-padded to a multiple of the model-axis size
when E doesn't divide (granite: 40 -> 48); padded experts get -inf router
logits so they never receive tokens.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map

from repro.models.layers import dense_init, mlp_apply, mlp_init

__all__ = ["MeshContext", "moe_init", "moe_apply", "padded_num_experts"]


@dataclasses.dataclass(frozen=True)
class MeshContext:
    """Mesh + axis-name conventions threaded through model apply fns."""

    mesh: object  # jax.sharding.Mesh
    dp_axes: Tuple[str, ...] = ("data",)  # batch axes ("pod","data") multi-pod
    model_axis: Optional[str] = "model"
    # FSDP axes the expert weights are sharded over (empty = no FSDP).
    # Expert all-gathers happen *inside* the shard_map body, one layer at
    # a time — declaring them gathered in in_specs makes GSPMD hoist the
    # all-gather of the whole stacked scan bank out of the loop (measured:
    # 127 GB/device peak on kimi-k2; see EXPERIMENTS.md §Dry-run).
    fsdp_axes: Tuple[str, ...] = ()

    @property
    def model_size(self) -> int:
        if self.model_axis is None:
            return 1
        return self.mesh.shape[self.model_axis]

    def constrain_heads(self, t):
        """Pin (B, S, H, D) attention activations to batch-over-dp +
        heads-over-model. Needed when the head axis only becomes
        divisible after zero-padding inside _sdpa — GSPMD won't re-shard
        a dim it already decided to replicate (measured: musicgen's 24
        unsharded heads cost 16x score traffic; EXPERIMENTS.md §Perf)."""
        if self.mesh is None or self.model_axis is None:
            return t
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        dp_total = 1
        for ax in self.dp_axes:
            dp_total *= self.mesh.shape[ax]
        dims = [None] * t.ndim
        if t.shape[0] % dp_total == 0:
            dims[0] = tuple(self.dp_axes)
        if t.shape[2] % self.model_size == 0:
            dims[2] = self.model_axis
        return jax.lax.with_sharding_constraint(
            t, NamedSharding(self.mesh, P(*dims))
        )

    def constrain_hidden(self, x):
        """Pin activation sharding to (batch over dp, rest replicated).

        Without this, GSPMD happily propagates weight shardings into the
        residual stream (measured: the embedding table's d-over-data
        spec turned the whole attention stack data-replicated — 16x
        compute; EXPERIMENTS.md §Dry-run). Applied at the embedding
        output and at each scan-step entry."""
        if self.mesh is None:
            return x
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        dp_total = 1
        for ax in self.dp_axes:
            dp_total *= self.mesh.shape[ax]
        if x.shape[0] % dp_total != 0:
            return x
        dims = [tuple(self.dp_axes)] + [None] * (x.ndim - 1)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P(*dims))
        )


def padded_num_experts(num_experts: int, mesh_ctx: Optional[MeshContext]):
    m = mesh_ctx.model_size if mesh_ctx is not None else 1
    return ((num_experts + m - 1) // m) * m


def moe_init(key, cfg, mesh_ctx: Optional[MeshContext] = None):
    """One MoE FFN layer: router + padded expert bank (+ shared experts)."""
    m = cfg.moe
    e_pad = padded_num_experts(m.num_experts, mesh_ctx)
    ks = jax.random.split(key, 5)
    d, f = cfg.d_model, m.d_expert
    p = {
        "router": dense_init(ks[0], (d, e_pad)),
        "w_up": dense_init(ks[1], (e_pad, d, f)),
        "w_gate": dense_init(ks[2], (e_pad, d, f)),
        "w_down": dense_init(ks[3], (e_pad, f, d), fan_in=f),
    }
    if m.num_shared_experts:
        p["shared"] = mlp_init(
            ks[4], d, f * m.num_shared_experts, cfg.mlp_act
        )
    return p


def _expert_ffn(p_loc, xb: jnp.ndarray, act: str) -> jnp.ndarray:
    """xb (E_loc, C, d) -> (E_loc, C, d), batched over local experts.
    Weights may be int8-quantized {"q","s"} dicts (serving)."""
    from repro.models.moe_quant import dequant_weight

    dt = xb.dtype
    up = jnp.einsum("ecd,edf->ecf", xb, dequant_weight(p_loc["w_up"], dt))
    gate = jnp.einsum(
        "ecd,edf->ecf", xb, dequant_weight(p_loc["w_gate"], dt)
    )
    h = jax.nn.silu(gate) * up
    return jnp.einsum(
        "ecf,efd->ecd", h, dequant_weight(p_loc["w_down"], dt)
    )


def _route_and_compute(
    x: jnp.ndarray,  # (T, d) local tokens
    p_loc,  # expert slice params: w_up (E_loc, d, f), router (d, E_pad) full
    e_start: jnp.ndarray,  # scalar: first expert id of this shard
    *,
    num_experts: int,  # real (unpadded) expert count
    e_pad: int,
    top_k: int,
    capacity: int,
    act: str,
    ffn_fn=None,  # override expert FFN (weights-stationary path)
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (y (T, d): this shard's expert contributions, aux loss)."""
    t, d = x.shape
    _wu = (
        p_loc["w_up"]["q"] if isinstance(p_loc["w_up"], dict)
        else p_loc["w_up"]
    )
    e_loc = _wu.shape[0]
    logits = (x.astype(jnp.float32) @ p_loc["router"].astype(jnp.float32))
    # mask padded experts out of routing
    pad_mask = jnp.arange(e_pad) < num_experts
    logits = jnp.where(pad_mask[None, :], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)  # (T, E_pad)
    gates, idx = jax.lax.top_k(probs, top_k)  # (T, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    # aux load-balance loss (Switch-style): E * sum(load * importance)
    importance = probs.mean(axis=0)  # (E_pad,)
    load = jnp.zeros((e_pad,), jnp.float32).at[idx.reshape(-1)].add(1.0) / (
        t * top_k
    )
    aux = num_experts * jnp.sum(importance * load)

    # ---- dispatch to this shard's local experts ----
    # All bookkeeping stays in (T*k,) index space; activations only ever
    # materialize at (T, d) and (E_loc*C, d) — never (T*k, d) — so the 1T
    # MoE's dispatch fits HBM (DESIGN.md §6).
    flat_e = idx.reshape(-1)  # (T*k,)
    flat_g = gates.reshape(-1).astype(x.dtype)
    flat_tok = jnp.repeat(jnp.arange(t), top_k)
    local = (flat_e >= e_start) & (flat_e < e_start + e_loc)
    e_rel = jnp.where(local, flat_e - e_start, e_loc)  # e_loc = trash row
    onehot = jax.nn.one_hot(e_rel, e_loc, dtype=jnp.int32)  # (T*k, E_loc)
    pos = jnp.cumsum(onehot, axis=0) - onehot  # exclusive rank per expert
    pos_in_e = jnp.sum(pos * onehot, axis=-1)  # (T*k,)
    keep = local & (pos_in_e < capacity)
    n_slots = e_loc * capacity
    slot = jnp.where(keep, e_rel * capacity + pos_in_e, n_slots)
    # invert slot -> token (each real slot receives at most one token)
    tok_for_slot = jnp.zeros((n_slots + 1,), jnp.int32).at[slot].max(
        flat_tok.astype(jnp.int32)
    )[:-1]
    gate_for_slot = jnp.zeros((n_slots + 1,), x.dtype).at[slot].max(
        jnp.where(keep, flat_g, 0)
    )[:-1]
    valid_slot = (
        jnp.zeros((n_slots + 1,), jnp.int32).at[slot].max(keep.astype(jnp.int32))
    )[:-1]
    buf = x[tok_for_slot] * valid_slot[:, None].astype(x.dtype)
    if ffn_fn is None:
        h = _expert_ffn(p_loc, buf.reshape(e_loc, capacity, d), act)
    else:
        h = ffn_fn(buf.reshape(e_loc, capacity, d))
    h_flat = h.reshape(n_slots, d)
    contrib = h_flat * (gate_for_slot * valid_slot.astype(x.dtype))[:, None]
    y = jnp.zeros((t, d), x.dtype).at[tok_for_slot].add(contrib)
    return y, aux


def moe_apply(
    p,
    x: jnp.ndarray,  # (B, S, d)
    cfg,
    mesh_ctx: Optional[MeshContext] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """MoE FFN layer. Returns (y, aux_loss)."""
    m = cfg.moe
    b, s, d = x.shape
    _wu = p["w_up"]["q"] if isinstance(p["w_up"], dict) else p["w_up"]
    e_pad = _wu.shape[0]

    if mesh_ctx is None or mesh_ctx.model_axis is None:
        t = b * s
        capacity = max(int(t * m.top_k / m.num_experts * m.capacity_factor), 4)
        y, aux = _route_and_compute(
            x.reshape(t, d),
            p,
            jnp.int32(0),
            num_experts=m.num_experts,
            e_pad=e_pad,
            top_k=m.top_k,
            capacity=capacity,
            act=cfg.mlp_act,
        )
        y = y.reshape(b, s, d)
    else:
        mc = mesh_ctx
        n_model = mc.model_size
        e_loc = e_pad // n_model
        dp_total = 1
        for ax in mc.dp_axes:
            dp_total *= mc.mesh.shape[ax]
        t_loc = max(b // dp_total, 1) * s
        capacity = max(
            int(t_loc * m.top_k / m.num_experts * m.capacity_factor), 4
        )

        fsdp = tuple(mc.fsdp_axes)
        # weights-stationary EP for small token counts (decode): moving
        # 2 TB of gathered expert weights to 1-token batches is what made
        # kimi decode collective-bound (4.9 s/step wire time, §Perf);
        # instead the TOKENS move (all-gather, ~MBs) and the expert
        # weights never leave their shards.
        stationary = bool(fsdp) and (
            t_loc * m.top_k <= getattr(m, "stationary_threshold", 4096)
        )

        def shard_fn(x_loc, router, w_up, w_gate, w_down):
            e_start = jax.lax.axis_index(mc.model_axis) * e_loc
            bb, ss, dd = x_loc.shape

            if not stationary:
                def gather_w(w, axis):
                    # int8 dicts: gather q along the sharded axis; the
                    # per-row scale only travels when its axis is sharded
                    if isinstance(w, dict):
                        out = {"q": jax.lax.all_gather(
                            w["q"], fsdp, axis=axis, tiled=True)}
                        out["s"] = (
                            jax.lax.all_gather(
                                w["s"], fsdp, axis=axis, tiled=True)
                            if axis != w["q"].ndim - 1 else w["s"]
                        )
                        return out
                    return jax.lax.all_gather(w, fsdp, axis=axis, tiled=True)

                if fsdp:
                    # gather this layer's expert shards over the FSDP
                    # axes (bwd becomes a reduce-scatter of expert grads)
                    w_up = gather_w(w_up, 1)
                    w_gate = gather_w(w_gate, 1)
                    w_down = gather_w(w_down, 2)
                p_loc = {
                    "router": router, "w_up": w_up,
                    "w_gate": w_gate, "w_down": w_down,
                }
                y, aux = _route_and_compute(
                    x_loc.reshape(bb * ss, dd),
                    p_loc,
                    e_start,
                    num_experts=m.num_experts,
                    e_pad=e_pad,
                    top_k=m.top_k,
                    capacity=capacity,
                    act=cfg.mlp_act,
                )
                y = jax.lax.psum(y, mc.model_axis)
                # router logits are identical across the model axis, so
                # aux is too; average over data (different tokens/shard)
                aux = jax.lax.pmean(aux, mc.dp_axes)
                return y.reshape(bb, ss, dd), aux

            # ---- stationary path ----
            dp = tuple(mc.dp_axes)
            dp_n = 1
            for ax in dp:
                dp_n *= mc.mesh.shape[ax]
            x_all = jax.lax.all_gather(
                x_loc.reshape(bb * ss, dd), dp, axis=0, tiled=True
            )  # (T_all, d)
            t_all = x_all.shape[0]
            cap_all = max(
                int(t_all * m.top_k / m.num_experts * m.capacity_factor), 4
            )
            d_shard = (
                w_up["q"].shape[1] if isinstance(w_up, dict)
                else w_up.shape[1]
            )
            fsdp_idx = jnp.int32(0)
            for ax in fsdp:
                fsdp_idx = fsdp_idx * mc.mesh.shape[ax] + jax.lax.axis_index(ax)

            def ffn_stationary(buf):  # (E_loc, C, d) full-d dispatch buffer
                from repro.models.moe_quant import dequant_weight

                buf_sl = jax.lax.dynamic_slice_in_dim(
                    buf, fsdp_idx * d_shard, d_shard, axis=2
                )
                up = jnp.einsum(
                    "ecd,edf->ecf", buf_sl, dequant_weight(w_up, buf.dtype)
                )
                gate = jnp.einsum(
                    "ecd,edf->ecf", buf_sl,
                    dequant_weight(w_gate, buf.dtype),
                )
                up = jax.lax.psum(up, fsdp)
                gate = jax.lax.psum(gate, fsdp)
                h = jax.nn.silu(gate) * up
                y_sl = jnp.einsum(
                    "ecf,efd->ecd", h, dequant_weight(w_down, buf.dtype)
                )  # (E_loc, C, d/F)
                return jax.lax.all_gather(y_sl, fsdp, axis=2, tiled=True)

            y_all, aux = _route_and_compute(
                x_all,
                {"router": router, "w_up": w_up, "w_gate": w_gate,
                 "w_down": w_down},
                e_start,
                num_experts=m.num_experts,
                e_pad=e_pad,
                top_k=m.top_k,
                capacity=cap_all,
                act=cfg.mlp_act,
                ffn_fn=ffn_stationary,
            )
            y_all = jax.lax.psum(y_all, mc.model_axis)
            # aux is numerically identical across data shards (computed
            # from the gathered token set); pmean proves replication to
            # shard_map's checker
            aux = jax.lax.pmean(aux, dp)
            dp_idx = jnp.int32(0)
            for ax in dp:
                dp_idx = dp_idx * mc.mesh.shape[ax] + jax.lax.axis_index(ax)
            y = jax.lax.dynamic_slice_in_dim(
                y_all, dp_idx * bb * ss, bb * ss, axis=0
            )
            return y.reshape(bb, ss, dd), aux

        bspec = tuple(mc.dp_axes)
        fspec = (fsdp if len(fsdp) > 1 else fsdp[0]) if fsdp else None

        def wspec(w, base):
            """in_spec for a weight that may be an int8 {"q","s"} dict:
            q inherits the base spec; the per-row scale (last dim 1)
            drops the last entry."""
            if isinstance(w, dict):
                return {"q": base, "s": P(*(list(base)[:-1] + [None]))}
            return base

        y, aux = shard_map(
            shard_fn,
            mesh=mc.mesh,
            in_specs=(
                P(bspec, None, None),
                P(None, None),  # router replicated
                wspec(p["w_up"], P(mc.model_axis, fspec, None)),
                wspec(p["w_gate"], P(mc.model_axis, fspec, None)),
                wspec(p["w_down"], P(mc.model_axis, None, fspec)),
            ),
            out_specs=(P(bspec, None, None), P()),
        )(x, p["router"], p["w_up"], p["w_gate"], p["w_down"])

    if "shared" in p:
        y = y + mlp_apply(p["shared"], x, cfg.mlp_act)
    return y, aux
