"""Mixture-of-Experts FFN: token-choice top-k routing with sort-based
dispatch into capacity-bounded expert buffers.

Why sort-based (DESIGN.md §3, hardware adaptation): the classic one-hot
dispatch einsum materializes a [tokens, experts, capacity] tensor — at
qwen3-235b scale (65k local tokens × 128 experts × 5k capacity) that is
~10^13 elements.  Instead we:

  1. route: top-k experts per token (gates renormalized),
  2. sort (expert, token) pairs by expert id (one lax.sort),
  3. position-in-expert via a cumsum over the sorted run,
  4. scatter tokens into an [E, C, D] buffer (overflow = dropped token,
     standard capacity-factor semantics),
  5. batched per-expert FFN einsum [E,C,D]x[E,D,F] — MXU-dense,
  6. gather back and combine with gates.

The [E, C, D] buffer is the object EP shards over the ``model`` axis: tokens
are replicated across ``model`` (megatron-style activations), each model
shard scatters/computes only its local experts, and the combine's psum over
``model`` is the same all-reduce TP already pays.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


from repro.models.common import dense_init, shard


@dataclasses.dataclass(frozen=True)
class MoEDims:
    d_model: int
    n_experts: int
    top_k: int
    d_ff: int  # per-expert hidden
    capacity_factor: float = 1.25
    shared_expert: bool = False
    shared_d_ff: int = 0  # defaults to d_ff


def init_moe(key, dims: MoEDims, dtype) -> dict:
    ks = jax.random.split(key, 5)
    D, E, F = dims.d_model, dims.n_experts, dims.d_ff
    p = {
        "router": dense_init(ks[0], (D, E), jnp.float32),  # router in f32
        "w_gate": dense_init(ks[1], (E, D, F), dtype),
        "w_up": dense_init(ks[2], (E, D, F), dtype),
        "w_down": dense_init(ks[3], (E, F, D), dtype, fan_in=F),
    }
    if dims.shared_expert:
        from repro.models.mlp import init_mlp

        p["shared"] = init_mlp(ks[4], D, dims.shared_d_ff or F, dtype)
    return p


def capacity(dims: MoEDims, n_tokens: int) -> int:
    c = int(n_tokens * dims.top_k * dims.capacity_factor / dims.n_experts)
    return max(8, ((c + 7) // 8) * 8)  # 8-aligned for TPU tiling


def _dp_groups() -> int:
    from repro.models.common import _cur_mesh

    mesh = _cur_mesh()
    if mesh is None:
        return 1
    sizes = dict(mesh.shape)
    g = 1
    for a in ("pod", "data"):
        g *= sizes.get(a, 1)
    return g


def _moe_mesh():
    """Physical mesh with a model axis, if one is active (shard_map needs it)."""
    from repro.models.common import _cur_mesh

    mesh = _cur_mesh()
    if mesh is None or "model" not in mesh.axis_names or not hasattr(mesh, "devices"):
        return None
    return mesh


def _dp_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _dispatch_gather(xg_pad: jax.Array, tok_of_slot: jax.Array) -> jax.Array:
    """buf[g, s] = xg_pad[g, tok_of_slot[g, s]] with explicit locality.

    xg_pad: [G, Tl+1, D] (group-sharded, replicated over model);
    tok_of_slot: [G, E*C] (group + model sharded).  Inside shard_map every
    device gathers its local slots from its local group copy — no comm.
    """
    mesh = _moe_mesh()
    if mesh is None:
        return jnp.take_along_axis(xg_pad, tok_of_slot[..., None], axis=1)
    from jax.sharding import PartitionSpec as P

    dp = _dp_axes(mesh)

    def body(xg_l, tok_l):
        gl = tok_l.shape[0]
        idx = jnp.arange(gl)[:, None]
        return xg_l[idx, tok_l]  # [g_loc, slots_loc, D]

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(dp, None, None), P(dp, "model")),
        out_specs=P(dp, "model", None),
    )(xg_pad, tok_of_slot)


def _combine_scatter(y_flat: jax.Array, tok_of_slot: jax.Array, Tl: int) -> jax.Array:
    """out[g, t] = sum over slots s with tok[g,s]==t of y_flat[g, s].

    Inside shard_map: local scatter-add into the group accumulator, then one
    bf16 psum over `model` — the minimal EP combine.
    """
    mesh = _moe_mesh()
    G, _, D = y_flat.shape
    if mesh is None:
        gi = jnp.arange(G, dtype=jnp.int32)[:, None]
        return jnp.zeros((G, Tl + 1, D), y_flat.dtype).at[gi, tok_of_slot].add(y_flat)
    from jax.sharding import PartitionSpec as P

    dp = _dp_axes(mesh)

    def body(y_l, tok_l):
        gl = tok_l.shape[0]
        idx = jnp.arange(gl)[:, None]
        out = jnp.zeros((gl, Tl + 1, D), y_l.dtype).at[idx, tok_l].add(y_l)
        return jax.lax.psum(out, "model")

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(dp, "model", None), P(dp, "model")),
        out_specs=P(dp, None, None),
    )(y_flat, tok_of_slot)


def moe_ffn(params, dims: MoEDims, x: jax.Array) -> jax.Array:
    """x: [B, S, D] -> [B, S, D].

    Grouped (GShard-style) dispatch: tokens are split into G groups aligned
    with the (pod, data) batch shards, so routing, the [G, E, C, D] expert
    buffer, and the combine all stay group-local.  Crucially, dispatch and
    combine are *slot-side gathers/scatters* — `buf[slot] = x[token_of_slot]`
    — so no [T*K, D] pair tensor ever materializes (the naive combine
    all-reduced 137 GB per layer at qwen3 scale; EXPERIMENTS.md §Perf).
    """
    B, S, D = x.shape
    E, K = dims.n_experts, dims.top_k
    T = B * S
    if S == 1:
        return _moe_ffn_decode(params, dims, x)
    G = _dp_groups()
    if T % G != 0 or B % G != 0:
        G = 1
    Tl = T // G
    C = capacity(dims, Tl)

    xg = x.reshape(G, Tl, D)
    xg = shard(xg, ("pod", "data"), None, None)

    # 1. routing (f32)
    logits = xg.astype(jnp.float32) @ params["router"]  # [G, Tl, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, K)  # [G, Tl, K]
    gate_vals = gate_vals / jnp.maximum(jnp.sum(gate_vals, -1, keepdims=True), 1e-9)

    # 2. sort (expert, token) pairs by expert, per group.  Integer keys only
    # (lax.sort JVP is unusable in this jax/jaxlib pairing); differentiable
    # gates follow via the permutation.
    flat_e = gate_idx.reshape(G, Tl * K).astype(jnp.int32)
    flat_t = jnp.broadcast_to(
        jnp.repeat(jnp.arange(Tl, dtype=jnp.int32), K)[None], (G, Tl * K)
    )
    perm0 = jnp.broadcast_to(jnp.arange(Tl * K, dtype=jnp.int32)[None], (G, Tl * K))
    se, st, perm = jax.lax.sort((flat_e, flat_t, perm0), dimension=1, num_keys=2)
    sg = jnp.take_along_axis(gate_vals.reshape(G, Tl * K), perm, axis=1)

    # 3. position within expert run
    pos = jnp.arange(Tl * K, dtype=jnp.int32)[None]
    run_start = jax.vmap(
        lambda row: jnp.searchsorted(row, jnp.arange(E, dtype=jnp.int32), side="left")
    )(se)  # [G, E]
    gi = jnp.arange(G, dtype=jnp.int32)[:, None]
    pos_in_e = pos - run_start[gi, se]
    keep = pos_in_e < C
    slot = jnp.where(keep, se * C + pos_in_e, E * C)  # E*C = trash slot

    # 4. slot-side maps: token and gate per buffer slot (tiny int/f32 arrays)
    tok_of_slot = jnp.full((G, E * C + 1), Tl, jnp.int32).at[gi, slot].set(st)
    gate_of_slot = jnp.zeros((G, E * C + 1), jnp.float32).at[gi, slot].set(sg)
    tok_of_slot = tok_of_slot[:, : E * C]
    gate_of_slot = gate_of_slot[:, : E * C]

    # 5. dispatch = one gather (pad row Tl reads zeros).  Under a mesh this
    # runs in shard_map: xg is naturally replicated over `model`, each model
    # shard gathers its own expert slots — zero communication.  GSPMD's
    # auto-partitioned gather instead replicated the full [G, Tl, D] tensor
    # (17 GB f32/layer measured at qwen3 scale).
    xg_pad = jnp.concatenate([xg, jnp.zeros((G, 1, D), x.dtype)], axis=1)
    buf = _dispatch_gather(xg_pad, tok_of_slot)  # [G, E*C, D]
    buf = buf.reshape(G, E, C, D)
    buf = shard(buf, ("pod", "data"), "model", None, None)  # EP over model

    # 6. batched expert FFN (SwiGLU)
    g_ = jnp.einsum("gecd,edf->gecf", buf, params["w_gate"])
    u_ = jnp.einsum("gecd,edf->gecf", buf, params["w_up"])
    h = jax.nn.silu(g_) * u_
    h = shard(h, ("pod", "data"), "model", None, None)
    y = jnp.einsum("gecf,efd->gecd", h, params["w_down"])
    y = shard(y, ("pod", "data"), "model", None, None)

    # 7. combine = one gate-weighted scatter-add from the sharded buffer.
    # shard_map again: each device scatters its local expert slots into its
    # group's [Tl+1, D] accumulator, then one bf16 psum over `model` — the
    # minimal EP-combine collective.
    y_flat = (y.reshape(G, E * C, D) * gate_of_slot[..., None]).astype(x.dtype)
    out = _combine_scatter(y_flat, tok_of_slot, Tl)
    out = out[:, :Tl]
    out = shard(out, ("pod", "data"), None, None)

    if dims.shared_expert:
        from repro.models.mlp import mlp_ffn

        out = out + mlp_ffn(params["shared"], xg)

    return out.reshape(B, S, D)


def _moe_ffn_decode(params, dims: MoEDims, x: jax.Array) -> jax.Array:
    """Decode-mode MoE (S==1): single group, D-sharded residual convention.

    Buffers are token-count-sized (tiny), so plain gathers/scatters suffice;
    what matters is the expert einsum contracting D over `data` in place —
    the GSPMD default gathered 4.8 GB of expert weights per layer per token
    (EXPERIMENTS.md §Perf iteration B2).
    """
    B, S, D = x.shape
    E, K = dims.n_experts, dims.top_k
    T = B * S
    C = capacity(dims, T)
    xf = shard(x.reshape(T, D), None, ("data",))

    logits = xf.astype(jnp.float32) @ params["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, K)
    gate_vals = gate_vals / jnp.maximum(jnp.sum(gate_vals, -1, keepdims=True), 1e-9)

    flat_e = gate_idx.reshape(-1).astype(jnp.int32)
    flat_t = jnp.repeat(jnp.arange(T, dtype=jnp.int32), K)
    perm0 = jnp.arange(T * K, dtype=jnp.int32)
    se, st, perm = jax.lax.sort((flat_e, flat_t, perm0), dimension=0, num_keys=2)
    sg = gate_vals.reshape(-1)[perm]
    pos = jnp.arange(T * K, dtype=jnp.int32)
    run_start = jnp.searchsorted(se, jnp.arange(E, dtype=jnp.int32), side="left")
    pos_in_e = pos - run_start[se]
    keep = pos_in_e < C
    slot = jnp.where(keep, se * C + pos_in_e, E * C)

    tok_of_slot = jnp.full((E * C + 1,), T, jnp.int32).at[slot].set(st)[: E * C]
    gate_of_slot = jnp.zeros((E * C + 1,), jnp.float32).at[slot].set(sg)[: E * C]
    xf_pad = jnp.concatenate([xf, jnp.zeros((1, D), x.dtype)], axis=0)
    buf = xf_pad[tok_of_slot].reshape(E, C, D)
    buf = shard(buf, "model", None, ("data",))  # EP over model, D over data

    g_ = jnp.einsum("ecd,edf->ecf", buf, params["w_gate"])
    u_ = jnp.einsum("ecd,edf->ecf", buf, params["w_up"])
    h = jax.nn.silu(g_) * u_
    h = shard(h, "model", None, None)
    y = jnp.einsum("ecf,efd->ecd", h, params["w_down"])
    y = shard(y, "model", None, ("data",))

    y_flat = (y.reshape(E * C, D) * gate_of_slot[:, None]).astype(x.dtype)
    out = jnp.zeros((T + 1, D), x.dtype).at[tok_of_slot].add(y_flat)[:T]
    out = shard(out, None, ("data",))

    if dims.shared_expert:
        from repro.models.mlp import mlp_ffn

        out = out + mlp_ffn(params["shared"], xf[None]).reshape(T, D)

    return out.reshape(B, S, D)


def aux_load_balance_loss(params, dims: MoEDims, x: jax.Array) -> jax.Array:
    """Switch-style auxiliary loss: E * sum_e f_e * p_e."""
    B, S, D = x.shape
    xf = x.reshape(-1, D).astype(jnp.float32)
    probs = jax.nn.softmax(xf @ params["router"], axis=-1)
    top1 = jnp.argmax(probs, axis=-1)
    f = jnp.mean(jax.nn.one_hot(top1, dims.n_experts, dtype=jnp.float32), axis=0)
    p = jnp.mean(probs, axis=0)
    return dims.n_experts * jnp.sum(f * p)
