"""Mixture-of-Experts FFN with real expert parallelism.

Three execution strategies over the same weights:

  * "local"      — single device (smoke tests / reduced configs): tokens are
    packed into per-expert capacity buckets and computed with one batched
    einsum per projection (activated-FLOPs only, up to capacity padding — no
    dense all-experts compute);
  * "a2a"        — training / prefill on a mesh: tokens sharded over
    (data x model), experts sharded over "model" (contiguous blocks of
    E_loc = E / M experts per shard).  Top-k pairs are packed into fixed
    capacity-C send buffers, exchanged with `jax.lax.all_to_all` over
    "model", bucket-packed and computed with batched einsums at the owning
    shard, and returned by the inverse all_to_all.  Over-capacity pairs are
    dropped (capacity_factor);
  * "replicated" — decode: a handful of tokens is replicated over "model",
    each shard computes only its local experts' contributions and a psum over
    "model" combines them (weights stay put — the right trade at tiny T).

Expert weights are stored (E, d, f) sharded ("ep", "fsdp", None): expert axis
over "model", d over "data" (FSDP); the a2a path all-gathers the local
experts' d axis per layer, and shard_map's transpose turns that into a
reduce-scatter of the gradients.

Router runs in float32; load-balance aux loss is the switch-style
E * sum_e(frac_e * prob_e).
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.common import dense_init, logical_to_physical


def init_moe(key, cfg: ModelConfig, dtype) -> Tuple[Dict, Dict]:
    d, E, f = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    ks = jax.random.split(key, 7)
    p, s = {}, {}
    p["router"], s["router"] = dense_init(ks[0], (d, E), (None, None), jnp.float32)
    p["w_gate"], s["w_gate"] = dense_init(ks[1], (E, d, f), ("ep", "fsdp", None), dtype)
    p["w_up"], s["w_up"] = dense_init(ks[2], (E, d, f), ("ep", "fsdp", None), dtype)
    p["w_down"], s["w_down"] = dense_init(ks[3], (E, f, d), ("ep", None, "fsdp"), dtype)
    if cfg.n_shared_experts:
        fs = cfg.moe_d_ff * cfg.n_shared_experts
        p["ws_gate"], s["ws_gate"] = dense_init(ks[4], (d, fs), ("fsdp", "tp"), dtype)
        p["ws_up"], s["ws_up"] = dense_init(ks[5], (d, fs), ("fsdp", "tp"), dtype)
        p["ws_down"], s["ws_down"] = dense_init(ks[6], (fs, d), ("tp", "fsdp"), dtype)
    return p, s


def _route(router_w, cfg: ModelConfig, x):
    """x (T, d) -> (ids (T, k), weights (T, k) f32, aux_loss scalar)."""
    logits = x.astype(jnp.float32) @ router_w
    probs = jax.nn.softmax(logits, axis=-1)
    weights, ids = jax.lax.top_k(probs, cfg.top_k)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    # switch-style load balance: E * sum_e frac_tokens_e * mean_prob_e
    E = cfg.n_experts
    frac = jnp.mean(jax.nn.one_hot(ids, E, dtype=jnp.float32), axis=(0, 1))
    prob = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac * prob)
    return ids, weights, aux


def _bucketize(rows, eids, n_buckets: int, cap: int):
    """Pack rows into per-expert capacity buckets (GShard/Switch style).

    rows (P, d); eids (P,) in [0, n_buckets).  Returns
    (buf (n_buckets, cap, d), src (n_buckets, cap) int32, -1 = empty slot).
    Rows beyond an expert's capacity are dropped.

    NOTE: jax.lax.ragged_dot would express this without padding, but its XLA
    lowering on non-TPU backends expands to a DENSE (E, P, d) masked compute —
    catastrophic for both memory and counted FLOPs.  Fixed-capacity buckets
    feed a plain batched einsum, which is also what the MXU prefers.
    """
    P, d = rows.shape
    oh = (eids[:, None] == jnp.arange(n_buckets)[None, :]).astype(jnp.int32)
    pos = jnp.sum((jnp.cumsum(oh, axis=0) - 1) * oh, axis=1)
    slot = jnp.where(pos < cap, pos, cap)                  # cap = trash slot
    src = jnp.arange(P, dtype=jnp.int32)
    buf = jnp.zeros((n_buckets, cap + 1, d), rows.dtype).at[eids, slot].set(rows)
    srcb = jnp.full((n_buckets, cap + 1), -1, jnp.int32).at[eids, slot].set(src)
    return buf[:, :cap], srcb[:, :cap]


def _unbucketize(ybuf, src, P: int):
    """Inverse of _bucketize: scatter (E, cap, d) back to (P, d) rows."""
    d = ybuf.shape[-1]
    src_flat = src.reshape(-1)
    vals = jnp.where((src_flat >= 0)[:, None], ybuf.reshape(-1, d), 0.0)
    return jnp.zeros((P, d), ybuf.dtype).at[jnp.maximum(src_flat, 0)].add(vals)


def _expert_mlp_bucketed(buf, w_gate, w_up, w_down, act):
    """buf (E, cap, d) x (E, d, f) -> (E, cap, d): batched expert MLP."""
    g = jnp.einsum("ecd,edf->ecf", buf, w_gate)
    u = jnp.einsum("ecd,edf->ecf", buf, w_up)
    h = (act(g.astype(jnp.float32)) * u.astype(jnp.float32)).astype(buf.dtype)
    return jnp.einsum("ecf,efd->ecd", h, w_down)


def _capacity(expected: float, cf: float, floor: int = 8) -> int:
    return max(floor, -(-int(expected * cf)) // 8 * 8 + 8)


def moe_ffn_local(params, cfg: ModelConfig, x, act) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Single-device routed FFN.  x (T, d) -> (out (T, d), aux)."""
    T, d = x.shape
    k, E = cfg.top_k, cfg.n_experts
    ids, weights, aux = _route(params["router"], cfg, x)
    flat_ids = ids.reshape(-1)                              # (T*k,)
    cap = _capacity(T * k / E, cfg.capacity_factor)
    buf, src = _bucketize(x[jnp.arange(T * k) // k], flat_ids, E, cap)
    ybuf = _expert_mlp_bucketed(buf, params["w_gate"], params["w_up"],
                                params["w_down"], act)
    ys = _unbucketize(ybuf, src, T * k)                    # (T*k, d)
    w_flat = weights.reshape(-1).astype(ys.dtype)
    out = jnp.zeros((T, d), ys.dtype).at[jnp.arange(T * k) // k].add(
        ys * w_flat[:, None])
    return out.astype(x.dtype), aux


def _pack_send(x, ids, cfg: ModelConfig, M: int, C: int):
    """Pack top-k pairs into per-destination-shard capacity buffers.

    Returns send_x (M, C, d), send_eloc (M, C) i32, send_src (M, C) i32
    (-1 = empty slot), with over-capacity pairs dropped into a trash slot.
    """
    T, d = x.shape
    k = cfg.top_k
    E_loc = cfg.n_experts // M
    flat_ids = ids.reshape(-1)                              # (P,) P = T*k
    dst = flat_ids // E_loc
    eloc = flat_ids - dst * E_loc
    oh = (dst[:, None] == jnp.arange(M)[None, :]).astype(jnp.int32)
    pos = jnp.sum((jnp.cumsum(oh, axis=0) - 1) * oh, axis=1)
    slot = jnp.where(pos < C, pos, C)                       # C = trash slot
    src = jnp.arange(T * k, dtype=jnp.int32)
    send_x = jnp.zeros((M, C + 1, d), x.dtype).at[dst, slot].set(x[src // k])
    send_eloc = jnp.zeros((M, C + 1), jnp.int32).at[dst, slot].set(eloc)
    send_src = jnp.full((M, C + 1), -1, jnp.int32).at[dst, slot].set(src)
    return send_x[:, :C], send_eloc[:, :C], send_src[:, :C]


def _moe_a2a_block(x, router_w, w_gate, w_up, w_down, *, cfg: ModelConfig,
                   M: int, C: int, act, fsdp_axis: str, all_axes: tuple):
    """Per-device body of the a2a strategy (runs inside shard_map)."""
    T, d = x.shape
    k = cfg.top_k
    E_loc = cfg.n_experts // M
    # FSDP all-gather of this shard's expert weights (transposes to
    # reduce-scatter of the gradient)
    w_gate = jax.lax.all_gather(w_gate, fsdp_axis, axis=1, tiled=True)
    w_up = jax.lax.all_gather(w_up, fsdp_axis, axis=1, tiled=True)
    w_down = jax.lax.all_gather(w_down, fsdp_axis, axis=2, tiled=True)

    ids, weights, aux = _route(router_w, cfg, x)
    send_x, send_eloc, send_src = _pack_send(x, ids, cfg, M, C)

    recv_x = jax.lax.all_to_all(send_x, "model", 0, 0, tiled=True)
    recv_eloc = jax.lax.all_to_all(send_eloc, "model", 0, 0, tiled=True)
    recv_valid = jax.lax.all_to_all(send_src >= 0, "model", 0, 0, tiled=True)

    flat_x = recv_x.reshape(M * C, d)
    # invalid slots go to a trash bucket (index E_loc), never computed
    flat_e = jnp.where(recv_valid.reshape(-1), recv_eloc.reshape(-1), E_loc)
    cap = _capacity(M * C / E_loc, cfg.capacity_factor)
    buf, src = _bucketize(flat_x, flat_e, E_loc + 1, cap)
    ybuf = _expert_mlp_bucketed(buf[:E_loc], w_gate, w_up, w_down, act)
    y_flat = _unbucketize(ybuf, src[:E_loc], M * C)
    y_back = jax.lax.all_to_all(y_flat.reshape(M, C, d), "model", 0, 0, tiled=True)

    # combine at source: gate-weight each returned pair into its token
    w_pair = weights.reshape(-1).astype(y_back.dtype)       # (T*k,)
    src = send_src.reshape(-1)                              # send-slot -> pair
    valid = src >= 0
    contrib = y_back.reshape(M * C, d) * jnp.where(
        valid, w_pair[jnp.maximum(src, 0)], 0.0)[:, None]
    out = jnp.zeros((T, d), y_back.dtype).at[
        jnp.maximum(src, 0) // k].add(contrib)
    n_dev = jax.lax.psum(1, all_axes)
    aux = jax.lax.psum(aux, all_axes) / n_dev
    return out.astype(x.dtype), aux


def _moe_replicated_block(x, router_w, w_gate, w_up, w_down, *,
                          cfg: ModelConfig, M: int, act, fsdp_axis: str,
                          all_axes: tuple, reduce_axes: tuple):
    """Decode-time body: tokens replicated over "model", experts local."""
    T, d = x.shape
    k = cfg.top_k
    E_loc = cfg.n_experts // M
    w_gate = jax.lax.all_gather(w_gate, fsdp_axis, axis=1, tiled=True)
    w_up = jax.lax.all_gather(w_up, fsdp_axis, axis=1, tiled=True)
    w_down = jax.lax.all_gather(w_down, fsdp_axis, axis=2, tiled=True)

    ids, weights, aux = _route(router_w, cfg, x)
    me = jax.lax.axis_index("model")
    flat_ids = ids.reshape(-1)
    mine = (flat_ids // E_loc) == me
    eloc = jnp.where(mine, flat_ids - me * E_loc, E_loc)   # E_loc = trash
    cap = _capacity(T * k / (E_loc * M) * E_loc, cfg.capacity_factor)
    buf, src = _bucketize(x[jnp.arange(T * k) // k], eloc, E_loc + 1, cap)
    ybuf = _expert_mlp_bucketed(buf[:E_loc], w_gate, w_up, w_down, act)
    ys = _unbucketize(ybuf, src[:E_loc], T * k)
    w_pair = (weights.reshape(-1) * mine).astype(ys.dtype)
    out = jnp.zeros((T, d), ys.dtype).at[jnp.arange(T * k) // k].add(
        ys * w_pair[:, None])
    out = jax.lax.psum(out, "model")
    # aux only varies over the axes the tokens are sharded on (possibly none)
    if reduce_axes:
        aux = jax.lax.psum(aux, reduce_axes) / jax.lax.psum(1, reduce_axes)
    return out.astype(x.dtype), aux


def _moe_replicated_psum_block(x, router_w, w_gate, w_up, w_down, *,
                               cfg: ModelConfig, M: int, act,
                               reduce_axes: tuple, data_size: int):
    """Decode-time MoE WITHOUT the expert-weight all-gather (beyond-paper).

    The baseline replicated strategy all-gathers (E_loc, d, f) expert weights
    over "data" every layer — ~2 GiB/layer for kimi-k2 to serve a handful of
    tokens.  Decode token batches are tiny, so invert the trade: all-gather
    the TOKENS over the token-sharded axes (~MBs), contract against the LOCAL
    d-shard of the weights, and psum the partial products over "data".  The
    wire now carries activations, never weights.
    """
    T_loc, d = x.shape
    k = cfg.top_k
    E_loc = cfg.n_experts // M
    d_loc = d // data_size
    # tokens are cheap at decode: replicate them across the data axis
    x_full = (jax.lax.all_gather(x, reduce_axes, axis=0, tiled=True)
              if reduce_axes else x)
    T = x_full.shape[0]
    ids, weights, aux = _route(router_w, cfg, x_full)   # identical on shards
    me = jax.lax.axis_index("model")
    me_d = jax.lax.axis_index("data")
    x_d = jax.lax.dynamic_slice_in_dim(x_full, me_d * d_loc, d_loc, axis=1)

    flat_ids = ids.reshape(-1)
    mine = (flat_ids // E_loc) == me
    eloc = jnp.where(mine, flat_ids - me * E_loc, E_loc)
    cap = _capacity(T * k / (E_loc * M) * E_loc, cfg.capacity_factor)
    buf, src = _bucketize(x_d[jnp.arange(T * k) // k], eloc, E_loc + 1, cap)
    buf = buf[:E_loc]
    # partial contraction over my d-shard, psum'd over "data"
    g = jax.lax.psum(jnp.einsum("ecd,edf->ecf", buf, w_gate), "data")
    u = jax.lax.psum(jnp.einsum("ecd,edf->ecf", buf, w_up), "data")
    h = (act(g.astype(jnp.float32)) * u.astype(jnp.float32)).astype(buf.dtype)
    y_loc = jnp.einsum("ecf,efd->ecd", h, w_down)        # (E_loc, cap, d_loc)
    y = jax.lax.all_gather(y_loc, "data", axis=2, tiled=True)
    ys = _unbucketize(y, src[:E_loc], T * k)
    w_pair = (weights.reshape(-1) * mine).astype(ys.dtype)
    out = jnp.zeros((T, d), ys.dtype).at[jnp.arange(T * k) // k].add(
        ys * w_pair[:, None])
    out = jax.lax.psum(out, "model")                     # (T, d) full tokens
    if reduce_axes:   # return to token-sharded layout
        me_lin = jax.lax.axis_index(reduce_axes)
        out = jax.lax.dynamic_slice_in_dim(out, me_lin * T_loc, T_loc, axis=0)
    return out.astype(x.dtype), aux


def moe_ffn(params, cfg: ModelConfig, x, act, *, strategy: str = "local",
            token_spec: P = None):
    """Routed-experts FFN dispatch.  x (T, d) -> (out, aux_loss)."""
    if strategy == "local":
        return moe_ffn_local(params, cfg, x, act)

    mesh = jax.sharding.get_abstract_mesh()
    assert "model" in mesh.axis_names, "needs a mesh"
    M = mesh.shape["model"]
    if cfg.n_experts % M != 0:
        return moe_ffn_local(params, cfg, x, act)
    data_axes = tuple(a for a in mesh.axis_names if a != "model")
    all_axes = tuple(mesh.axis_names)
    # expert weights arrive sharded ("ep","fsdp",·): keep "data" sharding in
    # the block spec and all-gather inside
    wg_spec = P("model", "data", None)
    wd_spec = P("model", None, "data")

    if strategy == "a2a":
        if token_spec is None:
            token_spec = P(tuple(list(data_axes) + ["model"]), None)
        T_glob = x.shape[0]
        n_blocks = math.prod(mesh.shape.values())
        T_loc = T_glob // n_blocks
        C = max(8, -(-int(T_loc * cfg.top_k / M * cfg.capacity_factor)) // 8 * 8)
        body = functools.partial(_moe_a2a_block, cfg=cfg, M=M, C=C, act=act,
                                 fsdp_axis="data", all_axes=all_axes)
        fn = jax.shard_map(
            body, mesh=mesh,
            in_specs=(token_spec, P(None, None), wg_spec, wg_spec, wd_spec),
            out_specs=(token_spec, P()), check_vma=False)
        return fn(x, params["router"], params["w_gate"], params["w_up"],
                  params["w_down"])

    if strategy in ("replicated", "replicated_psum"):
        if token_spec is None:
            token_spec = P(data_axes, None)
        entry = token_spec[0]
        reduce_axes = (() if entry is None
                       else (entry if isinstance(entry, tuple) else (entry,)))
        data_size = mesh.shape["data"]
        if strategy == "replicated_psum" and cfg.d_model % data_size == 0:
            body = functools.partial(
                _moe_replicated_psum_block, cfg=cfg, M=M, act=act,
                reduce_axes=tuple(reduce_axes), data_size=data_size)
        else:
            body = functools.partial(
                _moe_replicated_block, cfg=cfg, M=M, act=act,
                fsdp_axis="data", all_axes=all_axes,
                reduce_axes=tuple(reduce_axes))
        fn = jax.shard_map(
            body, mesh=mesh,
            in_specs=(token_spec, P(None, None), wg_spec, wg_spec, wd_spec),
            out_specs=(token_spec, P()), check_vma=False)
        return fn(x, params["router"], params["w_gate"], params["w_up"],
                  params["w_down"])

    raise ValueError(strategy)


def shared_expert_ffn(params, cfg: ModelConfig, x, act):
    """Dense always-on shared experts (DeepSeek/Kimi style), tp-sharded."""
    g = x @ params["ws_gate"]
    u = x @ params["ws_up"]
    return (act(g.astype(jnp.float32)) * u.astype(jnp.float32)).astype(x.dtype) @ params["ws_down"]
