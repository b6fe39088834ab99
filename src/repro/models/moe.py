"""Mixture-of-experts FFN (GShard-style capacity dispatch, EP-shardable).

Covers both assigned MoE archs:
  * llama4-scout-17b-a16e : 16 experts, top-1, 1 shared expert
  * deepseek-moe-16b      : 64 fine-grained experts, top-6, 2 shared experts

Dispatch is the dense one-hot formulation: tokens are routed to (expert,
capacity-slot) buckets via einsum, experts run as a batched (E, C, D) matmul
whose E axis shards over the ``model`` mesh axis (expert parallelism), and
results are combined with the routing weights. Over-capacity tokens are
dropped by the router (their combine weight is zero) — the standard
capacity-factor trade-off; the auxiliary load-balance loss keeps drops rare.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .layers import dense_init, dtype_of, init_mlp, apply_mlp


def init_moe(key, cfg) -> Dict:
    dt = dtype_of(cfg.param_dtype)
    d, fe = cfg.d_model, cfg.moe_d_ff
    ks = jax.random.split(key, 4)
    n_mat = 3 if cfg.act == "swiglu" else 2
    expert_keys = jax.random.split(ks[0], n_mat)
    p = {
        "router": dense_init(ks[1], d, cfg.n_experts, jnp.float32),
        # experts stacked on a leading E axis (shards over `model` for EP)
        "w_up": _stack(expert_keys[0], cfg.n_experts, d, fe, dt),
        "w_down": _stack(expert_keys[1], cfg.n_experts, fe, d, dt),
    }
    if cfg.act == "swiglu":
        p["w_gate"] = _stack(expert_keys[2], cfg.n_experts, d, fe, dt)
    if cfg.n_shared_experts:
        p["shared"] = init_mlp(ks[2], d, fe * cfg.n_shared_experts, cfg.act, dt)
    return p


def _stack(key, e, d_in, d_out, dt):
    return (
        jax.random.normal(key, (e, d_in, d_out), jnp.float32) * d_in ** -0.5
    ).astype(dt)


def apply_moe(p: Dict, x: jnp.ndarray, cfg) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, D). Returns (out, aux_loss).

    Long token streams (32k prefill = 1M tokens) are routed in chunks of
    ``cfg.moe_chunk`` tokens: the (T, E, C) dispatch tensors scale with the
    chunk, not the stream — without this, prefill dispatch alone is O(T^2)
    memory and cannot fit any HBM.
    """
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)
    chunk = min(getattr(cfg, "moe_chunk", 8192), t)
    if t > chunk:
        nchunks = -(-t // chunk)
        pad = nchunks * chunk - t
        xp = jnp.pad(xt, ((0, pad), (0, 0))).reshape(nchunks, chunk, 1, d)

        def one(xc):
            return _moe_chunk(p, xc.reshape(chunk, d), cfg)

        outs, auxs = jax.lax.map(one, xp)
        out = outs.reshape(nchunks * chunk, d)[:t].reshape(b, s, d)
        aux = jnp.mean(auxs)
    else:
        out_t, aux = _moe_chunk(p, xt, cfg)
        out = out_t.reshape(b, s, d)

    if cfg.n_shared_experts:
        out = out + apply_mlp(p["shared"], x, cfg.act).reshape(b, s, d)
    return out, aux


def _moe_chunk(p: Dict, xt: jnp.ndarray, cfg) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Route one chunk of tokens. xt: (T, D) -> ((T, D), aux)."""
    t, d = xt.shape
    e, k = cfg.n_experts, cfg.top_k

    # Expert-parallel boundary hints: keep every (E, C, ...) intermediate
    # sharded on the expert axis through fwd AND bwd — without them GSPMD
    # materializes full unsharded expert gradients and all-reduces them
    # (measured 4.1 TB/step on llama4-scout; EXPERIMENTS.md §Perf). NOTE:
    # additionally sharding the capacity dim over dp was measured WORSE
    # (6.3 TB all-reduce) — tokens would need a second exchange in bwd.
    # Pins apply only where measured beneficial: fsdp TRAIN cells (where
    # act_shard_axis is set). At prefill the pins replicate (C, D) per chunk
    # and regress memory 6 -> 25 GiB (measured on llama4; §Perf).
    ep_axis = getattr(cfg, "act_shard_axis", "")
    try:
        mesh = jax.sharding.get_abstract_mesh()
    except AttributeError:  # jax < 0.5 exposes only the internal accessor
        from jax._src.mesh import get_abstract_mesh

        mesh = get_abstract_mesh()
    axis_names = tuple(getattr(mesh, "axis_names", None) or ())
    if mesh is None or ep_axis not in axis_names:
        ep_axis = ""  # no such axis in scope (single-device tests etc.)
    bax = tuple(getattr(cfg, "act_batch_axes", ()) or ())
    bax = tuple(a for a in bax if a in axis_names) or None

    def pin_e(a):
        if not ep_axis:
            return a
        from jax.sharding import PartitionSpec as P

        return jax.lax.with_sharding_constraint(
            a, P(*([ep_axis] + [None] * (a.ndim - 1)))
        )

    def pin_ec(a):
        """Two-stage dispatch: materialize (E, C, ·) data-sharded on C first
        (each data shard dispatches its own tokens), THEN pin_e gathers C —
        the expert matmul sees the full capacity locally, so expert-weight
        grads complete without the full-size cross-data all-reduce that a
        single-stage pin provokes (llama4: 3.0 TB AR; §Perf H2.5)."""
        if not ep_axis or bax is None or a.ndim < 2:
            return a
        from jax.sharding import PartitionSpec as P

        bsz = 1
        for x in bax:
            bsz *= mesh.shape[x]
        if a.shape[1] % max(bsz, 1):
            return a
        return jax.lax.with_sharding_constraint(
            a, P(*([ep_axis, bax] + [None] * (a.ndim - 2)))
        )
    logits = (xt.astype(jnp.float32) @ p["router"]).astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)                         # (T, k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    capacity = max(int(t * k / e * cfg.capacity_factor), 1)
    # Position of each (token, choice) within its expert's capacity buffer.
    onehot = jax.nn.one_hot(gate_idx, e, dtype=jnp.float32)               # (T, k, E)
    flat = onehot.reshape(t * k, e)
    pos = (jnp.cumsum(flat, axis=0) - flat).reshape(t, k, e)              # (T, k, E)
    pos = jnp.sum(pos * onehot, axis=-1)                                  # (T, k)
    keep = pos < capacity
    gate_vals = gate_vals * keep.astype(gate_vals.dtype)

    # Build (T, E, C) dispatch/combine by unrolling the small top-k axis —
    # the 4-D (T, k, E, C) tensor must never materialize.
    dispatch = jnp.zeros((t, e, capacity), jnp.float32)
    combine = jnp.zeros((t, e, capacity), jnp.float32)
    for i in range(k):
        oh_e = onehot[:, i]                                               # (T, E)
        oh_c = jax.nn.one_hot(pos[:, i].astype(jnp.int32), capacity,
                              dtype=jnp.float32)                          # (T, C)
        d_i = (oh_e * keep[:, i: i + 1].astype(jnp.float32))[:, :, None] * oh_c[:, None, :]
        dispatch = dispatch + d_i
        combine = combine + d_i * gate_vals[:, i][:, None, None]

    # NOTE: a two-stage dispatch pin (C data-sharded, then gathered) was
    # also measured WORSE (85.0 -> 98.7 s collective; §Perf H2.5) — GSPMD
    # cannot be coaxed into token-local dispatch; a manual shard_map
    # all-to-all MoE remains the identified next step.
    xin = pin_e(jnp.einsum("tec,td->ecd", dispatch.astype(xt.dtype), xt))  # (E, C, D)
    if cfg.act == "swiglu":
        h = jax.nn.silu(pin_e(jnp.einsum("ecd,edf->ecf", xin, p["w_gate"])))
        h = h * pin_e(jnp.einsum("ecd,edf->ecf", xin, p["w_up"]))
    else:
        h = pin_e(jnp.einsum("ecd,edf->ecf", xin, p["w_up"]))
        h = jax.nn.gelu(h) if cfg.act == "gelu" else jnp.square(jax.nn.relu(h))
    eout = pin_e(jnp.einsum("ecf,efd->ecd", h, p["w_down"]))              # (E, C, D)
    out = jnp.einsum("tec,ecd->td", combine.astype(eout.dtype), eout)

    # Switch-style load-balance auxiliary loss.
    me = jnp.mean(onehot.sum(1), axis=0)        # fraction routed per expert
    pe = jnp.mean(probs, axis=0)                # mean router prob per expert
    aux = e * jnp.sum(me * pe)
    return out, aux
