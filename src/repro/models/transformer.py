"""Model assembly for all assigned architecture families.

Layer stacks are ``lax.scan`` over stacked weights (HLO size O(1) in depth).
Three entry points per family, built by factories so cfg/flags stay static:

  * ``loss_fn``      — full-sequence forward + CE loss           (train_4k)
  * ``prefill``      — full-sequence forward -> (last_logits, cache)
  * ``decode_step``  — one token with cache                      (decode_*)

Sharding: params carry logical axes resolved in repro/sharding/specs.py.
The embedding lookup is vocab-parallel via shard_map (a plain gather on a
vocab-sharded table would make GSPMD all-gather the table); the CE loss uses
an iota-compare fused reduction, so neither end materialises (B,S,V) one-hots
nor cross-shard gathers.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import layers, mamba2, moe as moe_lib, rwkv6
from jax.ad_checkpoint import checkpoint_name

from repro.models.attention import AttnSpec, attention, decode_attention

Array = jax.Array


# ---------------------------------------------------------------------------
# Run-time knobs (the hillclimb levers) and sharding context
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RunFlags:
    attn_impl: str = "chunked"          # naive | chunked | pallas
    q_chunk: int = 512
    kv_chunk: int = 512
    skip_masked_tiles: bool = False     # hillclimb: causal tile skipping
    microbatches: int = 1               # grad-accumulation microbatches
    remat: bool = True
    moe_mode: str = "pjit"              # pjit | ep_shardmap (hillclimb)
    moe_seq_chunk: int = 2048           # chunk S for MoE dispatch (prefill
                                        # memory bound; 0 = no chunking)
    scan_layers: bool = True
    compute_dtype: str = "bfloat16"     # bfloat16 | float32 (oracle mode)
    wkv_chunk: int = 16                 # RWKV WKV chunk length (hillclimb)
    remat_policy: str = "full"          # full | save_block_io (hillclimb)
    sequence_parallel: bool = False     # Megatron-SP activations (hillclimb)


@dataclasses.dataclass(frozen=True)
class ShardCtx:
    mesh: Any
    data_axes: Tuple[str, ...] = ("data",)
    model_axis: str = "model"

    @property
    def data_spec(self):
        return self.data_axes if len(self.data_axes) > 1 else self.data_axes[0]


def _constrain(x, ctx: Optional[ShardCtx], *spec):
    if ctx is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(ctx.mesh, P(*spec)))


def padded_vocab(cfg: ModelConfig) -> int:
    return -(-cfg.vocab_size // 16) * 16


def cast_params(params, dtype=jnp.bfloat16):
    """Compute-dtype cast (differentiable, so f32 masters get f32 grads)."""
    return jax.tree.map(
        lambda a: a.astype(dtype) if a.dtype == jnp.float32 else a, params)


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------


def _attn_init(key, cfg: ModelConfig, dtype, pre=()):
    ks = jax.random.split(key, 4)
    d = cfg.d_model
    return {
        "wq": layers.dense_init(ks[0], d, cfg.d_q, dtype, shape_prefix=pre),
        "wk": layers.dense_init(ks[1], d, cfg.d_kv, dtype, shape_prefix=pre),
        "wv": layers.dense_init(ks[2], d, cfg.d_kv, dtype, shape_prefix=pre),
        "wo": layers.dense_init(ks[3], cfg.d_q, d, dtype, shape_prefix=pre),
    }


def init_params(cfg: ModelConfig, key: Array, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 8)
    d = cfg.d_model
    Vp = padded_vocab(cfg)
    params: dict = {"final_norm": jnp.ones((d,), jnp.float32)}
    if cfg.frontend != "frames":
        params["embed"] = layers.embed_init(ks[0], Vp, d, dtype)
    if not cfg.tie_embeddings or cfg.frontend == "frames":
        params["lm_head"] = layers.dense_init(ks[1], d, Vp, dtype)

    L = cfg.n_layers
    fam = cfg.family
    if fam in ("dense", "audio", "moe"):
        blocks = {
            "attn": _attn_init(ks[2], cfg, dtype, pre=(L,)),
            "ln1": jnp.ones((L, d), jnp.float32),
            "ln2": jnp.ones((L, d), jnp.float32),
        }
        if cfg.moe is not None:
            blocks["moe"] = moe_lib.moe_init(ks[3], cfg, L, dtype)
        else:
            blocks["mlp"] = layers.mlp_init(ks[3], d, cfg.d_ff, cfg.mlp_type,
                                            dtype, shape_prefix=(L,))
        params["blocks"] = blocks
    elif fam == "vlm":
        n_cross = L // cfg.cross_attn_period
        per = cfg.cross_attn_period - 1
        assert n_cross * cfg.cross_attn_period == L
        params["blocks"] = {
            "attn": _attn_init(ks[2], cfg, dtype, pre=(n_cross, per)),
            "mlp": layers.mlp_init(ks[3], d, cfg.d_ff, cfg.mlp_type, dtype,
                                   shape_prefix=(n_cross, per)),
            "ln1": jnp.ones((n_cross, per, d), jnp.float32),
            "ln2": jnp.ones((n_cross, per, d), jnp.float32),
            "cross": {
                **_attn_init(ks[4], cfg, dtype, pre=(n_cross,)),
                "ln_q": jnp.ones((n_cross, d), jnp.float32),
                "gate": jnp.zeros((n_cross,), jnp.float32),
                "mlp": layers.mlp_init(ks[5], d, cfg.d_ff, cfg.mlp_type,
                                       dtype, shape_prefix=(n_cross,)),
                "ln2": jnp.ones((n_cross, d), jnp.float32),
                "gate_mlp": jnp.zeros((n_cross,), jnp.float32),
            },
        }
    elif fam == "hybrid":
        n_super = L // cfg.attn_period
        per = cfg.attn_period - 1
        assert n_super * cfg.attn_period == L
        params["blocks"] = {
            "mamba": mamba2.mamba2_init(ks[2], cfg, dtype,
                                        shape_prefix=(n_super, per)),
            "mamba_ln": jnp.ones((n_super, per, d), jnp.float32),
            "shared": {
                "attn": _attn_init(ks[3], cfg, dtype),
                "mlp": layers.mlp_init(ks[4], d, cfg.d_ff, cfg.mlp_type, dtype),
                "ln1": jnp.ones((d,), jnp.float32),
                "ln2": jnp.ones((d,), jnp.float32),
            },
        }
    elif fam == "ssm":
        params["blocks"] = {
            "rwkv": rwkv6.rwkv6_init(ks[2], cfg, dtype, shape_prefix=(L,)),
            "ln1": jnp.ones((L, d), jnp.float32),
            "ln2": jnp.ones((L, d), jnp.float32),
        }
    else:
        raise ValueError(fam)
    return params


# ---------------------------------------------------------------------------
# Embedding / head (vocab-parallel when ctx is given)
# ---------------------------------------------------------------------------


def embed_lookup(cfg: ModelConfig, params, ids: Array,
                 ctx: Optional[ShardCtx]) -> Array:
    table = params["embed"]
    if ctx is None:
        return jnp.take(table, ids, axis=0)

    def body(tab, ids_l):
        start = jax.lax.axis_index(ctx.model_axis) * tab.shape[0]
        loc = ids_l - start
        ok = (loc >= 0) & (loc < tab.shape[0])
        emb = jnp.take(tab, jnp.clip(loc, 0, tab.shape[0] - 1), axis=0)
        emb = jnp.where(ok[..., None], emb, jnp.zeros((), emb.dtype))
        return jax.lax.psum(emb, ctx.model_axis)

    ax = dict(zip(ctx.mesh.axis_names, ctx.mesh.devices.shape))
    dsize = 1
    for a in ctx.data_axes:
        dsize *= ax[a]
    bspec = ctx.data_spec if ids.shape[0] % dsize == 0 and \
        ids.shape[0] >= dsize else None
    ids_spec = P(bspec, *([None] * (ids.ndim - 1)))
    out_spec = P(bspec, *([None] * ids.ndim))
    return jax.shard_map(
        body, mesh=ctx.mesh,
        in_specs=(P(ctx.model_axis, None), ids_spec),
        out_specs=out_spec, check_vma=False)(table, ids)


def lm_logits(cfg: ModelConfig, params, x: Array,
              ctx: Optional[ShardCtx]) -> Array:
    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings and cfg.frontend != "frames":
        logits = x @ params["embed"].T.astype(x.dtype)
    else:
        logits = x @ params["lm_head"].astype(x.dtype)
    if ctx is not None:
        spec = [None] * logits.ndim
        spec[0] = ctx.data_spec
        spec[-1] = ctx.model_axis
        logits = _constrain(logits, ctx, *spec)
    return logits


# ---------------------------------------------------------------------------
# Attention block (dense / moe / audio / vlm / hybrid-shared)
# ---------------------------------------------------------------------------


def _qkv(cfg, w, x, pos):
    B, S, _ = x.shape
    q = (x @ w["wq"]).reshape(B, S, cfg.n_heads, cfg.head_dim)
    k = (x @ w["wk"]).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    v = (x @ w["wv"]).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    q = layers.apply_rope(q, pos, cfg.rope)
    k = layers.apply_rope(k, pos, cfg.rope)
    return q, k, v


def attn_block(cfg, flags: RunFlags, ctx, w, ln, x, pos, *, kv_pos,
               window=0, return_kv=False):
    """``kv_pos`` is ``pos`` with -1 on the keys no query may see."""
    h = layers.rms_norm(x, ln, cfg.norm_eps)
    q, k, v = _qkv(cfg, w, h, pos)
    spec = AttnSpec(causal=cfg.causal, window=window, q_chunk=flags.q_chunk,
                    kv_chunk=flags.kv_chunk,
                    skip_masked_tiles=flags.skip_masked_tiles,
                    positions_are_arange=True)
    o = attention(q, k, v, impl=flags.attn_impl, spec=spec, q_pos=pos,
                  kv_pos=kv_pos)
    B, S, _ = x.shape
    out = x + checkpoint_name(
        o.reshape(B, S, cfg.d_q) @ w["wo"], "attn_out")
    if return_kv:
        # cache copies are sequence-sharded on the model axis (context-
        # parallel decode layout) so the stacked prefill cache is /16 per
        # device rather than replicated along S
        if ctx is not None and S % 16 == 0:
            k = _constrain(k, ctx, ctx.data_spec, ctx.model_axis, None, None)
            v = _constrain(v, ctx, ctx.data_spec, ctx.model_axis, None, None)
        return out, (k, v)
    return out


def attn_block_decode(cfg, w, ln, x, q_pos, kcache, vcache, kv_pos, *,
                      window=0):
    """x (B,1,d); kcache/vcache (B,S,KH,hd) already containing this token."""
    h = layers.rms_norm(x, ln, cfg.norm_eps)
    B = x.shape[0]
    q = (h @ w["wq"]).reshape(B, 1, cfg.n_heads, cfg.head_dim)
    q = layers.apply_rope(q, q_pos, cfg.rope)
    o = decode_attention(q, kcache, vcache, q_pos=q_pos, kv_pos=kv_pos,
                         window=window)
    return x + o.reshape(B, 1, cfg.d_q) @ w["wo"]


def mlp_block(cfg, w, ln, x):
    h = layers.rms_norm(x, ln, cfg.norm_eps)
    return x + checkpoint_name(
        layers.mlp_apply(w, h, cfg.mlp_type), "mlp_out")


def moe_block(cfg, flags, ctx, w_moe, ln, x, layer_idx):
    B, S, d = x.shape
    h = layers.rms_norm(x, ln, cfg.norm_eps)

    def apply_tokens(ht):                       # ht (T, d)
        if flags.moe_mode == "ep_shardmap" and ctx is not None:
            from repro.sharding.ep import moe_apply_ep
            return moe_apply_ep(w_moe, ht, cfg, ctx)
        return moe_lib.moe_apply(w_moe, ht, cfg)

    ch = flags.moe_seq_chunk
    if ch and S > ch and S % ch == 0:
        # chunk the sequence dim so dispatch buffers stay bounded at 32k+
        # prefill (S stays unsharded -> clean chunk slicing under GSPMD)
        nc = S // ch
        hc = h.reshape(B, nc, ch, d).transpose(1, 0, 2, 3)

        def body(aux, hi):
            y, a = apply_tokens(hi.reshape(B * ch, d))
            return aux + a, y.reshape(B, ch, d)

        aux, ys = jax.lax.scan(body, jnp.zeros((), jnp.float32), hc)
        y = ys.transpose(1, 0, 2, 3).reshape(B, S, d)
        aux = aux / nc
    else:
        y, aux = apply_tokens(h.reshape(B * S, d))
        y = y.reshape(B, S, d)
    return x + y, aux


# ---------------------------------------------------------------------------
# Full-sequence forward (train / prefill) per family
# ---------------------------------------------------------------------------


def _maybe_remat(fn, flags: RunFlags):
    if not flags.remat:
        return fn
    if flags.remat_policy == "save_block_io":
        # keep post-all-reduce block outputs resident: the rematerialised
        # forward then re-runs only local math, not the TP collectives
        policy = jax.checkpoint_policies.save_only_these_names(
            "attn_out", "mlp_out")
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


def forward(cfg: ModelConfig, params, batch: dict, flags: RunFlags,
            ctx: Optional[ShardCtx], *, collect_cache: bool = False):
    """Returns (hidden (B,S,d), aux_losses, cache_parts or None).

    ``batch["n_pad"]`` (B,), if given, counts row b's leading pad tokens:
    their keys get position -1, so no attention query sees them.
    """
    fam = cfg.family
    cdt = jnp.dtype(flags.compute_dtype)
    if cfg.frontend == "frames":
        x = batch["frames"].astype(cdt)
        B, S = x.shape[:2]
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        x = x + layers.sinusoidal_positions(pos, cfg.d_model).astype(x.dtype)
    else:
        ids = batch["tokens"]
        B, S = ids.shape
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        x = embed_lookup(cfg, params, ids, ctx).astype(cdt)
    kv_pos = pos
    if "n_pad" in batch:
        kv_pos = jnp.where(pos < batch["n_pad"][:, None], -1, pos)
    seq_axis = "model" if flags.sequence_parallel else None
    x = _constrain(x, ctx, ctx.data_spec if ctx else None, seq_axis, None)

    bl = params["blocks"]
    aux = jnp.zeros((), jnp.float32)
    cache = None

    if fam in ("dense", "audio", "moe"):
        has_moe = cfg.moe is not None

        def body(carry, wl):
            x, aux = carry
            if collect_cache:
                x, (k, v) = attn_block(cfg, flags, ctx, wl["attn"], wl["ln1"],
                                       x, pos, kv_pos=kv_pos, return_kv=True)
            else:
                x = attn_block(cfg, flags, ctx, wl["attn"], wl["ln1"], x, pos,
                               kv_pos=kv_pos)
            if has_moe:
                x, a = moe_block(cfg, flags, ctx, wl["moe"], wl["ln2"], x, None)
                aux = aux + a
            else:
                x = mlp_block(cfg, wl["mlp"], wl["ln2"], x)
            x = _constrain(x, ctx, ctx.data_spec if ctx else None,
                           seq_axis, None)
            if collect_cache:
                return (x, aux), (k, v)
            return (x, aux), None

        (x, aux), kv = jax.lax.scan(_maybe_remat(body, flags), (x, aux), bl)
        if collect_cache:
            cache = {"k": kv[0], "v": kv[1]}                  # (L,B,S,KH,hd)

    elif fam == "vlm":
        patches = batch["patches"].astype(cdt)                # (B,M,d)
        M = patches.shape[1]
        ppos = jnp.broadcast_to(jnp.arange(M, dtype=jnp.int32),
                                (patches.shape[0], M))

        def super_body(carry, wl):
            x, aux = carry

            def inner(x, wi):
                if collect_cache:
                    x, (k, v) = attn_block(cfg, flags, ctx, wi["attn"],
                                           wi["ln1"], x, pos, kv_pos=kv_pos,
                                           return_kv=True)
                    x = mlp_block(cfg, wi["mlp"], wi["ln2"], x)
                    return x, (k, v)
                x = attn_block(cfg, flags, ctx, wi["attn"], wi["ln1"], x, pos,
                               kv_pos=kv_pos)
                x = mlp_block(cfg, wi["mlp"], wi["ln2"], x)
                return x, None

            x, inner_kv = jax.lax.scan(
                _maybe_remat(inner, flags), x,
                {"attn": wl["attn"], "mlp": wl["mlp"], "ln1": wl["ln1"],
                 "ln2": wl["ln2"]})
            # cross-attention to patch embeddings (non-causal, gated)
            cw = wl["cross"]
            h = layers.rms_norm(x, cw["ln_q"], cfg.norm_eps)
            B_, S_, _ = x.shape
            q = (h @ cw["wq"]).reshape(B_, S_, cfg.n_heads, cfg.head_dim)
            k = (patches @ cw["wk"]).reshape(B_, M, cfg.n_kv_heads, cfg.head_dim)
            v = (patches @ cw["wv"]).reshape(B_, M, cfg.n_kv_heads, cfg.head_dim)
            spec = AttnSpec(causal=False, q_chunk=flags.q_chunk,
                            kv_chunk=flags.kv_chunk)
            o = attention(q, k, v, impl=flags.attn_impl, spec=spec,
                          q_pos=pos, kv_pos=ppos)
            x = x + jnp.tanh(cw["gate"]).astype(x.dtype) * (
                o.reshape(B_, S_, cfg.d_q) @ cw["wo"])
            h = layers.rms_norm(x, cw["ln2"], cfg.norm_eps)
            x = x + jnp.tanh(cw["gate_mlp"]).astype(x.dtype) * \
                layers.mlp_apply(cw["mlp"], h, cfg.mlp_type)
            if collect_cache:
                return (x, aux), (inner_kv, (k, v))
            return (x, aux), None

        (x, aux), ys = jax.lax.scan(super_body, (x, aux), bl)
        if collect_cache:
            (sk, sv), (ck, cv) = ys            # sk: (n_cross, per, B, S, KH, hd)
            n_self = sk.shape[0] * sk.shape[1]
            cache = {"k": sk.reshape((n_self,) + sk.shape[2:]),
                     "v": sv.reshape((n_self,) + sv.shape[2:]),
                     "cross_k": ck, "cross_v": cv}

    elif fam == "hybrid":
        shared = bl["shared"]

        def super_body(carry, wl):
            x, aux = carry

            def inner(x, wi):
                h = layers.rms_norm(x, wi["ln"], cfg.norm_eps)
                y, (st, tails) = mamba2.mamba2_forward(wi["w"], h, cfg)
                return x + y, (st, tails)

            x, states = jax.lax.scan(
                _maybe_remat(inner, flags), x,
                {"w": wl["mamba"], "ln": wl["mamba_ln"]})
            if collect_cache:
                x, (k, v) = attn_block(cfg, flags, ctx, shared["attn"],
                                       shared["ln1"], x, pos, kv_pos=kv_pos,
                                       window=cfg.attn_window, return_kv=True)
            else:
                x = attn_block(cfg, flags, ctx, shared["attn"], shared["ln1"],
                               x, pos, kv_pos=kv_pos, window=cfg.attn_window)
            x = mlp_block(cfg, shared["mlp"], shared["ln2"], x)
            if collect_cache:
                W = min(cfg.attn_window or x.shape[1], x.shape[1])
                return (x, aux), (states, (k[:, -W:], v[:, -W:]))
            return (x, aux), None

        xs_hy = {"mamba": bl["mamba"], "mamba_ln": bl["mamba_ln"]}
        (x, aux), ys = jax.lax.scan(super_body, (x, aux), xs_hy)
        if collect_cache:
            states, (kw, vw) = ys
            cache = {"mamba_state": states[0], "conv_tails": states[1],
                     "win_k": kw, "win_v": vw}

    elif fam == "ssm":
        def body(carry, wl):
            x, aux = carry
            w = wl["rwkv"]
            h = layers.rms_norm(x, wl["ln1"], cfg.norm_eps)
            B_, S_, d_ = h.shape
            H, K = cfg.n_heads, cfg.rwkv.head_size
            state0 = jnp.zeros((B_, H, K, K), jnp.float32)
            shift0 = jnp.zeros((B_, 1, d_), h.dtype)
            y, tshift, tstate = rwkv6.time_mix(w["tmix"], h, cfg, shift0,
                                               state0, chunk=flags.wkv_chunk)
            x = x + y
            h = layers.rms_norm(x, wl["ln2"], cfg.norm_eps)
            y, cshift = rwkv6.channel_mix(w["cmix"], h, shift0)
            x = x + y
            if collect_cache:
                return (x, aux), (tshift, tstate, cshift)
            return (x, aux), None

        (x, aux), ys = jax.lax.scan(_maybe_remat(body, flags), (x, aux), bl)
        if collect_cache:
            cache = {"tmix_shift": ys[0], "wkv_state": ys[1],
                     "cmix_shift": ys[2]}
    else:
        raise ValueError(fam)

    if collect_cache:
        cache["kv_pos"] = kv_pos
    return x, aux, cache


# ---------------------------------------------------------------------------
# Loss (train), prefill, decode factories
# ---------------------------------------------------------------------------


def make_loss_fn(cfg: ModelConfig, flags: RunFlags, ctx: Optional[ShardCtx]):
    def loss_fn(params, batch):
        params = cast_params(params, jnp.dtype(flags.compute_dtype))
        x, aux, _ = forward(cfg, params, batch, flags, ctx)
        logits = lm_logits(cfg, params, x, ctx)
        labels = batch["labels"]
        mask = batch.get("loss_mask")
        loss, _ = layers.softmax_cross_entropy(logits, labels, mask)
        return loss + 0.01 * aux, {"loss": loss, "aux": aux}
    return loss_fn


def make_prefill_fn(cfg: ModelConfig, flags: RunFlags, ctx: Optional[ShardCtx],
                    max_len: int):
    """Returns fn(params, batch) -> (last_logits (B,Vp), cache dict)."""
    def prefill(params, batch):
        params = cast_params(params, jnp.dtype(flags.compute_dtype))
        x, _, parts = forward(cfg, params, batch, flags, ctx,
                              collect_cache=True)
        logits = lm_logits(cfg, params, x[:, -1:], ctx)[:, 0]
        B, S = x.shape[0], x.shape[1]
        cache = _grow_cache(cfg, parts, B, S, max_len)
        return logits, cache
    return prefill


def _grow_cache(cfg, parts, B, S, max_len):
    """Pad prefill-collected cache parts out to max_len and add bookkeeping.

    ``parts["kv_pos"]`` (B, S) holds the prefill's key positions, -1 on
    left padding, so decode keeps the pad keys masked."""
    fam = cfg.family
    pos = jnp.full((B,), S, jnp.int32)                        # next position
    out = dict(parts)
    kv_pos = out.pop("kv_pos")
    if "k" in out:                                            # dense/moe/vlm/audio
        pad = max_len - S
        out["k"] = jnp.pad(out["k"], ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        out["v"] = jnp.pad(out["v"], ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        out["kv_pos"] = jnp.pad(kv_pos, ((0, 0), (0, pad)), constant_values=-1)
    if fam == "hybrid":
        W = out["win_k"].shape[2]
        # Align window cache to the decode ring-slot convention slot = pos % W:
        # the collected slice holds positions S-W..S-1 at indices 0..W-1, so
        # roll by (S - W) % W to place position p at index p % W.
        shift = (S - W) % W
        out["win_k"] = jnp.roll(out["win_k"], shift, axis=2)
        out["win_v"] = jnp.roll(out["win_v"], shift, axis=2)
        out["win_pos"] = jnp.roll(jnp.broadcast_to(
            kv_pos[:, S - W:], out["win_k"].shape[:3]), shift, axis=2)
    out["pos"] = pos
    return out


def init_cache(cfg: ModelConfig, B: int, max_len: int,
               dtype=jnp.bfloat16) -> dict:
    """Empty cache for pure-decode dry-runs and serving."""
    fam = cfg.family
    pos = jnp.zeros((B,), jnp.int32)
    if fam in ("dense", "audio", "moe"):
        L, KH, hd = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
        return {
            "k": jnp.zeros((L, B, max_len, KH, hd), dtype),
            "v": jnp.zeros((L, B, max_len, KH, hd), dtype),
            "kv_pos": jnp.full((B, max_len), -1, jnp.int32),
            "pos": pos,
        }
    if fam == "vlm":
        L, KH, hd = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
        n_cross = L // cfg.cross_attn_period
        n_self = L - n_cross
        return {
            "k": jnp.zeros((n_self, B, max_len, KH, hd), dtype),
            "v": jnp.zeros((n_self, B, max_len, KH, hd), dtype),
            "kv_pos": jnp.full((B, max_len), -1, jnp.int32),
            "cross_k": jnp.zeros((n_cross, B, cfg.n_media_tokens, KH, hd), dtype),
            "cross_v": jnp.zeros((n_cross, B, cfg.n_media_tokens, KH, hd), dtype),
            "pos": pos,
        }
    if fam == "hybrid":
        n_super = cfg.n_layers // cfg.attn_period
        per = cfg.attn_period - 1
        d_in, H, Pd, N = mamba2.dims(cfg)
        cw = cfg.ssm.conv_width
        W = min(cfg.attn_window or max_len, max_len)
        return {
            "mamba_state": jnp.zeros((n_super, per, B, H, Pd, N), jnp.float32),
            "conv_tails": (
                jnp.zeros((n_super, per, B, cw - 1, d_in), dtype),
                jnp.zeros((n_super, per, B, cw - 1, N), dtype),
                jnp.zeros((n_super, per, B, cw - 1, N), dtype),
            ),
            "win_k": jnp.zeros((n_super, B, W, cfg.n_kv_heads, cfg.head_dim), dtype),
            "win_v": jnp.zeros((n_super, B, W, cfg.n_kv_heads, cfg.head_dim), dtype),
            "win_pos": jnp.full((n_super, B, W), -1, jnp.int32),
            "pos": pos,
        }
    if fam == "ssm":
        L, H, K = cfg.n_layers, cfg.n_heads, cfg.rwkv.head_size
        d = cfg.d_model
        return {
            "tmix_shift": jnp.zeros((L, B, 1, d), dtype),
            "wkv_state": jnp.zeros((L, B, H, K, K), jnp.float32),
            "cmix_shift": jnp.zeros((L, B, 1, d), dtype),
            "pos": pos,
        }
    raise ValueError(fam)


_CACHE_BATCH_AXIS = {
    "k": 1, "v": 1, "cross_k": 1, "cross_v": 1, "kv_pos": 0, "pos": 0,
    "mamba_state": 2, "conv_tails": 2, "win_k": 1, "win_v": 1, "win_pos": 1,
    "tmix_shift": 1, "wkv_state": 1, "cmix_shift": 1,
}


def cache_insert(cache: dict, single: dict, slot: int) -> dict:
    """Insert a batch-1 cache (from prefill) into slot `slot` of a batched
    cache — the continuous-batching primitive used by repro/serving."""
    def one(path, big, small):
        name = None
        for p in path:
            k = getattr(p, "key", None)
            if isinstance(k, str) and k in _CACHE_BATCH_AXIS:
                name = k
        ax = _CACHE_BATCH_AXIS.get(name, 0)
        idx = [slice(None)] * big.ndim
        idx[ax] = slot
        small_idx = [slice(None)] * small.ndim
        small_idx[ax] = 0
        return big.at[tuple(idx)].set(small[tuple(small_idx)].astype(big.dtype))

    return jax.tree_util.tree_map_with_path(one, cache, single)


def make_decode_fn(cfg: ModelConfig, flags: RunFlags,
                   ctx: Optional[ShardCtx]):
    """Returns fn(params, cache, tokens (B,)) -> (logits (B,Vp), cache)."""

    def decode(params, cache, tokens):
        params = cast_params(params, jnp.dtype(flags.compute_dtype))
        B = tokens.shape[0]
        pos = cache["pos"]                                    # (B,)
        qpos = pos[:, None]
        x = embed_lookup(cfg, params, tokens[:, None], ctx).astype(
            jnp.dtype(flags.compute_dtype))
        bl = params["blocks"]
        fam = cfg.family
        barange = jnp.arange(B)

        if fam in ("dense", "audio", "moe", "vlm"):
            kc, vc = cache["k"], cache["v"]                   # (L,B,S,KH,hd)
            kv_pos = cache["kv_pos"].at[barange, pos].set(pos)
            S = kc.shape[2]

            if fam == "vlm":
                n_cross = cfg.n_layers // cfg.cross_attn_period
                per = cfg.cross_attn_period - 1

                def super_body(carry, xs):
                    x, kc, vc = carry
                    wl, ci = xs

                    def inner(carry2, xs2):
                        x, kc, vc = carry2
                        wi, li = xs2
                        x, kc, vc = _decode_attn_layer(
                            cfg, wi, x, qpos, kc, vc, kv_pos, li, pos, barange)
                        x = mlp_block(cfg, wi["mlp"], wi["ln2"], x)
                        return (x, kc, vc), None

                    lidx = ci * per + jnp.arange(per)   # flattened self-layer idx
                    (x, kc, vc), _ = jax.lax.scan(
                        inner, (x, kc, vc),
                        ({"attn": wl["attn"], "mlp": wl["mlp"],
                          "ln1": wl["ln1"], "ln2": wl["ln2"]}, lidx))
                    cw = wl["cross"]
                    h = layers.rms_norm(x, cw["ln_q"], cfg.norm_eps)
                    q = (h @ cw["wq"]).reshape(B, 1, cfg.n_heads, cfg.head_dim)
                    ck, cv = cache["cross_k"][ci], cache["cross_v"][ci]
                    M = ck.shape[1]
                    # non-causal cross attention: q_pos=0, kv_pos=0 everywhere
                    o = decode_attention(q, ck, cv,
                                         q_pos=jnp.zeros((B, 1), jnp.int32),
                                         kv_pos=jnp.zeros((B, M), jnp.int32))
                    x = x + jnp.tanh(cw["gate"]).astype(x.dtype) * (
                        o.reshape(B, 1, cfg.d_q) @ cw["wo"])
                    h = layers.rms_norm(x, cw["ln2"], cfg.norm_eps)
                    x = x + jnp.tanh(cw["gate_mlp"]).astype(x.dtype) * \
                        layers.mlp_apply(cw["mlp"], h, cfg.mlp_type)
                    return (x, kc, vc), None

                (x, kc, vc), _ = jax.lax.scan(
                    super_body, (x, kc, vc),
                    (bl, jnp.arange(n_cross)))
            else:
                has_moe = cfg.moe is not None

                def body(carry, xs):
                    x, kc, vc = carry
                    wl, li = xs
                    x, kc, vc = _decode_attn_layer(
                        cfg, wl, x, qpos, kc, vc, kv_pos, li, pos, barange)
                    if has_moe:
                        x, _ = moe_block(cfg, flags, ctx, wl["moe"], wl["ln2"],
                                         x, None)
                    else:
                        x = mlp_block(cfg, wl["mlp"], wl["ln2"], x)
                    return (x, kc, vc), None

                (x, kc, vc), _ = jax.lax.scan(
                    body, (x, kc, vc), (bl, jnp.arange(cfg.n_layers)))

            new_cache = dict(cache, k=kc, v=vc, kv_pos=kv_pos, pos=pos + 1)

        elif fam == "hybrid":
            shared = bl["shared"]
            W = cache["win_k"].shape[2]
            slot = pos % W
            win_pos = cache["win_pos"]

            def super_body(carry, xs):
                x = carry
                wl, st, tails, wk, wv, wp = xs

                def inner(carry2, xs2):
                    x = carry2
                    wi, st_i, tails_i = xs2
                    h = layers.rms_norm(x, wi["ln"], cfg.norm_eps)
                    y, (st2, tails2) = mamba2.mamba2_decode(
                        wi["w"], h, cfg, st_i, tails_i)
                    return x + y, (st2, tails2)

                x, (st2, tails2) = jax.lax.scan(
                    inner, x, ({"w": wl["mamba"], "ln": wl["mamba_ln"]},
                               st, tails))
                # shared attention with ring-buffer window cache
                h = layers.rms_norm(x, shared["ln1"], cfg.norm_eps)
                k1 = (h @ shared["attn"]["wk"]).reshape(B, 1, cfg.n_kv_heads,
                                                        cfg.head_dim)
                v1 = (h @ shared["attn"]["wv"]).reshape(B, 1, cfg.n_kv_heads,
                                                        cfg.head_dim)
                k1 = layers.apply_rope(k1, qpos, cfg.rope)
                wk = wk.at[barange, slot].set(k1[:, 0])
                wv = wv.at[barange, slot].set(v1[:, 0])
                wp = wp.at[barange, slot].set(pos)
                x = attn_block_decode(cfg, shared["attn"], shared["ln1"], x,
                                      qpos, wk, wv, wp, window=cfg.attn_window)
                x = mlp_block(cfg, shared["mlp"], shared["ln2"], x)
                return x, (st2, tails2, wk, wv, wp)

            xs_hy = {"mamba": bl["mamba"], "mamba_ln": bl["mamba_ln"]}
            x, ys = jax.lax.scan(
                super_body, x,
                (xs_hy, cache["mamba_state"], cache["conv_tails"],
                 cache["win_k"], cache["win_v"], cache["win_pos"]))
            st2, tails2, wk, wv, wp = ys
            new_cache = dict(cache, mamba_state=st2, conv_tails=tails2,
                             win_k=wk, win_v=wv, win_pos=wp, pos=pos + 1)

        elif fam == "ssm":
            def body(carry, xs):
                x = carry
                wl, tsh, wst, csh = xs
                w = wl["rwkv"]
                h = layers.rms_norm(x, wl["ln1"], cfg.norm_eps)
                y, tsh2, wst2 = rwkv6.time_mix(w["tmix"], h, cfg, tsh, wst)
                x = x + y
                h = layers.rms_norm(x, wl["ln2"], cfg.norm_eps)
                y, csh2 = rwkv6.channel_mix(w["cmix"], h, csh)
                return x + y, (tsh2, wst2, csh2)

            x, ys = jax.lax.scan(
                body, x, (bl, cache["tmix_shift"], cache["wkv_state"],
                          cache["cmix_shift"]))
            new_cache = dict(cache, tmix_shift=ys[0], wkv_state=ys[1],
                             cmix_shift=ys[2], pos=pos + 1)
        else:
            raise ValueError(fam)

        logits = lm_logits(cfg, params, x, ctx)[:, 0]
        return logits, new_cache

    return decode


def _decode_attn_layer(cfg, wl, x, qpos, kc, vc, kv_pos, li, pos, barange):
    """Project k/v for this token, write into layer li of the cache, attend.

    The scatter is applied to a per-layer slice, then dynamic-update-sliced
    back into the carried stack: scattering directly into the (L, ...) stack
    makes XLA-CPU materialise a whole-cache f32 copy (scatter dtype
    promotion), which wrecks the dry-run memory fit; the slice bound keeps
    that artifact to one layer while the carry DUS stays in place."""
    h = layers.rms_norm(x, wl["ln1"], cfg.norm_eps)
    B = x.shape[0]
    k1 = (h @ wl["attn"]["wk"]).reshape(B, 1, cfg.n_kv_heads, cfg.head_dim)
    v1 = (h @ wl["attn"]["wv"]).reshape(B, 1, cfg.n_kv_heads, cfg.head_dim)
    k1 = layers.apply_rope(k1, qpos, cfg.rope)
    kc_l = jax.lax.dynamic_index_in_dim(kc, li, 0, keepdims=False)
    vc_l = jax.lax.dynamic_index_in_dim(vc, li, 0, keepdims=False)
    kc_l = kc_l.at[barange, pos].set(k1[:, 0].astype(kc_l.dtype))
    vc_l = vc_l.at[barange, pos].set(v1[:, 0].astype(vc_l.dtype))
    kc = jax.lax.dynamic_update_slice_in_dim(kc, kc_l[None], li, 0)
    vc = jax.lax.dynamic_update_slice_in_dim(vc, vc_l[None], li, 0)
    x = attn_block_decode(cfg, wl["attn"], wl["ln1"], x, qpos, kc_l, vc_l,
                          kv_pos)
    return x, kc, vc
