"""Unified model stack for the assigned architectures.

One functional implementation drives all 10 configs through a block-pattern
abstraction: the pattern (e.g. ``('rglru','rglru','lattn')``) is one scanned
*unit*; parameters are stacked ``[n_units, ...]`` and the layer loop is a
single ``lax.scan`` (constant compile time in depth — required to dry-run a
126-layer 405B model on the 512-device mesh).  Remainder blocks (pattern not
dividing n_layers) run unscanned after the stack.

Modes:
  * ``train``   — full causal forward → logits [B, S, V]
  * ``prefill`` — forward + emit per-layer caches/states, logits at last pos
  * ``decode``  — one token against caches/states

Caches are pytrees matching the pattern; attention caches are
``[B, S_cache, KV, Dh]`` with ``cache_seq → model`` sharding (flash-decoding
combine emitted by GSPMD), recurrent blocks carry constant-size states.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.distributed.sharding import shard
from . import griffin, moe as moe_mod, xlstm
from .common import (PSpec, abstract, attention, decode_attention, gelu_mlp,
                     materialize, norm, rope, sinusoidal, swiglu)


# ---------------------------------------------------------------------------
# parameter specs
# ---------------------------------------------------------------------------

def _norm_spec(cfg: ArchConfig) -> PSpec | None:
    return None if cfg.nonparam_norm else PSpec((cfg.d_model,), (None,), "zeros")


def _maybe(d: dict, key: str, spec: PSpec | None) -> None:
    if spec is not None:
        d[key] = spec


def attn_specs(cfg: ArchConfig, *, cross: bool = False) -> dict:
    d, hd = cfg.d_model, cfg.head_dim
    q, kv = cfg.q_dim, cfg.kv_dim
    s: dict = {}
    _maybe(s, "norm", _norm_spec(cfg))
    s["wq"] = PSpec((d, q), ("embed_fsdp", "heads"))
    s["wk"] = PSpec((d, kv), ("embed_fsdp", "kv"))
    s["wv"] = PSpec((d, kv), ("embed_fsdp", "kv"))
    s["wo"] = PSpec((q, d), ("heads", "embed_fsdp"))
    if cfg.qk_norm and not cross:
        s["qn"] = PSpec((hd,), (None,), "zeros")
        s["kn"] = PSpec((hd,), (None,), "zeros")
    return s


def ffn_specs(cfg: ArchConfig) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    s: dict = {}
    _maybe(s, "norm", _norm_spec(cfg))
    if cfg.family == "encdec":                      # whisper: GELU MLP
        s["w_up"] = PSpec((d, f), ("embed_fsdp", "mlp"))
        s["w_down"] = PSpec((f, d), ("mlp", "embed_fsdp"))
    else:
        s["w_gate"] = PSpec((d, f), ("embed_fsdp", "mlp"))
        s["w_up"] = PSpec((d, f), ("embed_fsdp", "mlp"))
        s["w_down"] = PSpec((f, d), ("mlp", "embed_fsdp"))
    return s


def block_specs(cfg: ArchConfig, kind: str) -> dict:
    if kind in ("attn", "lattn"):
        return {"attn": attn_specs(cfg), "ffn": ffn_specs(cfg)}
    if kind == "dattn":                              # enc-dec decoder layer
        return {"attn": attn_specs(cfg), "xattn": attn_specs(cfg, cross=True),
                "ffn": ffn_specs(cfg)}
    if kind == "xattn":                              # VLM cross-attn layer
        s = {"attn": attn_specs(cfg, cross=True), "ffn": ffn_specs(cfg)}
        s["gate"] = PSpec((1,), (None,), "zeros")    # gated residual
        return s
    if kind == "moe":
        return {"attn": attn_specs(cfg), "moe": moe_mod.moe_specs(cfg),
                "moe_norm": _norm_spec(cfg) or PSpec((cfg.d_model,), (None,), "zeros")}
    if kind == "rglru":
        return {"rec": griffin.rglru_specs(cfg), "ffn": ffn_specs(cfg)}
    if kind == "mlstm":
        return {"cell": xlstm.mlstm_specs(cfg)}
    if kind == "slstm":
        return {"cell": xlstm.slstm_specs(cfg)}
    raise ValueError(kind)


def effective_pattern(cfg: ArchConfig) -> tuple[str, ...]:
    if cfg.family == "encdec":
        return tuple("dattn" for _ in cfg.block_pattern)
    return cfg.block_pattern


def init_specs(cfg: ArchConfig) -> dict:
    """Full parameter spec tree (leaves = PSpec)."""
    pat = effective_pattern(cfg)
    unit = {f"b{i}": block_specs(cfg, k) for i, k in enumerate(pat)}
    stacked = jax.tree.map(
        lambda s: PSpec((cfg.n_units,) + s.shape, ("layers",) + s.logical,
                        s.init, s.scale),
        unit, is_leaf=lambda x: isinstance(x, PSpec))
    specs: dict = {
        "embed": PSpec((cfg.vocab, cfg.d_model), ("vocab", "embed_fsdp"),
                       scale=0.02),
        "stack": stacked,
        "lm_head": PSpec((cfg.d_model, cfg.vocab), ("embed_fsdp", "vocab")),
    }
    _maybe(specs, "final_norm", _norm_spec(cfg))
    rem = cfg.remainder_pattern
    if rem:
        specs["rem"] = {f"r{i}": block_specs(cfg, "dattn" if cfg.family ==
                                             "encdec" else k)
                        for i, k in enumerate(rem)}
    if cfg.family == "encdec":
        enc_unit = {"attn": attn_specs(cfg), "ffn": ffn_specs(cfg)}
        specs["encoder"] = {
            "stack": jax.tree.map(
                lambda s: PSpec((cfg.encoder_layers,) + s.shape,
                                ("layers",) + s.logical, s.init, s.scale),
                enc_unit, is_leaf=lambda x: isinstance(x, PSpec)),
            "final_norm": PSpec((cfg.d_model,), (None,), "zeros"),
        }
    return specs


# ---------------------------------------------------------------------------
# cache specs
# ---------------------------------------------------------------------------

def _attn_cache(cfg: ArchConfig, batch: int, seq: int, *, window: int = 0) -> dict:
    s_c = min(window, seq) if window else seq
    kl = ("batch", "cache_seq", "kv", None)
    return {"k": PSpec((batch, s_c, cfg.n_kv_heads, cfg.head_dim), kl, "zeros"),
            "v": PSpec((batch, s_c, cfg.n_kv_heads, cfg.head_dim), kl, "zeros")}


def _xattn_cache(cfg: ArchConfig, batch: int) -> dict:
    src = cfg.encoder_seq if cfg.family == "encdec" else cfg.vision_tokens
    kl = ("batch", "cache_seq", "kv", None)
    return {"xk": PSpec((batch, src, cfg.n_kv_heads, cfg.head_dim), kl, "zeros"),
            "xv": PSpec((batch, src, cfg.n_kv_heads, cfg.head_dim), kl, "zeros")}


def block_cache_specs(cfg: ArchConfig, kind: str, batch: int, seq: int) -> dict:
    if kind == "attn":
        return _attn_cache(cfg, batch, seq)
    if kind == "lattn":
        return _attn_cache(cfg, batch, seq, window=cfg.window)
    if kind == "dattn":
        return {**_attn_cache(cfg, batch, seq), **_xattn_cache(cfg, batch)}
    if kind == "xattn":
        return _xattn_cache(cfg, batch)
    if kind == "moe":
        return _attn_cache(cfg, batch, seq)
    if kind == "rglru":
        return griffin.rglru_state_specs(cfg, batch)
    if kind == "mlstm":
        return xlstm.mlstm_state_specs(cfg, batch)
    if kind == "slstm":
        return xlstm.slstm_state_specs(cfg, batch)
    raise ValueError(kind)


def cache_specs(cfg: ArchConfig, batch: int, seq: int) -> dict:
    pat = effective_pattern(cfg)
    unit = {f"b{i}": block_cache_specs(cfg, k, batch, seq)
            for i, k in enumerate(pat)}
    stacked = jax.tree.map(
        lambda s: PSpec((cfg.n_units,) + s.shape, ("layers",) + s.logical,
                        s.init, s.scale),
        unit, is_leaf=lambda x: isinstance(x, PSpec))
    out = {"stack": stacked}
    rem = cfg.remainder_pattern
    if rem:
        out["rem"] = {f"r{i}": block_cache_specs(
            cfg, "dattn" if cfg.family == "encdec" else k, batch, seq)
            for i, k in enumerate(rem)}
    return out


# ---------------------------------------------------------------------------
# block applications
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Ctx:
    cfg: ArchConfig
    mode: str                       # 'train' | 'prefill' | 'decode'
    pos: Any = None                 # decode position (scalar int32)
    enc: Any = None                 # encoder output / vision patches


def _project_qkv(p: dict, xq: jax.Array, xkv: jax.Array, cfg: ArchConfig):
    dtype = xq.dtype
    B, Sq, _ = xq.shape
    Skv = xkv.shape[1]
    q = jnp.einsum("bsd,dk->bsk", xq, p["wq"].astype(dtype)
                   ).reshape(B, Sq, cfg.n_heads, cfg.head_dim)
    k = jnp.einsum("bsd,dk->bsk", xkv, p["wk"].astype(dtype)
                   ).reshape(B, Skv, cfg.n_kv_heads, cfg.head_dim)
    v = jnp.einsum("bsd,dk->bsk", xkv, p["wv"].astype(dtype)
                   ).reshape(B, Skv, cfg.n_kv_heads, cfg.head_dim)
    if "qn" in p:
        from .common import rms_norm
        q = rms_norm(q, p["qn"])
        k = rms_norm(k, p["kn"])
    return q, k, v


def _self_attention(p: dict, x: jax.Array, ctx: Ctx, cache: dict | None,
                    *, causal: bool, window: int = 0
                    ) -> tuple[jax.Array, dict | None]:
    cfg = ctx.cfg
    dtype = x.dtype
    h = norm(x, p.get("norm"), cfg.nonparam_norm)
    new_cache = None

    if ctx.mode == "decode":
        q, k, v = _project_qkv(p, h, h, cfg)
        pos = ctx.pos
        if cfg.rope_theta:
            pvec = jnp.full((1,), pos)
            q = rope(q, pvec, cfg.rope_theta)
            k = rope(k, pvec, cfg.rope_theta)
        def upd(buf, new, at):
            # pin the updated cache to its input sharding: without the
            # constraint GSPMD replicates the whole cache around the
            # dynamic-index update (cache-size temps per layer; see §Perf)
            out = jax.lax.dynamic_update_slice(buf, new.astype(buf.dtype),
                                               (0, at, 0, 0))
            return shard(out, "batch", "cache_seq", None, None)

        if window:
            slot = jnp.mod(pos, window)
            kc = upd(cache["k"], k, slot)
            vc = upd(cache["v"], v, slot)
            W = kc.shape[1]
            valid_upto = jnp.where(pos >= W, W, pos + 1)
            out = decode_attention(q, kc, vc, valid_upto - 1)
        else:
            kc = upd(cache["k"], k, pos)
            vc = upd(cache["v"], v, pos)
            out = decode_attention(q, kc, vc, pos)
        new_cache = {"k": kc, "v": vc}
    else:
        q, k, v = _project_qkv(p, h, h, cfg)
        if cfg.rope_theta:
            pvec = jnp.arange(x.shape[1])
            q = rope(q, pvec, cfg.rope_theta)
            k = rope(k, pvec, cfg.rope_theta)
        out = attention(q, k, v, causal=causal, window=window,
                        chunk=cfg.attn_chunk)
        if ctx.mode == "prefill":
            if window and x.shape[1] > window:
                # ring-buffer alignment: position p lives at slot p % window
                shift = x.shape[1] % window
                new_cache = {"k": jnp.roll(k[:, -window:], shift, axis=1
                                           ).astype(dtype),
                             "v": jnp.roll(v[:, -window:], shift, axis=1
                                           ).astype(dtype)}
            else:
                new_cache = {"k": k.astype(dtype), "v": v.astype(dtype)}
    out = shard(out, "batch", "seq", "heads", None)
    B, Sq = out.shape[:2]
    o = jnp.einsum("bsk,kd->bsd", out.reshape(B, Sq, cfg.q_dim),
                   p["wo"].astype(dtype))
    return x + o, new_cache


def _cross_attention(p: dict, x: jax.Array, ctx: Ctx, cache: dict | None
                     ) -> tuple[jax.Array, dict | None]:
    """Cross-attn to encoder frames / vision patches.  k/v from ``ctx.enc``
    (prefill/train) or from the cache (decode)."""
    cfg = ctx.cfg
    dtype = x.dtype
    h = norm(x, p.get("norm"), cfg.nonparam_norm)
    new_cache = None
    if ctx.mode == "decode":
        B, Sq, _ = h.shape
        q = jnp.einsum("bsd,dk->bsk", h, p["wq"].astype(dtype)
                       ).reshape(B, Sq, cfg.n_heads, cfg.head_dim)
        k, v = cache["xk"], cache["xv"]
        out = decode_attention(q, k, v, k.shape[1] - 1)
        new_cache = {"xk": k, "xv": v}
    else:
        q, k, v = _project_qkv(p, h, ctx.enc.astype(dtype), cfg)
        out = attention(q, k, v, causal=False, chunk=cfg.attn_chunk)
        if ctx.mode == "prefill":
            new_cache = {"xk": k.astype(dtype), "xv": v.astype(dtype)}
    B, Sq = out.shape[:2]
    o = jnp.einsum("bsk,kd->bsd", out.reshape(B, Sq, cfg.q_dim),
                   p["wo"].astype(dtype))
    return x + o, new_cache


def _ffn(p: dict, x: jax.Array, cfg: ArchConfig) -> jax.Array:
    dtype = x.dtype
    h = norm(x, p.get("norm"), cfg.nonparam_norm)
    if cfg.family == "encdec":
        return x + gelu_mlp(h, p["w_up"].astype(dtype), p["w_down"].astype(dtype))
    return x + swiglu(h, p["w_gate"].astype(dtype), p["w_up"].astype(dtype),
                      p["w_down"].astype(dtype))


def _residual_shard(x: jax.Array, ctx: Ctx) -> jax.Array:
    """Sequence-parallel residual stream (train only): keeping the [B,S,D]
    stream seq-sharded between blocks turns the TP output all-reduces into
    reduce-scatter/all-gather pairs (~2x collective bytes saved on the
    dominant train term; EXPERIMENTS.md §Perf-I14)."""
    if ctx.mode == "train" and ctx.cfg.act_shard == "seq":
        return shard(x, "batch", "act_seq", None)
    return x


def block_apply(kind: str, p: dict, x: jax.Array, ctx: Ctx,
                cache: dict | None) -> tuple[jax.Array, dict | None]:
    cfg = ctx.cfg
    x = _residual_shard(x, ctx)
    if kind in ("attn", "moe"):
        x, c1 = _self_attention(p["attn"], x, ctx, cache, causal=True)
        x = _residual_shard(x, ctx)
        if kind == "attn":
            return _ffn(p["ffn"], x, cfg), c1
        h = norm(x, p.get("moe_norm"), cfg.nonparam_norm)
        return x + moe_mod.moe_apply(p["moe"], h, cfg), c1
    if kind == "lattn":
        x, c1 = _self_attention(p["attn"], x, ctx, cache, causal=True,
                                window=cfg.window)
        return _ffn(p["ffn"], x, cfg), c1
    if kind == "dattn":
        self_cache = None if cache is None else {k: cache[k] for k in ("k", "v")}
        x, c1 = _self_attention(p["attn"], x, ctx, self_cache, causal=True)
        xc = None if cache is None else {k: cache[k] for k in ("xk", "xv")}
        x, c2 = _cross_attention(p["xattn"], x, ctx, xc)
        x = _ffn(p["ffn"], x, cfg)
        if c1 is None and c2 is None:
            return x, None
        return x, {**(c1 or {}), **(c2 or {})}
    if kind == "xattn":
        y, c1 = _cross_attention(p["attn"], x, ctx, cache)
        gate = jnp.tanh(p["gate"].astype(x.dtype))
        x = x + gate * (y - x)                     # gated residual (VLM)
        return _ffn(p["ffn"], x, cfg), c1
    if kind == "rglru":
        x, st = (griffin.rglru_decode if ctx.mode == "decode"
                 else griffin.rglru_apply)(p["rec"], x, cfg, cache)
        return _ffn(p["ffn"], x, cfg), st
    if kind == "mlstm":
        fn = xlstm.mlstm_decode if ctx.mode == "decode" else xlstm.mlstm_apply
        return fn(p["cell"], x, cfg, cache)
    if kind == "slstm":
        fn = xlstm.slstm_decode if ctx.mode == "decode" else xlstm.slstm_apply
        return fn(p["cell"], x, cfg, cache)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# stack driver
# ---------------------------------------------------------------------------

@jax.custom_vjp
def _carry_barrier(x: jax.Array) -> jax.Array:
    return jax.lax.optimization_barrier(x)


def _carry_barrier_fwd(x):
    return jax.lax.optimization_barrier(x), None


def _carry_barrier_bwd(_, g):
    # identity cotangent: the barrier is a scheduling hint — the math is
    # identity
    return (g,)


_carry_barrier.defvjp(_carry_barrier_fwd, _carry_barrier_bwd)


def _remat_policy(cfg: ArchConfig):
    if cfg.remat == "none":
        return None
    if cfg.remat == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    return jax.checkpoint_policies.nothing_saveable


def _run_stack(params: dict, x: jax.Array, ctx: Ctx,
               caches: dict | None) -> tuple[jax.Array, dict | None]:
    cfg = ctx.cfg
    pat = effective_pattern(cfg)

    def unit(x, unit_params, unit_cache):
        if ctx.mode == "train":
            # barrier: stops XLA hoisting a convert of the whole remat-saved
            # carry stack out of the backward loop (a full-stack f32 copy —
            # observed 2x memory on the CPU pipeline; see EXPERIMENTS.md §Perf)
            x = _carry_barrier(x)
        new_cache = {}
        for i, kind in enumerate(pat):
            c = None if unit_cache is None else unit_cache[f"b{i}"]
            x, nc = block_apply(kind, unit_params[f"b{i}"], x, ctx, c)
            if nc is not None:
                new_cache[f"b{i}"] = nc
        if ctx.mode == "train" and cfg.act_shard == "seq":
            # SP carry: the remat-saved stack shards over 'model' too
            x = shard(x, "batch", "act_seq", None)
        return x, (new_cache or None)

    policy = _remat_policy(cfg)
    if policy is not None and ctx.mode == "train":
        # prevent_cse=False is the documented-safe setting under scan and
        # avoids the rematerialization barrier plumbing (EXPERIMENTS §Perf)
        unit = jax.checkpoint(unit, policy=policy, prevent_cse=False)

    if ctx.mode == "train":
        def body(carry, up):
            y, _ = unit(carry, up, None)
            return y, None
        x, _ = jax.lax.scan(body, x, params["stack"])
        new_caches = None
    elif ctx.mode == "prefill":
        def body(carry, up):
            y, nc = unit(carry, up, None)
            return y, nc
        x, stacked_cache = jax.lax.scan(body, x, params["stack"])
        new_caches = {"stack": stacked_cache}
    else:  # decode
        def body(carry, xs):
            up, uc = xs
            y, nc = unit(carry, up, uc)
            return y, nc
        x, stacked_cache = jax.lax.scan(body, x,
                                        (params["stack"], caches["stack"]))
        new_caches = {"stack": stacked_cache}

    rem = cfg.remainder_pattern
    if rem:
        rem_kinds = ["dattn" if cfg.family == "encdec" else k for k in rem]
        new_rem = {}
        for i, kind in enumerate(rem_kinds):
            c = None
            if ctx.mode == "decode":
                c = caches["rem"][f"r{i}"]
            x, nc = block_apply(kind, params["rem"][f"r{i}"], x, ctx, c)
            if nc is not None:
                new_rem[f"r{i}"] = nc
        if new_caches is not None and new_rem:
            new_caches["rem"] = new_rem
    return x, new_caches


def _run_encoder(params: dict, frames: jax.Array, cfg: ArchConfig) -> jax.Array:
    """Whisper encoder over precomputed frame embeddings (frontend stub)."""
    dtype = frames.dtype
    x = frames + sinusoidal(frames.shape[1], cfg.d_model).astype(dtype)[None]
    ctx = Ctx(cfg=cfg, mode="train")

    def body(carry, up):
        y, _ = _self_attention(up["attn"], carry, ctx, None, causal=False)
        y = _ffn(up["ffn"], y, cfg)
        return y, None

    x, _ = jax.lax.scan(body, x, params["encoder"]["stack"])
    from .common import rms_norm
    return rms_norm(x, params["encoder"]["final_norm"])


# ---------------------------------------------------------------------------
# public model API
# ---------------------------------------------------------------------------

def _embed(params: dict, tokens: jax.Array, cfg: ArchConfig,
           pos_offset: Any = None) -> jax.Array:
    dtype = jnp.dtype(cfg.compute_dtype)
    x = jnp.take(params["embed"], tokens, axis=0).astype(dtype)
    if not cfg.rope_theta:                          # sinusoidal positions
        if pos_offset is None:
            x = x + sinusoidal(tokens.shape[1], cfg.d_model).astype(dtype)[None]
        else:
            table = sinusoidal(1, cfg.d_model)      # pos handled via offset
            ang_pos = jnp.asarray(pos_offset, jnp.float32)
            d = cfg.d_model
            dim = jnp.arange(d // 2, dtype=jnp.float32)
            ang = ang_pos / (10_000.0 ** (2 * dim / d))
            pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)])[None, None, :]
            x = x + pe.astype(dtype)
            del table
    return shard(x, "batch", "seq", None)


def _enc_source(params: dict, batch: dict, cfg: ArchConfig) -> jax.Array | None:
    dtype = jnp.dtype(cfg.compute_dtype)
    if cfg.family == "encdec":
        return _run_encoder(params, batch["frames"].astype(dtype), cfg)
    if cfg.family == "vlm":
        return batch["patches"].astype(dtype)
    return None


def _logits(params: dict, x: jax.Array, cfg: ArchConfig) -> jax.Array:
    from .common import rms_norm
    if "final_norm" in params:
        x = rms_norm(x, params["final_norm"])
    elif cfg.nonparam_norm:
        from .common import layer_norm_nonparam
        x = layer_norm_nonparam(x)
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"].astype(x.dtype))
    return shard(logits, "batch", "seq", "vocab")


def forward_train(params: dict, batch: dict, cfg: ArchConfig) -> jax.Array:
    """Full causal forward → logits [B, S, V]."""
    x = _embed(params, batch["tokens"], cfg)
    ctx = Ctx(cfg=cfg, mode="train", enc=_enc_source(params, batch, cfg))
    x, _ = _run_stack(params, x, ctx, None)
    return _logits(params, x, cfg)


def forward_prefill(params: dict, batch: dict, cfg: ArchConfig
                    ) -> tuple[jax.Array, dict]:
    """Forward + caches; returns (last-position logits [B, 1, V], caches)."""
    x = _embed(params, batch["tokens"], cfg)
    ctx = Ctx(cfg=cfg, mode="prefill", enc=_enc_source(params, batch, cfg))
    x, caches = _run_stack(params, x, ctx, None)
    return _logits(params, x[:, -1:, :], cfg), caches


def forward_decode(params: dict, caches: dict, token: jax.Array,
                   pos: jax.Array, cfg: ArchConfig,
                   return_hidden: bool = False):
    """One decode step.  ``token [B, 1] int32``, ``pos`` scalar int32.
    ``return_hidden`` additionally yields the pre-logits hidden state (the
    kNN-softmax head retrieves candidates from it)."""
    x = _embed(params, token, cfg, pos_offset=pos)
    ctx = Ctx(cfg=cfg, mode="decode", pos=pos)
    x, new_caches = _run_stack(params, x, ctx, caches)
    logits = _logits(params, x, cfg)
    if return_hidden:
        return logits, new_caches, x
    return logits, new_caches


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def init_params(cfg: ArchConfig, rng: jax.Array) -> dict:
    return materialize(init_specs(cfg), rng, jnp.dtype(cfg.param_dtype))


def abstract_params(cfg: ArchConfig) -> dict:
    return abstract(init_specs(cfg), jnp.dtype(cfg.param_dtype))


def init_cache(cfg: ArchConfig, batch: int, seq: int) -> dict:
    return materialize(cache_specs(cfg, batch, seq), jax.random.PRNGKey(0),
                       jnp.dtype(cfg.compute_dtype))


def abstract_cache(cfg: ArchConfig, batch: int, seq: int) -> dict:
    return abstract(cache_specs(cfg, batch, seq), jnp.dtype(cfg.compute_dtype))


def count_params(cfg: ArchConfig) -> int:
    specs = init_specs(cfg)
    return sum(int(np.prod(s.shape)) for s in
               jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, PSpec)))
