"""build_model(cfg) -> Model: a functional bundle exposing

  specs                    parameter ParamSpec tree
  init(key)                materialize params
  train_forward(p, batch)  -> {"logits", "aux", ["mtp_logits"]}
  prefill(p, batch, max_len) -> (last_logits, cache)
  decode(p, cache, tokens, positions, ...) -> (logits, cache)
  cache_spec(batch, max_len) -> pytree of (shape, logical_axes)

Cache layouts are canonical per family (see models/attention.py docstring);
decode for scanned stacks runs jax.lax.scan over (layer_params, layer_cache).
A layer-pattern stack (Jamba) keeps two kinds of state side by side, one
entry per layer: ``{"layers": ({"ssm": ...}, ..., {"kv": ...}, ...)}``.
Where its expert layers are dropless, prefill and decode asked with
``counts=True`` return a third output beside the logits and the cache: the
call's routed assignments per held expert of each expert layer
(``Model.expert_load`` gives the shape).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import ssm as ssm_mod
from repro.models import transformer as tfm
from repro.models.common import init_params, rms_norm
from repro.parallel.sharding import with_logical_constraint


@dataclasses.dataclass
class Model:
    cfg: ModelConfig
    specs: Dict[str, Any]
    init: Callable
    train_forward: Callable
    prefill: Callable
    decode: Callable
    cache_spec: Callable
    # shape of the routed-assignment counts that prefill and decode return
    # as a third output when asked (counts=True), or None where the model
    # keeps none
    expert_load: Optional[Tuple[int, int]] = None

    def token_seq_len(self, seq_len: int) -> int:
        """Text-token count for a given total sequence length."""
        return seq_len - self.cfg.vision_tokens


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _positions(b: int, s: int) -> jax.Array:
    return jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))


def _embed_inputs(params, batch, cfg: ModelConfig):
    """Token (+modality) embedding -> (x, positions)."""
    tokens = batch["tokens"]
    x = tfm.embed_tokens(params, tokens, cfg)
    if cfg.vision_tokens:
        pe = batch["patch_embeds"].astype(x.dtype)         # (B, Nv, Dv)
        v = jnp.einsum("bnd,dk->bnk", pe, params["proj1"])
        v = jax.nn.gelu(v.astype(jnp.float32)).astype(x.dtype)
        v = jnp.einsum("bnk,kd->bnd", v, params["proj2"])
        x = jnp.concatenate([v, x], axis=1)
    b, s = x.shape[:2]
    return x, _positions(b, s)


def _kv_cache_from_prefill(kv, positions, max_len: int, window: int):
    """(k,v) stacked (L,B,S,nkv,hd) -> decode cache {"k","v","pos"}."""
    k, v = kv
    l, b, s = k.shape[:3]
    sc = min(max_len, window) if window else max_len
    if not window or s <= sc:
        pad = sc - min(s, sc)
        take = min(s, sc)
        kc = jnp.pad(k[:, :, :take], ((0, 0), (0, 0), (0, pad)) + ((0, 0),) * (k.ndim - 3))
        vc = jnp.pad(v[:, :, :take], ((0, 0), (0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 3))
        pos = jnp.pad(positions[:, :take], ((0, 0), (0, pad)), constant_values=-1)
    else:
        shift = (s - sc) % sc
        kc = jnp.roll(k[:, :, -sc:], shift, axis=2)
        vc = jnp.roll(v[:, :, -sc:], shift, axis=2)
        pos = jnp.roll(positions[:, -sc:], shift, axis=1)
    pos = jnp.broadcast_to(pos[None], (l,) + pos.shape)
    return {"k": kc, "v": vc, "pos": pos.astype(jnp.int32)}


def _mla_cache_from_prefill(kv, positions, max_len: int):
    c_kv, k_rope = kv
    l, b, s = c_kv.shape[:3]
    pad = max_len - s
    cc = jnp.pad(c_kv, ((0, 0), (0, 0), (0, pad), (0, 0)))
    rc = jnp.pad(k_rope, ((0, 0), (0, 0), (0, pad), (0, 0)))
    pos = jnp.pad(positions, ((0, 0), (0, pad)), constant_values=-1)
    pos = jnp.broadcast_to(pos[None], (l,) + pos.shape)
    return {"c_kv": cc, "k_rope": rc, "pos": pos.astype(jnp.int32)}


def _scan_decode(stack_params, stack_cache, x, cfg: ModelConfig, positions,
                 window: int):
    """Decode through a scanned layer stack with the cache held in the scan
    CARRY and updated in place at the layer index.

    Passing the cache as scan xs/ys instead forces XLA to materialize a
    fresh stacked-cache output every step (measured: ~150x the unavoidable
    cache+param traffic on deepseek-v3 decode); a carry with
    dynamic-update-slice writes is in-place eligible in the compiled while
    loop, so each iteration touches only its own layer's slice.
    """
    n_layers = jax.tree.leaves(stack_params)[0].shape[0]

    def f(carry, xs):
        x, full = carry
        lp, i = xs
        lc = jax.tree.map(lambda t: jax.lax.dynamic_index_in_dim(
            t, i, 0, keepdims=False), full)
        x, nc = tfm.layer_decode(lp, x, lc, cfg, positions=positions,
                                 window=window)
        full = jax.tree.map(
            lambda t, new: jax.lax.dynamic_update_index_in_dim(
                t, new.astype(t.dtype), i, 0), full, nc)
        return (x, full), None

    (x, new_cache), _ = jax.lax.scan(
        f, (x, stack_cache),
        (stack_params, jnp.arange(n_layers, dtype=jnp.int32)))
    return x, new_cache


def _stack_cache_spec(cfg: ModelConfig, num_layers: int, batch: int,
                      max_len: int, window: int):
    """(shape, logical) specs for one scanned stack's decode cache."""
    out: Dict[str, Any] = {}
    if cfg.attention == "gqa":
        spec = attn.init_gqa_cache_spec(cfg, batch, max_len, window)
        out["kv"] = {k: ((num_layers,) + sh, ("layers",) + lg)
                     for k, (sh, lg) in spec.items()}
    elif cfg.attention == "mla":
        spec = attn.init_mla_cache_spec(cfg, batch, max_len)
        out["kv"] = {k: ((num_layers,) + sh, ("layers",) + lg)
                     for k, (sh, lg) in spec.items()}
    if cfg.ssm is not None:
        spec = ssm_mod.init_ssm_state_spec(cfg, batch)
        out["ssm"] = {k: ((num_layers,) + sh, ("layers",) + lg)
                      for k, (sh, lg) in spec.items()}
    return out


# ---------------------------------------------------------------------------
# builder
# ---------------------------------------------------------------------------

def _pattern_cache(collected, positions, max_len: int, window: int):
    """A layer-pattern stack's per-layer (kv, ssm_state) -> decode cache."""
    layers = []
    for kv, st in collected:
        if kv is not None:
            c = _kv_cache_from_prefill(jax.tree.map(lambda t: t[None], kv),
                                       positions, max_len, window)
            layers.append({"kv": jax.tree.map(lambda t: t[0], c)})
        else:
            layers.append({"ssm": st})
    return tuple(layers)


def build_model(cfg: ModelConfig) -> Model:
    specs = tfm.model_specs(cfg)
    expert_load = None
    if cfg.attn_layer_period and cfg.is_moe and cfg.moe.dropless:
        lo, hi = cfg.moe.held_range()
        expert_load = (sum(cfg.layer_ffn(i) == "moe"
                           for i in range(cfg.num_layers)), hi - lo)

    def init(key):
        return init_params(key, specs)

    # ------------------------------ train ---------------------------------
    def train_forward(params, batch):
        if cfg.encoder_layers:
            enc_out = tfm.encoder_forward(params, batch["frames"].astype(
                jnp.dtype(cfg.dtype)), cfg)
            tokens = batch["tokens"]
            x = tfm.embed_tokens(params, tokens, cfg)
            pos = _positions(*tokens.shape)
            x, _ = tfm.encdec_decoder_forward(params, x, enc_out, cfg,
                                              positions=pos)
            return {"logits": tfm.lm_logits(params, x, cfg),
                    "aux": jnp.float32(0.0)}
        x, pos = _embed_inputs(params, batch, cfg)
        h, aux, _ = tfm.decoder_forward(params, x, cfg, positions=pos)
        out = {"aux": aux}
        if cfg.vision_tokens:
            h = h[:, cfg.vision_tokens:]
            pos = pos[:, cfg.vision_tokens:]
        out["logits"] = tfm.lm_logits(params, h, cfg)
        if cfg.mtp_depth:
            nxt = jnp.roll(batch["tokens"], -1, axis=1)
            out["mtp_logits"] = tfm.mtp_forward(params, h, nxt, cfg,
                                                positions=pos)
        return out

    # ----------------------------- prefill --------------------------------
    def prefill(params, batch, max_len: int, counts: bool = False):
        if cfg.encoder_layers:
            enc_out = tfm.encoder_forward(params, batch["frames"].astype(
                jnp.dtype(cfg.dtype)), cfg)
            tokens = batch["tokens"]
            x = tfm.embed_tokens(params, tokens, cfg)
            pos = _positions(*tokens.shape)
            x, collected = tfm.encdec_decoder_forward(
                params, x, enc_out, cfg, positions=pos, need_cache=True)
            kv, cross = collected
            cache = {"main": {
                "kv": _kv_cache_from_prefill(kv, pos, max_len, 0),
                "cross": cross,
            }}
            logits = tfm.lm_logits(params, x[:, -1:], cfg)
            return logits[:, 0], cache

        x, pos = _embed_inputs(params, batch, cfg)
        if cfg.attn_layer_period:
            h, _, collected, loads = tfm.pattern_forward(
                params, x, cfg, positions=pos, need_cache=True)
            cache = {"layers": _pattern_cache(collected, pos, max_len,
                                              cfg.sliding_window)}
            logits = tfm.lm_logits(params, h[:, -1:], cfg)
            if counts and loads is not None:
                return logits[:, 0], cache, loads
            return logits[:, 0], cache
        h, _, collected = tfm.decoder_forward(params, x, cfg, positions=pos,
                                              need_cache=True)
        cache: Dict[str, Any] = {}
        if cfg.parallel_ssm:  # hybrid: scanned stack, per-layer cache windows
            kv, st = collected["main"]
            per_layer = []
            for i in range(cfg.num_layers):
                w = tfm._layer_window(cfg, i)
                entry: Dict[str, Any] = {}
                if kv is not None:
                    one = jax.tree.map(lambda t: t[i:i + 1], kv)
                    c = _kv_cache_from_prefill(one, pos, max_len, w)
                    entry["kv"] = jax.tree.map(lambda t: t[0], c)
                if st is not None:
                    entry["ssm"] = jax.tree.map(lambda t: t[i], st)
                per_layer.append(entry)
            cache = tuple(per_layer)
        else:
            for name, c in collected.items():
                kv, st = c
                entry = {}
                if kv is not None:
                    if cfg.attention == "mla":
                        entry["kv"] = _mla_cache_from_prefill(kv, pos, max_len)
                    else:
                        entry["kv"] = _kv_cache_from_prefill(
                            kv, pos, max_len, cfg.sliding_window)
                if st is not None:
                    entry["ssm"] = st
                cache[name] = entry
        logits = tfm.lm_logits(params, h[:, -1:], cfg)
        return logits[:, 0], cache

    # ------------------------------ decode --------------------------------
    def decode(params, cache, tokens, positions, counts: bool = False):
        """tokens: (B,1) int32; positions: (B,) absolute position."""
        x = tfm.embed_tokens(params, tokens, cfg)
        new_cache: Any
        loads = None
        if cfg.attn_layer_period:
            x, layers, loads = tfm.pattern_decode(
                params, cache["layers"], x, cfg, positions=positions)
            new_cache = {"layers": layers}
        elif cfg.parallel_ssm:
            new_layers = []
            for i in range(cfg.num_layers):
                lp = jax.tree.map(lambda t: t[i], params["layers"])
                w = tfm._layer_window(cfg, i)
                x, nc = tfm.layer_decode(lp, x, cache[i], cfg,
                                         positions=positions, window=w)
                new_layers.append(nc)
            new_cache = tuple(new_layers)
        elif cfg.encoder_layers:
            x, nc = _scan_decode(params["layers"], cache["main"], x, cfg,
                                 positions, window=0)
            new_cache = {"main": nc}
        else:
            new_cache = {}
            if "layers_dense" in params:
                x, nc = _scan_decode(params["layers_dense"], cache["dense"],
                                     x, cfg, positions,
                                     window=cfg.sliding_window)
                new_cache["dense"] = nc
            x, nc = _scan_decode(params["layers"], cache["main"], x, cfg,
                                 positions, window=cfg.sliding_window)
            new_cache["main"] = nc
        logits = tfm.lm_logits(params, x, cfg)
        if counts and loads is not None:
            return logits[:, 0], new_cache, loads
        return logits[:, 0], new_cache

    # ---------------------------- cache spec -------------------------------
    def cache_spec(batch: int, max_len: int):
        if cfg.attn_layer_period:
            return {"layers": tuple(
                {"kv": attn.init_gqa_cache_spec(cfg, batch, max_len,
                                                cfg.sliding_window)}
                if cfg.layer_mixer(i) == "attn"
                else {"ssm": ssm_mod.init_ssm_state_spec(cfg, batch)}
                for i in range(cfg.num_layers))}
        if cfg.parallel_ssm:
            per_layer = []
            for i in range(cfg.num_layers):
                w = tfm._layer_window(cfg, i)
                entry = {}
                s = attn.init_gqa_cache_spec(cfg, batch, max_len, w)
                entry["kv"] = s
                entry["ssm"] = ssm_mod.init_ssm_state_spec(cfg, batch)
                per_layer.append(entry)
            return tuple(per_layer)
        if cfg.encoder_layers:
            spec = _stack_cache_spec(cfg, cfg.num_layers, batch, max_len, 0)
            nkv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
            t = cfg.encoder_seq_len
            l = cfg.num_layers
            spec["cross"] = (
                ((l, batch, t, nkv, hd),
                 ("layers", "batch", None, "act_kv_heads", "act_head_dim")),
                ((l, batch, t, nkv, hd),
                 ("layers", "batch", None, "act_kv_heads", "act_head_dim")),
            )
            return {"main": spec}
        out = {}
        if cfg.is_moe and cfg.moe.first_k_dense:
            out["dense"] = _stack_cache_spec(cfg, cfg.moe.first_k_dense, batch,
                                             max_len, cfg.sliding_window)
            out["main"] = _stack_cache_spec(
                cfg, cfg.num_layers - cfg.moe.first_k_dense, batch, max_len,
                cfg.sliding_window)
        else:
            out["main"] = _stack_cache_spec(cfg, cfg.num_layers, batch,
                                            max_len, cfg.sliding_window)
        return out

    return Model(cfg=cfg, specs=specs, init=init, train_forward=train_forward,
                 prefill=prefill, decode=decode, cache_spec=cache_spec,
                 expert_load=expert_load)
