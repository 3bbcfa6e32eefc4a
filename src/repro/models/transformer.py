"""Decoder / enc-dec / hybrid transformer assembly for every architecture.

One layer definition parameterized by (attention kind, ffn kind, parallel-SSM
flag); uniform stacks run under jax.lax.scan with remat (compact HLO, O(1)
compile in depth), heterogeneous stacks (hymba's per-layer global/SWA mix,
DeepSeek-V3's dense->MoE split) unroll or split into homogeneous sub-stacks.
A layer-pattern stack (Jamba: Mamba or attention, dense or expert FFN, by
layer index) is a tuple of per-layer parameter dicts, run unrolled; a
layer's mixers are the ones its parameters hold.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.common import (ParamSpec, dense_ffn, rms_norm, stack_specs)
from repro.parallel.sharding import with_logical_constraint

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Specs
# ---------------------------------------------------------------------------

def dense_ffn_specs(cfg: ModelConfig, d_ff: int) -> Dict[str, ParamSpec]:
    d = cfg.d_model
    specs = {
        "w_up": ParamSpec((d, d_ff), ("embed", "mlp"), "scaled"),
        "w_down": ParamSpec((d_ff, d), ("mlp", "embed"), "scaled"),
    }
    if cfg.ffn_act == "swiglu":
        specs["w_gate"] = ParamSpec((d, d_ff), ("embed", "mlp"), "scaled")
    return specs


def layer_specs(cfg: ModelConfig, ffn: str = "dense",
                d_ff: Optional[int] = None,
                mixer: Optional[str] = None) -> Dict[str, Any]:
    """One layer's specs; `mixer` ("attn" or "ssm") keeps only that one of
    the config's mixers (a layer-pattern stack)."""
    d = cfg.d_model
    specs: Dict[str, Any] = {"norm1": ParamSpec((d,), ("embed",), "ones")}
    if mixer != "ssm" and cfg.attention == "gqa":
        specs["attn"] = attn.gqa_specs(cfg)
    elif mixer != "ssm" and cfg.attention == "mla":
        specs["attn"] = attn.mla_specs(cfg)
    if cfg.ssm is not None and mixer != "attn":
        specs["ssm"] = ssm_mod.ssm_specs(cfg)
        if cfg.parallel_ssm:
            specs["ssm_norm"] = ParamSpec((d,), ("embed",), "ones")
            specs["attn_norm"] = ParamSpec((d,), ("embed",), "ones")
    if ffn == "dense" and (d_ff or cfg.d_ff):
        specs["norm2"] = ParamSpec((d,), ("embed",), "ones")
        specs["ffn"] = dense_ffn_specs(cfg, d_ff or cfg.d_ff)
    elif ffn == "moe":
        specs["norm2"] = ParamSpec((d,), ("embed",), "ones")
        specs["moe"] = moe_mod.moe_specs(cfg)
    return specs


def encoder_layer_specs(cfg: ModelConfig) -> Dict[str, Any]:
    d = cfg.d_model
    return {
        "norm1": ParamSpec((d,), ("embed",), "ones"),
        "attn": attn.gqa_specs(cfg),
        "norm2": ParamSpec((d,), ("embed",), "ones"),
        "ffn": dense_ffn_specs(cfg, cfg.d_ff),
    }


def decoder_xattn_layer_specs(cfg: ModelConfig) -> Dict[str, Any]:
    specs = layer_specs(cfg, ffn="dense")
    specs["norm_x"] = ParamSpec((cfg.d_model,), ("embed",), "ones")
    specs["xattn"] = attn.gqa_specs(cfg)
    return specs


def model_specs(cfg: ModelConfig) -> Dict[str, Any]:
    d, v = cfg.d_model, cfg.vocab_size
    specs: Dict[str, Any] = {
        "embed": ParamSpec((v, d), ("vocab", "embed"), "normal", scale=0.02),
        "final_norm": ParamSpec((d,), ("embed",), "ones"),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = ParamSpec((d, v), ("embed", "vocab"), "scaled")

    if cfg.encoder_layers:  # enc-dec (whisper)
        specs["enc_layers"] = stack_specs(encoder_layer_specs(cfg), cfg.encoder_layers)
        specs["enc_norm"] = ParamSpec((d,), ("embed",), "ones")
        specs["layers"] = stack_specs(decoder_xattn_layer_specs(cfg), cfg.num_layers)
        return specs

    if cfg.vision_tokens:  # vlm projector (stubbed ViT -> LM)
        dv = cfg.vision_embed_dim
        specs["proj1"] = ParamSpec((dv, d), (None, "embed"), "scaled")
        specs["proj2"] = ParamSpec((d, d), ("embed", None), "scaled")

    if cfg.attn_layer_period:
        specs["layers"] = tuple(
            layer_specs(cfg, ffn=cfg.layer_ffn(i), mixer=cfg.layer_mixer(i))
            for i in range(cfg.num_layers))
    elif cfg.is_moe and cfg.moe.first_k_dense:
        dense_ff = cfg.moe.first_dense_d_ff or cfg.d_ff
        specs["layers_dense"] = stack_specs(
            layer_specs(cfg, ffn="dense", d_ff=dense_ff), cfg.moe.first_k_dense)
        specs["layers"] = stack_specs(
            layer_specs(cfg, ffn="moe"), cfg.num_layers - cfg.moe.first_k_dense)
    elif cfg.is_moe:
        specs["layers"] = stack_specs(layer_specs(cfg, ffn="moe"), cfg.num_layers)
    else:
        # hybrids scan too: per-layer window is scanned *data* (see
        # decoder_forward), so heterogeneous SWA/global mixes stay compact
        specs["layers"] = stack_specs(layer_specs(cfg, ffn="dense"), cfg.num_layers)

    if cfg.mtp_depth:  # DeepSeek-V3 multi-token prediction module
        dense_ff = (cfg.moe.first_dense_d_ff if cfg.is_moe else 0) or cfg.d_ff
        specs["mtp"] = {
            "norm_h": ParamSpec((d,), ("embed",), "ones"),
            "norm_e": ParamSpec((d,), ("embed",), "ones"),
            "proj": ParamSpec((2 * d, d), (None, "embed"), "scaled"),
            "layer": layer_specs(cfg, ffn="dense", d_ff=dense_ff),
            "final_norm": ParamSpec((d,), ("embed",), "ones"),
        }
    return specs


# ---------------------------------------------------------------------------
# Layer application
# ---------------------------------------------------------------------------

def _layer_window(cfg: ModelConfig, layer_idx: Optional[int]) -> int:
    if layer_idx is not None and layer_idx in cfg.global_attn_layers:
        return 0
    return cfg.sliding_window


def _ffn(lp: Params, x: jax.Array, cfg: ModelConfig, loads):
    """The layer's FFN residual step -> (x, aux).  A dropless expert layer
    appends its routed-assignment counts to `loads` where one is given
    (serving), and computes its load-balance loss only where none is
    (training)."""
    aux = jnp.float32(0.0)
    if "ffn" in lp:
        h2 = rms_norm(x, lp["norm2"], cfg.norm_eps)
        x = x + dense_ffn(h2, lp["ffn"], cfg.ffn_act).astype(x.dtype)
    elif "moe" in lp:
        h2 = rms_norm(x, lp["norm2"], cfg.norm_eps)
        if cfg.moe.dropless:
            y, aux, counts = moe_mod.dropless_moe(lp["moe"], h2, cfg,
                                                  aux=loads is None)
            if loads is not None:
                loads.append(counts)
        else:
            y, aux = moe_mod.moe_ffn(lp["moe"], h2, cfg)
        x = x + y.astype(x.dtype)
    return x, aux


def layer_forward(lp: Params, x: jax.Array, cfg: ModelConfig, *, positions,
                  window: int, ffn: str, need_cache: bool = False,
                  ssm_state=None, loads: Optional[list] = None):
    """Full-sequence layer. Returns (x, aux, cache_contrib)."""
    h = rms_norm(x, lp["norm1"], cfg.norm_eps)
    cache_kv = None
    branch = 0.0
    if "attn" in lp and cfg.attention == "gqa":
        a = attn.gqa_forward(lp["attn"], h, cfg=cfg, positions=positions,
                             window=window)
        if cfg.parallel_ssm:
            a = rms_norm(a, lp["attn_norm"], cfg.norm_eps)
        branch = branch + a
        if need_cache:
            cache_kv = attn.gqa_prefill_kv(lp["attn"], h, cfg=cfg,
                                           positions=positions)
    elif "attn" in lp and cfg.attention == "mla":
        branch = branch + attn.mla_forward(lp["attn"], h, cfg=cfg,
                                           positions=positions)
        if need_cache:
            _, _, c_kv, k_rope = attn._mla_qkv_latent(lp["attn"], h, cfg=cfg,
                                                      positions=positions)
            cache_kv = (c_kv, k_rope)
    new_ssm_state = None
    if "ssm" in lp:
        if need_cache or ssm_state is not None:
            s_out, new_ssm_state = ssm_mod.mamba_forward(
                lp["ssm"], h, cfg, state=ssm_state, return_state=True)
        else:
            s_out = ssm_mod.mamba_forward(lp["ssm"], h, cfg)
        if cfg.parallel_ssm:
            s_out = rms_norm(s_out, lp["ssm_norm"], cfg.norm_eps)
            branch = 0.5 * (branch + s_out)
        else:
            branch = branch + s_out
    x = x + branch.astype(x.dtype)
    x = with_logical_constraint(x, "batch", "seq", "act_embed")
    x, aux = _ffn(lp, x, cfg, loads)
    x = with_logical_constraint(x, "batch", "seq", "act_embed")
    return x, aux, (cache_kv, new_ssm_state)


def layer_decode(lp: Params, x: jax.Array, cache, cfg: ModelConfig, *,
                 positions, window: int, loads: Optional[list] = None):
    """One-token layer step. cache: dict possibly holding kv/ssm/cross caches."""
    h = rms_norm(x, lp["norm1"], cfg.norm_eps)
    new_cache = dict(cache)
    branch = 0.0
    if "xattn" in lp:  # enc-dec decoder layer: self-attn then cross-attn
        a, kv = attn.gqa_decode(lp["attn"], h, cache["kv"], cfg=cfg,
                                positions=positions, window=window)
        x = x + a.astype(x.dtype)
        new_cache["kv"] = kv
        hx = rms_norm(x, lp["norm_x"], cfg.norm_eps)
        ek, ev = cache["cross"]
        x = x + attn.cross_attention(lp["xattn"], hx, ek, ev,
                                     cfg=cfg).astype(x.dtype)
        h2 = rms_norm(x, lp["norm2"], cfg.norm_eps)
        x = x + dense_ffn(h2, lp["ffn"], cfg.ffn_act).astype(x.dtype)
        return x, new_cache
    if "attn" in lp and cfg.attention == "gqa":
        a, kv = attn.gqa_decode(lp["attn"], h, cache["kv"], cfg=cfg,
                                positions=positions, window=window)
        if cfg.parallel_ssm:
            a = rms_norm(a, lp["attn_norm"], cfg.norm_eps)
        branch = branch + a
        new_cache["kv"] = kv
    elif "attn" in lp and cfg.attention == "mla":
        a, kv = attn.mla_decode(lp["attn"], h, cache["kv"], cfg=cfg,
                                positions=positions)
        branch = branch + a
        new_cache["kv"] = kv
    if "ssm" in lp:
        s_out, st = ssm_mod.mamba_decode(lp["ssm"], h, cache["ssm"], cfg)
        if cfg.parallel_ssm:
            s_out = rms_norm(s_out, lp["ssm_norm"], cfg.norm_eps)
            branch = 0.5 * (branch + s_out)
        else:
            branch = branch + s_out
        new_cache["ssm"] = st
    x = x + branch.astype(x.dtype)
    x, _ = _ffn(lp, x, cfg, loads)
    return x, new_cache


# ---------------------------------------------------------------------------
# Stacks
# ---------------------------------------------------------------------------

def _scan_stack(stack_params, x, body, cfg: ModelConfig,
                need_cache: bool = False, per_layer=None):
    """lax.scan over a homogeneous stacked layer group; accumulates aux and
    (optionally) collects per-layer cache contributions as stacked ys.
    ``per_layer``: extra scanned inputs (e.g. per-layer window widths)."""
    def f(carry, xs):
        lp, extra = xs
        x, aux = carry
        x, a, cache = body(lp, x, extra)
        return (x, aux + a), (cache if need_cache else None)

    f = jax.checkpoint(f, policy=_remat_policy(cfg))
    if per_layer is None:
        per_layer = jnp.zeros((jax.tree.leaves(stack_params)[0].shape[0],),
                              jnp.int32)
    (x, aux), caches = jax.lax.scan(f, (x, jnp.float32(0.0)),
                                    (stack_params, per_layer))
    return x, aux, caches


def _remat_policy(cfg: ModelConfig):
    if cfg.remat == "dots":
        return jax.checkpoint_policies.checkpoint_dots
    if cfg.remat == "none":
        return jax.checkpoint_policies.everything_saveable
    return None  # full remat


def layer_windows(cfg: ModelConfig) -> jnp.ndarray:
    """Per-layer attention window (0 = full), as scanned data."""
    return jnp.array([_layer_window(cfg, i) for i in range(cfg.num_layers)],
                     jnp.int32)


def pattern_forward(params: Params, x: jax.Array, cfg: ModelConfig, *,
                    positions, need_cache: bool = False):
    """A layer-pattern stack, unrolled -> (hidden, aux, caches, loads):
    with `need_cache` (prefill), caches a (kv, ssm_state) pair per layer
    and loads the expert layers' routed-assignment counts stacked (n_moe,
    held); loads is None without it or where no layer has dropless
    experts."""
    aux = jnp.float32(0.0)
    caches = []
    loads = [] if need_cache else None
    for i, lp in enumerate(params["layers"]):
        x, a, c = layer_forward(lp, x, cfg, positions=positions,
                                window=cfg.sliding_window,
                                ffn=cfg.layer_ffn(i), need_cache=need_cache,
                                loads=loads)
        aux += a
        caches.append(c)
    return (x, aux, tuple(caches) if need_cache else None,
            jnp.stack(loads) if loads else None)


def pattern_decode(params: Params, caches, x: jax.Array, cfg: ModelConfig,
                   *, positions):
    """One token through a layer-pattern stack -> (x, caches, loads)."""
    new, loads = [], []
    for lp, c in zip(params["layers"], caches):
        x, nc = layer_decode(lp, x, c, cfg, positions=positions,
                             window=cfg.sliding_window, loads=loads)
        new.append(nc)
    return x, tuple(new), jnp.stack(loads) if loads else None


def decoder_forward(params: Params, x: jax.Array, cfg: ModelConfig, *,
                    positions, need_cache: bool = False):
    """Runs the decoder stack on embedded inputs -> (hidden, aux, caches)."""
    if cfg.attn_layer_period:
        x, aux, caches, _ = pattern_forward(params, x, cfg,
                                            positions=positions,
                                            need_cache=need_cache)
        return x, aux, caches
    aux = jnp.float32(0.0)
    caches: Any = {}
    if "layers_dense" in params:
        body = lambda lp, x, w: layer_forward(
            lp, x, cfg, positions=positions, window=cfg.sliding_window,
            ffn="dense", need_cache=need_cache)
        x, a, c = _scan_stack(params["layers_dense"], x, body, cfg,
                              need_cache)
        aux += a
        caches["dense"] = c
    ffn = "moe" if cfg.is_moe else "dense"
    per_layer = layer_windows(cfg) if cfg.global_attn_layers else None
    if per_layer is not None:
        body = lambda lp, x, w: layer_forward(
            lp, x, cfg, positions=positions, window=w, ffn=ffn,
            need_cache=need_cache)
    else:
        body = lambda lp, x, w: layer_forward(
            lp, x, cfg, positions=positions, window=cfg.sliding_window,
            ffn=ffn, need_cache=need_cache)
    x, a, c = _scan_stack(params["layers"], x, body, cfg, need_cache,
                          per_layer=per_layer)
    aux += a
    caches["main"] = c
    if not need_cache:
        caches = None
    return x, aux, caches


def encoder_forward(params: Params, frames: jax.Array, cfg: ModelConfig):
    """Whisper-style encoder over (stubbed) frame embeddings (B,T,d)."""
    b, t = frames.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))

    def f(x, lp):
        h = rms_norm(x, lp["norm1"], cfg.norm_eps)
        x = x + attn.encoder_attention(lp["attn"], h, cfg=cfg,
                                       positions=positions).astype(x.dtype)
        h2 = rms_norm(x, lp["norm2"], cfg.norm_eps)
        x = x + dense_ffn(h2, lp["ffn"], cfg.ffn_act).astype(x.dtype)
        return x, None

    x, _ = jax.lax.scan(jax.checkpoint(f, policy=_remat_policy(cfg)),
                        frames, params["enc_layers"])
    return rms_norm(x, params["enc_norm"], cfg.norm_eps)


def encdec_decoder_forward(params: Params, x: jax.Array, enc_out: jax.Array,
                           cfg: ModelConfig, *, positions,
                           need_cache: bool = False):
    """Whisper decoder: self-attn + cross-attn + ffn per layer (scanned)."""
    def f(carry, lp):
        x = carry
        h = rms_norm(x, lp["norm1"], cfg.norm_eps)
        x = x + attn.gqa_forward(lp["attn"], h, cfg=cfg, positions=positions,
                                 window=0).astype(x.dtype)
        hx = rms_norm(x, lp["norm_x"], cfg.norm_eps)
        ek, ev = attn.cross_kv(lp["xattn"], enc_out)
        x = x + attn.cross_attention(lp["xattn"], hx, ek, ev,
                                     cfg=cfg).astype(x.dtype)
        h2 = rms_norm(x, lp["norm2"], cfg.norm_eps)
        x = x + dense_ffn(h2, lp["ffn"], cfg.ffn_act).astype(x.dtype)
        outs = None
        if need_cache:
            kv = attn.gqa_prefill_kv(lp["attn"], h, cfg=cfg, positions=positions)
            outs = (kv, (ek, ev))
        return x, outs

    x, caches = jax.lax.scan(jax.checkpoint(f, policy=_remat_policy(cfg)),
                             x, params["layers"])
    return x, caches


def embed_tokens(params: Params, tokens: jax.Array, cfg: ModelConfig):
    x = jnp.take(params["embed"], tokens, axis=0).astype(
        jnp.dtype(cfg.dtype))
    return with_logical_constraint(x, "batch", "seq", "act_embed")


def lm_logits(params: Params, x: jax.Array, cfg: ModelConfig):
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", x, head)
    return with_logical_constraint(logits, "batch", "seq", "act_vocab")


def mtp_forward(params: Params, h: jax.Array, tokens: jax.Array,
                cfg: ModelConfig, *, positions):
    """DeepSeek-V3 MTP (depth 1): combine final hidden h_t with embedding of
    token_{t+1}; the shared head then predicts token_{t+2}."""
    mp = params["mtp"]
    emb_next = embed_tokens(params, tokens, cfg)           # (B,S,d) of t+1 toks
    h_n = rms_norm(h, mp["norm_h"], cfg.norm_eps)
    e_n = rms_norm(emb_next, mp["norm_e"], cfg.norm_eps)
    z = jnp.einsum("bsd,dk->bsk", jnp.concatenate([h_n, e_n], axis=-1),
                   mp["proj"])
    z, _, _ = layer_forward(mp["layer"], z, cfg, positions=positions,
                            window=cfg.sliding_window, ffn="dense")
    z = rms_norm(z, mp["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", z, head)
    return with_logical_constraint(logits, "batch", "seq", "act_vocab")
