"""Dense & MoE causal LM with scanned layers (pure JAX).

Layer parameters are stacked along a leading (n_layers,) axis and the forward
pass is a single `lax.scan` — one layer's HLO regardless of depth (compile
time and HLO size stay bounded for the 512-device dry-runs, and remat applies
per scan step).

Entry points:
  init_params(key, cfg)            real weights (smoke tests / training)
  abstract_params(cfg)             ShapeDtypeStructs (dry-run, no allocation)
  forward(params, cfg, tokens)     logits for training
  loss_fn / train-step             in train/trainer.py
  prefill / decode_step            serving with a KV cache
  param_specs(cfg, ...)            PartitionSpec pytree (2D FSDP x TP)
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import layers, moe as moe_lib
from repro.models.layers import AttentionSpec


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    d_head: int = 128
    qk_norm: bool = False
    qkv_bias: bool = False
    rope_theta: float = 500_000.0
    # MoE (None = dense)
    moe_experts: Optional[int] = None
    moe_top_k: int = 8
    moe_d_ff: Optional[int] = None
    # system
    dtype: str = "bfloat16"
    tp: int = 1                 # tensor-parallel degree (padding target)
    vocab_pad_to: int = 512
    remat: bool = True
    kv_chunk: int = 1024
    scan_unroll: int = 1        # n_layers => fully unrolled (dry-run roofline)
    # activation sharding constraints (None = none; set by the launch layer)
    batch_axes: Optional[tuple] = None
    tp_axis: Optional[str] = "model"
    moe_impl: str = "einsum"    # "einsum" | "shard_a2a" (needs mesh)
    mesh: Optional[object] = None

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    @property
    def padded_vocab(self) -> int:
        return -(-self.vocab // self.vocab_pad_to) * self.vocab_pad_to

    @property
    def attn_spec(self) -> AttentionSpec:
        return AttentionSpec(
            d_model=self.d_model, n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads, d_head=self.d_head,
            qk_norm=self.qk_norm, qkv_bias=self.qkv_bias,
            rope_theta=self.rope_theta, tp_pad_to=self.tp)

    @property
    def moe_spec(self) -> Optional[moe_lib.MoeSpec]:
        if self.moe_experts is None:
            return None
        return moe_lib.MoeSpec(
            d_model=self.d_model, d_ff=self.moe_d_ff or self.d_ff,
            n_experts=self.moe_experts, top_k=self.moe_top_k,
            ep_pad_to=self.tp, batch_axes=self.batch_axes,
            ep_axis=(self.tp_axis if self.batch_axes is not None
                     and self.tp > 1 else None),
            impl=self.moe_impl, mesh=self.mesh)

    def _constrain(self, x, *parts):
        if self.batch_axes is None:
            return x
        return jax.lax.with_sharding_constraint(x, P(*parts))

    @property
    def is_moe(self) -> bool:
        return self.moe_experts is not None

    def param_count(self) -> int:
        """Approximate true (unpadded) parameter count."""
        a = self.d_model * self.d_head * (self.n_heads * 2 + self.n_kv_heads * 2)
        if self.is_moe:
            f = 3 * self.d_model * (self.moe_d_ff or self.d_ff) * self.moe_experts
            f += self.d_model * self.moe_experts
        else:
            f = 3 * self.d_model * self.d_ff
        emb = self.vocab * self.d_model * 2
        return self.n_layers * (a + f) + emb

    def active_param_count(self) -> int:
        if not self.is_moe:
            return self.param_count()
        a = self.d_model * self.d_head * (self.n_heads * 2 + self.n_kv_heads * 2)
        f = 3 * self.d_model * (self.moe_d_ff or self.d_ff) * self.moe_top_k
        emb = self.vocab * self.d_model * 2
        return self.n_layers * (a + f) + emb


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def _layer_params(key, cfg: TransformerConfig, abstract: bool):
    ks = jax.random.split(key, 3) if not abstract else [None] * 3
    p = {
        "attn_norm": layers.make_ones((cfg.d_model,), cfg.jdtype, abstract),
        "mlp_norm": layers.make_ones((cfg.d_model,), cfg.jdtype, abstract),
        "attn": layers.attention_params(ks[0], cfg.attn_spec, cfg.jdtype,
                                        abstract),
    }
    if cfg.is_moe:
        p["moe"] = moe_lib.moe_params(ks[1], cfg.moe_spec, cfg.jdtype, abstract)
    else:
        p["mlp"] = layers.mlp_params(ks[1], cfg.d_model, cfg.d_ff, cfg.jdtype,
                                     abstract)
    return p


def _stack_layers(cfg: TransformerConfig, key, abstract: bool):
    if abstract:
        one = _layer_params(None, cfg, True)
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((cfg.n_layers,) + s.shape, s.dtype),
            one)
    keys = jax.random.split(key, cfg.n_layers)
    per = [_layer_params(k, cfg, False) for k in keys]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per)


def init_params(key, cfg: TransformerConfig):
    k_emb, k_layers, k_out = jax.random.split(key, 3)
    emb_scale = 1.0 / math.sqrt(cfg.d_model)
    return {
        "embed": layers.make_param(k_emb, (cfg.padded_vocab, cfg.d_model),
                                   cfg.jdtype, emb_scale, False),
        "layers": _stack_layers(cfg, k_layers, False),
        "final_norm": layers.make_ones((cfg.d_model,), cfg.jdtype, False),
        "unembed": layers.make_param(k_out, (cfg.d_model, cfg.padded_vocab),
                                     cfg.jdtype, emb_scale, False),
    }


def abstract_params(cfg: TransformerConfig):
    return {
        "embed": jax.ShapeDtypeStruct((cfg.padded_vocab, cfg.d_model),
                                      cfg.jdtype),
        "layers": _stack_layers(cfg, None, True),
        "final_norm": jax.ShapeDtypeStruct((cfg.d_model,), cfg.jdtype),
        "unembed": jax.ShapeDtypeStruct((cfg.d_model, cfg.padded_vocab),
                                        cfg.jdtype),
    }


# ---------------------------------------------------------------------------
# sharding specs (2D: "data" = FSDP dim, "model" = TP dim)
# ---------------------------------------------------------------------------

def decode_param_specs(cfg: TransformerConfig, *, tp_axis="model"):
    """Serving-time weight sharding: every projection sharded on its INPUT
    dim (contraction) over TP.  At decode the activations are (B, 1, .) so
    the per-projection psum is tiny, no head padding is needed (the KV cache
    keeps the true kv-head count) and the cache shards on d_head.
    """
    m = tp_axis
    attn = {"wq": P(None, m, None), "wk": P(None, m, None),
            "wv": P(None, m, None), "wo": P(None, m, None)}
    if cfg.qkv_bias:
        attn.update({"bq": P(None, None), "bk": P(None, None),
                     "bv": P(None, None)})
    if cfg.qk_norm:
        attn.update({"q_norm": P(None, None), "k_norm": P(None, None)})
    layer = {"attn_norm": P(None, None), "mlp_norm": P(None, None),
             "attn": attn}
    if cfg.is_moe:
        # input-dim sharding per expert matrix (the expert dim is NOT padded
        # at tp=1 — granite's 40 experts don't divide the mesh)
        layer["moe"] = {
            "router": P(None, None, None),
            "w_gate": P(None, None, m, None),
            "w_up": P(None, None, m, None),
            "w_down": P(None, None, m, None),
        }
    else:
        layer["mlp"] = {"w_gate": P(None, m, None), "w_up": P(None, m, None),
                        "w_down": P(None, m, None)}
    return {"embed": P(None, m), "layers": layer, "final_norm": P(None),
            "unembed": P(m, None)}


def fsdp_param_specs(cfg: TransformerConfig, axes=("data", "model")):
    """Pure FSDP: every weight sharded over ALL mesh axes on one dim, no
    tensor parallelism (use with tp=1 configs).  For batch >= devices this
    removes the per-layer TP activation all-reduces entirely; the only
    collectives left are the per-layer weight all-gathers and the gradient
    reduce-scatter (EXPERIMENTS.md §Perf, train hillclimb)."""
    fs = axes
    attn = {"wq": P(None, fs, None), "wk": P(None, fs, None),
            "wv": P(None, fs, None), "wo": P(None, fs, None)}
    if cfg.qkv_bias:
        attn.update({"bq": P(None, None), "bk": P(None, None),
                     "bv": P(None, None)})
    if cfg.qk_norm:
        attn.update({"q_norm": P(None, None), "k_norm": P(None, None)})
    layer = {"attn_norm": P(None, None), "mlp_norm": P(None, None),
             "attn": attn}
    if cfg.is_moe:
        layer["moe"] = {
            "router": P(None, fs, None),
            "w_gate": P(None, None, fs, None),
            "w_up": P(None, None, fs, None),
            "w_down": P(None, None, None, fs),
        }
    else:
        layer["mlp"] = {"w_gate": P(None, fs, None),
                        "w_up": P(None, fs, None),
                        "w_down": P(None, None, fs)}
    return {"embed": P(fs, None), "layers": layer, "final_norm": P(None),
            "unembed": P(fs, None)}


def param_specs(cfg: TransformerConfig, *, fsdp_axis="data", tp_axis="model"):
    f, m = fsdp_axis, tp_axis
    attn = {
        "wq": P(None, f, m), "wk": P(None, f, m), "wv": P(None, f, m),
        "wo": P(None, m, f),
    }
    if cfg.qkv_bias:
        attn.update({"bq": P(None, m), "bk": P(None, m), "bv": P(None, m)})
    if cfg.qk_norm:
        attn.update({"q_norm": P(None, None), "k_norm": P(None, None)})
    layer = {
        "attn_norm": P(None, None),
        "mlp_norm": P(None, None),
        "attn": attn,
    }
    if cfg.is_moe:
        layer["moe"] = {
            "router": P(None, None, None),
            "w_gate": P(None, m, f, None),
            "w_up": P(None, m, f, None),
            "w_down": P(None, m, None, f),
        }
    else:
        layer["mlp"] = {
            "w_gate": P(None, f, m), "w_up": P(None, f, m),
            "w_down": P(None, m, f),
        }
    return {
        "embed": P(m, f),
        "layers": layer,
        "final_norm": P(None),
        "unembed": P(f, m),
    }


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _block(p, x, cfg: TransformerConfig, positions, cache=None, kv_len=None):
    h, new_kv = layers.attention_fwd(
        p["attn"], layers.rms_norm(x, p["attn_norm"]), cfg.attn_spec,
        positions=positions, causal=cache is None, cache=cache,
        kv_chunk=cfg.kv_chunk)
    x = x + h
    aux = jnp.float32(0.0)
    if cfg.is_moe:
        h, aux = moe_lib.moe_fwd(p["moe"], layers.rms_norm(x, p["mlp_norm"]),
                                 cfg.moe_spec)
    else:
        h = layers.mlp_fwd(p["mlp"], layers.rms_norm(x, p["mlp_norm"]))
    return x + h, new_kv, aux


def forward(params, cfg: TransformerConfig, tokens):
    """Training forward: tokens (B, S) -> logits (B, S, padded_vocab)."""
    b, s = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0)
    x = cfg._constrain(x, cfg.batch_axes, None, None)
    positions = jnp.arange(s)[None, :]

    def scan_fn(carry, layer_p):
        x, aux = carry
        fn = lambda q, y: _block(q, y, cfg, positions)
        if cfg.remat:
            fn = jax.checkpoint(fn)
        x, _, a = fn(layer_p, x)
        x = cfg._constrain(x, cfg.batch_axes, None, None)
        return (x, aux + a), None

    (x, aux), _ = jax.lax.scan(scan_fn, (x, jnp.float32(0.0)),
                               params["layers"], unroll=cfg.scan_unroll)
    x = layers.rms_norm(x, params["final_norm"])
    logits = jnp.einsum("bsm,mv->bsv", x, params["unembed"])
    logits = cfg._constrain(logits, cfg.batch_axes, None, cfg.tp_axis)
    return logits, aux / cfg.n_layers


def pipeline_forward(params, cfg: TransformerConfig, tokens, *, mesh,
                     n_micro: int = 8, axis: str = "pod"):
    """GPipe training forward: layer stack split into mesh.shape[axis]
    stages (stacked layer params sharded P(axis) on dim 0), microbatches
    streamed with ppermute.  The pipeline region is fully manual: the
    per-microbatch batch dim shards over the remaining batch axes (when it
    divides), everything else — including any TP axis — replicates inside
    stages.  Embed/unembed run outside the pipeline (pod-replicated)."""
    from repro.models.pipeline import pipeline_apply

    b, s = tokens.shape
    assert b % n_micro == 0 and cfg.n_layers % mesh.shape[axis] == 0
    x = jnp.take(params["embed"], tokens, axis=0)
    x = cfg._constrain(x, cfg.batch_axes, None, None)
    positions = jnp.arange(s)[None, :]
    d = cfg.d_model
    xm = x.reshape(n_micro, b // n_micro, s, d)

    def stage_fn(layers_local, h):
        def scan_fn(h, lp):
            fn = lambda q, y: _block(q, y, cfg, positions)
            if cfg.remat:
                fn = jax.checkpoint(fn)
            h, _, _ = fn(lp, h)
            return h, None

        h, _ = jax.lax.scan(scan_fn, h, layers_local,
                            unroll=cfg.scan_unroll)
        return h

    # shard the per-microbatch batch dim over the non-pipeline batch axes
    # when it divides evenly; remaining axes (e.g. TP) replicate inside the
    # manual pipeline region.
    rest = tuple(a for a in (cfg.batch_axes or ()) if a != axis)
    mb = b // n_micro
    rest_devices = math.prod(mesh.shape[a] for a in rest) if rest else 1
    mb_spec = rest if rest and mb % rest_devices == 0 else None
    out = pipeline_apply(params["layers"], xm, stage_fn, mesh=mesh,
                         axis=axis, inner_specs=P(None, mb_spec, None, None))
    x = out.reshape(b, s, d)
    x = layers.rms_norm(x, params["final_norm"])
    logits = jnp.einsum("bsm,mv->bsv", x, params["unembed"])
    logits = cfg._constrain(logits, cfg.batch_axes, None, cfg.tp_axis)
    return logits, jnp.float32(0.0)


def pipeline_loss_fn(params, cfg: TransformerConfig, tokens, targets, *,
                     mesh, n_micro: int = 8, axis: str = "pod"):
    logits, aux = pipeline_forward(params, cfg, tokens, mesh=mesh,
                                   n_micro=n_micro, axis=axis)
    logits = logits.astype(jnp.float32)
    mask = jnp.arange(logits.shape[-1]) < cfg.vocab
    logits = jnp.where(mask[None, None, :], logits, -1e30)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(nll)


# ---------------------------------------------------------------------------
# serving: prefill + single-token decode with a preallocated KV cache
# ---------------------------------------------------------------------------

def init_cache(cfg: TransformerConfig, batch: int, max_len: int,
               abstract: bool = False):
    spec = cfg.attn_spec
    shape = (cfg.n_layers, batch, max_len, spec.padded_kv_heads, spec.d_head)
    if abstract:
        k = jax.ShapeDtypeStruct(shape, cfg.jdtype)
        return {"k": k, "v": k, "len": jax.ShapeDtypeStruct((), jnp.int32)}
    return {"k": jnp.zeros(shape, cfg.jdtype), "v": jnp.zeros(shape, cfg.jdtype),
            "len": jnp.int32(0)}


def cache_specs(cfg: TransformerConfig, *, batch_axes=("data",),
                tp_axis="model"):
    """KV cache sharding: batch over data axes, head_dim over TP (GQA-safe
    for any kv_heads; see DESIGN.md)."""
    kv = P(None, batch_axes, None, None, tp_axis)
    return {"k": kv, "v": kv, "len": P()}


def decode_step(params, cfg: TransformerConfig, tokens, cache):
    """tokens (B, 1) + cache -> (logits (B, vocab), new cache)."""
    b, s = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0)
    positions = cache["len"] + jnp.arange(s)[None, :]

    def scan_fn(carry, inp):
        x = carry
        layer_p, ck, cv = inp
        x, (nk, nv), _ = _block(layer_p, x, cfg, positions,
                                cache=(ck, cv, cache["len"]))
        return x, (nk, nv)

    x, (nk, nv) = jax.lax.scan(scan_fn, x,
                               (params["layers"], cache["k"], cache["v"]),
                               unroll=cfg.scan_unroll)
    x = layers.rms_norm(x, params["final_norm"])
    logits = jnp.einsum("bsm,mv->bsv", x[:, -1:, :], params["unembed"])
    logits = cfg._constrain(logits, cfg.batch_axes, None, cfg.tp_axis)
    new_cache = {"k": nk, "v": nv, "len": cache["len"] + s}
    return logits[:, 0, :], new_cache


def prefill(params, cfg: TransformerConfig, tokens, max_len: int):
    """Full-sequence prefill building the cache; returns (logits, cache)."""
    b, s = tokens.shape
    cache = init_cache(cfg, b, max_len)
    x = jnp.take(params["embed"], tokens, axis=0)
    positions = jnp.arange(s)[None, :]

    def scan_fn(carry, inp):
        x = carry
        layer_p, ck, cv = inp
        x, (nk, nv), _ = _block(layer_p, x, cfg, positions)
        ck = jax.lax.dynamic_update_slice(ck, nk.astype(ck.dtype), (0, 0, 0, 0))
        cv = jax.lax.dynamic_update_slice(cv, nv.astype(cv.dtype), (0, 0, 0, 0))
        return x, (ck, cv)

    x, (nk, nv) = jax.lax.scan(scan_fn, x,
                               (params["layers"], cache["k"], cache["v"]),
                               unroll=cfg.scan_unroll)
    x = layers.rms_norm(x, params["final_norm"])
    logits = jnp.einsum("bsm,mv->bsv", x, params["unembed"])
    logits = cfg._constrain(logits, cfg.batch_axes, None, cfg.tp_axis)
    return logits, {"k": nk, "v": nv, "len": jnp.int32(s)}


def loss_fn(params, cfg: TransformerConfig, tokens, targets, *,
            aux_weight: float = 0.01):
    logits, aux = forward(params, cfg, tokens)
    logits = logits.astype(jnp.float32)
    mask = jnp.arange(logits.shape[-1]) < cfg.vocab
    logits = jnp.where(mask[None, None, :], logits, -1e30)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(nll) + aux_weight * aux


__all__ = [
    "TransformerConfig", "init_params", "abstract_params", "param_specs",
    "forward", "init_cache", "cache_specs", "decode_step", "prefill",
    "loss_fn",
]
