"""Generic causal LM covering the dense / MoE / VLM / gemma2 / rwkv / hybrid
families via a *grouped layer scan*.

Every architecture is expressed as ``n_groups`` repetitions of a small
group of sub-blocks (+ an optional ragged tail), so the whole stack lowers
to one ``lax.scan`` with stacked parameters — tiny HLO even for 94-layer
models, uniform sharding specs, and natural per-group remat:

  dense / moe      group = ("attn",)                      x L
  gemma2           group = ("attn_local", "attn_global")  x L/2
  llama-vision     group = ("attn",)*5 + ("cross",)       x L/5
  rwkv6            group = ("rwkv",)                      x L
  zamba2           group = ("mamba",)*k + ("shared_attn",) x L//k, tail L%k

"shared_attn" weights are shared across groups (zamba2); its KV caches are
per-invocation (stacked over groups).  "cross" layers carry their own
stacked weights and attend to frozen image-embedding K/V.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention, layers, mamba2, mlp, moe, rwkv6
from repro.parallel import ctx as pctx


# ---------------------------------------------------------------------------
# Layer plan
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LayerPlan:
    group_kinds: tuple[str, ...]
    n_groups: int
    tail_kinds: tuple[str, ...] = ()


def layer_plan(cfg: ModelConfig) -> LayerPlan:
    if cfg.rwkv:
        return LayerPlan(("rwkv",), cfg.num_layers)
    if cfg.family in ("ssm", "hybrid") and cfg.ssm_state:
        if cfg.attn_every:
            k = cfg.attn_every
            n = cfg.num_layers // k
            tail = cfg.num_layers - n * k
            return LayerPlan(("mamba",) * k + ("shared_attn",), n,
                             ("mamba",) * tail)
        return LayerPlan(("mamba",), cfg.num_layers)
    if cfg.cross_attn_every:
        k = cfg.cross_attn_every
        assert cfg.num_layers % k == 0
        return LayerPlan(("attn",) * k + ("cross",), cfg.num_layers // k)
    if cfg.attn_pattern == "local_global":
        assert cfg.num_layers % 2 == 0
        return LayerPlan(("attn_local", "attn_global"), cfg.num_layers // 2)
    return LayerPlan(("attn",), cfg.num_layers)


def _attn_cfg(cfg: ModelConfig, kind: str) -> attention.AttnConfig:
    window = cfg.window if kind == "attn_local" else None
    if kind == "shared_attn" and cfg.family == "hybrid":
        window = cfg.window  # zamba2 long-context posture (DESIGN §4)
    return attention.AttnConfig(
        d_model=cfg.d_model, num_heads=cfg.num_heads,
        num_kv_heads=cfg.num_kv_heads, head_dim=cfg.resolved_head_dim,
        qkv_bias=cfg.qkv_bias, logit_softcap=cfg.attn_softcap,
        window=window, causal=True, rope_theta=cfg.rope_theta,
        use_rope=kind != "cross", dtype=cfg.dtype,
        tp_expand_heads=cfg.attn_tp_expand,
        bf16_score_grad=cfg.attn_bf16_score_grad)


def _norm_init(cfg, d=None):
    d = d or cfg.d_model
    return (layers.rmsnorm_init(d, jnp.dtype(cfg.dtype))
            if cfg.norm == "rmsnorm"
            else layers.layernorm_init(d, jnp.dtype(cfg.dtype)))


def _norm(cfg, p, x):
    return (layers.rmsnorm(p, x) if cfg.norm == "rmsnorm"
            else layers.layernorm(p, x))


def _moe_cfg(cfg: ModelConfig) -> moe.MoEConfig:
    return moe.MoEConfig(
        d_model=cfg.d_model, d_expert=cfg.d_expert,
        num_experts=cfg.num_experts, top_k=cfg.top_k,
        num_shared_experts=cfg.num_shared_experts,
        activation=cfg.activation, dtype=cfg.dtype,
        capacity_factor=cfg.moe_capacity_factor,
        bf16_combine=cfg.moe_bf16_combine,
        scoring=cfg.moe_scoring, n_group=cfg.moe_n_group,
        topk_group=cfg.moe_topk_group,
        routed_scaling_factor=cfg.moe_routed_scaling_factor)


# ---------------------------------------------------------------------------
# Sub-block init / apply
# ---------------------------------------------------------------------------


def _sub_init(key, cfg: ModelConfig, kind: str) -> dict:
    dt = jnp.dtype(cfg.dtype)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    if kind == "rwkv":
        rc = rwkv6.RWKVConfig(d_model=cfg.d_model, d_ff=cfg.d_ff,
                              dtype=cfg.dtype)
        return {"norm1": _norm_init(cfg), "norm2": _norm_init(cfg),
                "mix": rwkv6.init(k1, rc)}
    if kind == "mamba":
        mc = mamba2.Mamba2Config(d_model=cfg.d_model, state_dim=cfg.ssm_state,
                                 head_dim=cfg.ssm_head_dim,
                                 chunk=cfg.ssm_chunk, dtype=cfg.dtype)
        return {"norm": _norm_init(cfg), "ssm": mamba2.init(k1, mc)}
    p = {"norm1": _norm_init(cfg),
         "attn": attention.init(k1, _attn_cfg(cfg, kind)),
         "norm2": _norm_init(cfg)}
    if kind == "cross":
        p["ffn"] = mlp.init(k2, cfg.d_model, cfg.d_ff, dt, cfg.activation)
        p["gate_attn"] = jnp.zeros((), jnp.float32)
        p["gate_ffn"] = jnp.zeros((), jnp.float32)
    elif kind == "shared_attn" or not cfg.is_moe:
        p["ffn"] = mlp.init(k2, cfg.d_model, cfg.d_ff, dt, cfg.activation)
    else:
        p["ffn"] = moe.init(k2, _moe_cfg(cfg))
    return p


def _ffn_apply(cfg: ModelConfig, p, h, kind: str):
    """Returns (out, aux, dispatch_ids or None)."""
    if kind in ("cross", "shared_attn") or not cfg.is_moe:
        return mlp.apply(p, h, cfg.activation), 0.0, None
    mesh_ctx = pctx.current()
    mcfg = _moe_cfg(cfg)
    if mesh_ctx is None:
        b, s, d = h.shape
        out, aux, disp = moe.apply_local(p, h.reshape(b * s, d), mcfg)
        return out.reshape(b, s, d), aux, disp
    if mcfg.use_ep:
        out, aux, disp = moe.apply_ep(
            p, h, mcfg, mesh_ctx.mesh, data_axes=mesh_ctx.data_axes,
            tp_axis=mesh_ctx.tp_axis,
            ep_axis=mesh_ctx.data_axes[-1])
    else:
        out, aux, disp = moe.apply_sharded(
            p, h, mcfg, mesh_ctx.mesh, data_axes=mesh_ctx.data_axes,
            tp_axis=mesh_ctx.tp_axis)
    return out, aux, disp


def _sub_apply(cfg: ModelConfig, kind: str, p: dict, h: jnp.ndarray,
               *, mode: str, cache: Optional[dict], positions,
               image_embeds=None, kv_block=None, q_block=None):
    """One sub-block.  Returns (h, aux, new_cache, dispatch_ids)."""
    aux = 0.0
    disp = None
    if kind == "rwkv":
        rc = rwkv6.RWKVConfig(d_model=cfg.d_model, d_ff=cfg.d_ff,
                              dtype=cfg.dtype)
        if mode == "decode":
            tm, st = rwkv6.time_mix_decode(
                p["mix"], _norm(cfg, p["norm1"], h),
                {"s": cache["s"], "last": cache["last"]}, rc)
            h = h + tm
            x2 = _norm(cfg, p["norm2"], h)
            cm = rwkv6.channel_mix(p["mix"], x2, last=cache["cm_last"])
            h = h + cm
            new_cache = {"s": st["s"], "last": st["last"],
                         "cm_last": x2[:, 0, :]}
            return h, aux, new_cache, disp
        x1 = _norm(cfg, p["norm1"], h)
        h = h + rwkv6.time_mix(p["mix"], x1, rc, impl=cfg.rwkv_impl)
        x2 = _norm(cfg, p["norm2"], h)
        h = h + rwkv6.channel_mix(p["mix"], x2)
        return h, aux, None, disp
    if kind == "mamba":
        mc = mamba2.Mamba2Config(d_model=cfg.d_model, state_dim=cfg.ssm_state,
                                 head_dim=cfg.ssm_head_dim,
                                 chunk=cfg.ssm_chunk, dtype=cfg.dtype)
        xn = _norm(cfg, p["norm"], h)
        if mode == "decode":
            out, st = mamba2.decode_step(p["ssm"], xn, cache, mc)
            return h + out, aux, st, disp
        return h + mamba2.apply(p["ssm"], xn, mc), aux, None, disp

    acfg = _attn_cfg(cfg, kind)
    xn = _norm(cfg, p["norm1"], h)
    if kind == "cross":
        if cache is not None:  # decode: frozen image K/V from cache
            attn_out, _ = _cross_from_cache(p, xn, acfg, cache)
            new_cache = cache
        else:
            attn_out, new_cache = attention.attend(
                p["attn"], xn, acfg, positions=positions,
                kv_x=image_embeds, cache=None, kv_block=None)
        h = h + jnp.tanh(p["gate_attn"]).astype(h.dtype) * attn_out
        ffn_out, aux, disp = _ffn_apply(cfg, p["ffn"], _norm(
            cfg, p["norm2"], h), kind)
        h = h + jnp.tanh(p["gate_ffn"]).astype(h.dtype) * ffn_out
        return h, aux, new_cache, disp

    attn_out, new_cache = attention.attend(
        p["attn"], xn, acfg, positions=positions, cache=cache,
        kv_block=kv_block, q_block=q_block)
    h = h + attn_out
    ffn_out, aux, disp = _ffn_apply(cfg, p["ffn"],
                                    _norm(cfg, p["norm2"], h), kind)
    h = h + ffn_out
    return h, aux, new_cache, disp


def _cross_from_cache(p, xn, acfg, cache):
    """Cross-attention against precomputed image K/V (decode path)."""
    b, t, _ = xn.shape
    q = layers.dense(p["attn"]["wq"], xn).reshape(
        b, t, acfg.num_heads, acfg.head_dim).transpose(0, 2, 1, 3)
    g = acfg.num_heads // acfg.num_kv_heads
    qg = q.reshape(b, acfg.num_kv_heads, g, t, acfg.head_dim)
    scores = jnp.einsum("bkgqh,bkth->bkgqt", qg, cache["k"],
                        preferred_element_type=jnp.float32)
    scores = scores * acfg.head_dim ** -0.5
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqt,bkth->bkgqh", probs.astype(cache["v"].dtype),
                     cache["v"])
    out = out.reshape(b, acfg.num_heads, t, acfg.head_dim)
    out = out.transpose(0, 2, 1, 3).reshape(b, t, -1)
    return layers.dense(p["attn"]["wo"], out), None


def _chunked_xent(model, params, h, labels, loss_chunk: int) -> jnp.ndarray:
    """Next-token xent, optionally scanning sequence chunks so the f32
    (B, chunk, V) logits never materialize at full sequence length —
    the 256k-vocab memory lever for the large dense archs."""
    h_in, gold = h[:, :-1], labels[:, 1:]
    t = h_in.shape[1]
    if not loss_chunk or t <= loss_chunk:
        logits = model.unembed_logits(params, h_in)
        return layers.softmax_xent(logits, gold)
    pad = (-t) % loss_chunk
    mask = jnp.ones_like(gold, jnp.float32)
    if pad:
        h_in = jnp.pad(h_in, ((0, 0), (0, pad), (0, 0)))
        gold = jnp.pad(gold, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    n = (t + pad) // loss_chunk

    def body(acc, i):
        hc = jax.lax.dynamic_slice_in_dim(h_in, i * loss_chunk, loss_chunk, 1)
        gc = jax.lax.dynamic_slice_in_dim(gold, i * loss_chunk, loss_chunk, 1)
        mc = jax.lax.dynamic_slice_in_dim(mask, i * loss_chunk, loss_chunk, 1)
        logits = model.unembed_logits(params, hc)
        logits = logits.astype(jnp.float32)
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        g = jnp.take_along_axis(logits, gc[..., None], axis=-1)[..., 0]
        return acc + jnp.sum((logz - g) * mc), None

    total, _ = jax.lax.scan(body, jnp.float32(0.0), jnp.arange(n))
    return total / jnp.maximum(mask.sum(), 1.0)


# ---------------------------------------------------------------------------
# Cache construction
# ---------------------------------------------------------------------------


def _sub_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int,
               params_sub=None, image_embeds=None):
    dt = jnp.dtype(cfg.dtype)
    if kind == "rwkv":
        rc = rwkv6.RWKVConfig(d_model=cfg.d_model, d_ff=cfg.d_ff,
                              dtype=cfg.dtype)
        st = rwkv6.init_state(rc, batch)
        return {"s": st["s"], "last": st["last"].astype(dt),
                "cm_last": st["cm_last"].astype(dt)}
    if kind == "mamba":
        mc = mamba2.Mamba2Config(d_model=cfg.d_model, state_dim=cfg.ssm_state,
                                 head_dim=cfg.ssm_head_dim,
                                 chunk=cfg.ssm_chunk, dtype=cfg.dtype)
        st = mamba2.init_state(mc, batch)
        return {"h": st["h"], "conv": st["conv"].astype(dt)}
    if kind == "cross":
        acfg = _attn_cfg(cfg, kind)
        k = layers.dense(params_sub["attn"]["wk"], image_embeds)
        v = layers.dense(params_sub["attn"]["wv"], image_embeds)
        b, ti, _ = image_embeds.shape
        k = k.reshape(b, ti, acfg.num_kv_heads, acfg.head_dim
                      ).transpose(0, 2, 1, 3)
        v = v.reshape(b, ti, acfg.num_kv_heads, acfg.head_dim
                      ).transpose(0, 2, 1, 3)
        return {"k": k, "v": v}
    acfg = _attn_cfg(cfg, kind)
    c = attention.init_cache(acfg, batch, max_len, dt)
    return {"k": c["k"], "v": c["v"]}  # pos passed externally per step


# ---------------------------------------------------------------------------
# The model
# ---------------------------------------------------------------------------


class CausalLM:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.plan = layer_plan(cfg)

    # -- init ---------------------------------------------------------------

    def init(self, rng) -> dict:
        cfg = self.cfg
        plan = self.plan
        dt = jnp.dtype(cfg.dtype)
        keys = jax.random.split(rng, 8)
        params: dict[str, Any] = {
            "embed": layers.embed_init(keys[0], cfg.padded_vocab, cfg.d_model,
                                       dt),
            "final_norm": _norm_init(cfg),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = layers.dense_init(
                keys[1], cfg.d_model, cfg.padded_vocab, dt)

        group: dict[str, Any] = {}
        for i, kind in enumerate(plan.group_kinds):
            if kind == "shared_attn":
                continue
            sub_keys = jax.random.split(jax.random.fold_in(keys[2], i),
                                        plan.n_groups)
            group[f"sub{i}"] = jax.vmap(
                lambda k: _sub_init(k, cfg, kind))(sub_keys)
        params["groups"] = group
        if "shared_attn" in plan.group_kinds:
            params["shared_attn"] = _sub_init(keys[3], cfg, "shared_attn")
        if plan.tail_kinds:
            params["tail"] = [
                _sub_init(jax.random.fold_in(keys[4], i), cfg, kind)
                for i, kind in enumerate(plan.tail_kinds)]
        return params

    # -- forward (train) ------------------------------------------------------

    def hidden(self, params, tokens, *, image_embeds=None):
        """Final-norm hidden states (B, T, d) + MoE aux loss."""
        cfg, plan = self.cfg, self.plan
        h = layers.embed(params["embed"], tokens)
        if cfg.family == "audio":
            raise ValueError("use whisper.WhisperModel for audio")
        h = pctx.shard_batch(h)
        positions = jnp.arange(tokens.shape[1])
        kv_block = cfg.kv_block if cfg.attn_impl == "blockwise" else None
        q_block = cfg.q_block or None

        def group_body(carry, group_params):
            h, aux = carry
            for i, kind in enumerate(plan.group_kinds):
                p = (params["shared_attn"] if kind == "shared_attn"
                     else group_params[f"sub{i}"])
                h, a, _, _ = _sub_apply(
                    cfg, kind, p, h, mode="train", cache=None,
                    positions=positions, image_embeds=image_embeds,
                    kv_block=kv_block, q_block=q_block)
                h = pctx.shard_batch(h)
                aux = aux + a
            return (h, aux), None

        if cfg.remat == "block":
            group_body = jax.checkpoint(group_body)
        (h, aux), _ = jax.lax.scan(group_body, (h, 0.0), params["groups"])
        for i, kind in enumerate(plan.tail_kinds):
            h, a, _, _ = _sub_apply(cfg, kind, params["tail"][i], h,
                                    mode="train", cache=None,
                                    positions=positions, kv_block=kv_block,
                                    q_block=q_block)
            aux = aux + a
        h = _norm(cfg, params["final_norm"], h)
        return h, aux

    def unembed_logits(self, params, h):
        cfg = self.cfg
        logits = (layers.unembed(params["embed"], h)
                  if cfg.tie_embeddings
                  else layers.dense(params["lm_head"], h))
        logits = pctx.shard_batch_tp(logits)  # vocab TP-sharded
        return layers.softcap(logits.astype(jnp.float32), cfg.final_softcap)

    def forward(self, params, tokens, *, image_embeds=None):
        h, aux = self.hidden(params, tokens, image_embeds=image_embeds)
        return self.unembed_logits(params, h), aux

    def loss(self, params, batch, *, loss_chunk: int = 0):
        h, aux = self.hidden(params, batch["tokens"],
                             image_embeds=batch.get("image_embeds"))
        xent = _chunked_xent(self, params, h, batch["labels"], loss_chunk)
        aux = jnp.asarray(aux, jnp.float32)
        total = xent + 0.001 * aux if self.cfg.is_moe else xent
        return total, {"xent": xent, "aux": aux}

    # -- serving --------------------------------------------------------------

    def init_cache(self, params, batch: int, max_len: int,
                   image_embeds=None):
        cfg, plan = self.cfg, self.plan

        def one_group(g):
            caches = {}
            for i, kind in enumerate(plan.group_kinds):
                psub = None
                img = None
                if kind == "cross":
                    psub = jax.tree.map(lambda a: a[g],
                                        params["groups"][f"sub{i}"])
                    img = image_embeds
                caches[f"sub{i}"] = _sub_cache(cfg, kind, batch, max_len,
                                               psub, img)
            return caches

        stacked = jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *[one_group(g) for g in range(plan.n_groups)]) \
            if plan.n_groups > 1 else jax.tree.map(
                lambda x: x[None], one_group(0))
        tail = [
            _sub_cache(cfg, kind, batch, max_len, params["tail"][i], None)
            for i, kind in enumerate(plan.tail_kinds)]
        return {"groups": stacked, "tail": tail}

    def decode_step(self, params, tokens, cache, *, pos):
        """tokens (B, 1); pos scalar int32 — absolute position."""
        cfg, plan = self.cfg, self.plan
        h = layers.embed(params["embed"], tokens)
        h = pctx.shard_batch(h)
        positions = pos + jnp.arange(1)

        def group_body(h, xs):
            group_params, group_cache = xs
            new_caches = {}
            for i, kind in enumerate(plan.group_kinds):
                p = (params["shared_attn"] if kind == "shared_attn"
                     else group_params[f"sub{i}"])
                c = group_cache[f"sub{i}"]
                if kind in ("attn", "attn_local", "attn_global",
                            "shared_attn"):
                    c = dict(c, pos=pos)
                h, _, nc, _ = _sub_apply(cfg, kind, p, h, mode="decode",
                                         cache=c, positions=positions)
                if nc is not None and "pos" in nc:
                    nc = {k: v for k, v in nc.items() if k != "pos"}
                new_caches[f"sub{i}"] = nc if nc is not None else c
            return h, new_caches

        h, new_group_caches = jax.lax.scan(
            group_body, h, (params["groups"], cache["groups"]))
        new_tail = []
        for i, kind in enumerate(plan.tail_kinds):
            c = cache["tail"][i]
            if kind in ("attn", "attn_local", "attn_global", "shared_attn"):
                c = dict(c, pos=pos)
            h, _, nc, _ = _sub_apply(cfg, kind, params["tail"][i], h,
                                     mode="decode", cache=c,
                                     positions=positions)
            if nc is not None and "pos" in nc:
                nc = {k: v for k, v in nc.items() if k != "pos"}
            new_tail.append(nc if nc is not None else c)
        h = _norm(cfg, params["final_norm"], h)
        logits = (layers.unembed(params["embed"], h)
                  if cfg.tie_embeddings
                  else layers.dense(params["lm_head"], h))
        logits = layers.softcap(logits.astype(jnp.float32),
                                cfg.final_softcap)
        return logits, {"groups": new_group_caches, "tail": new_tail}
