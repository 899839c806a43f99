"""Config-driven decoder LM covering all assigned architecture families.

One :class:`Model` per :class:`~repro.configs.base.ModelConfig`; the layer
stack is built as *scan groups* so ``jax.lax.scan`` keeps HLO size and
compile time O(1) in depth:

- dense / moe / audio / vlm: scan over uniform layers (optionally a few
  leading dense layers, Moonlight-style, kept in the same stacked cache:
  cache layer i is model layer i); attention is grouped-query or, with
  ``cfg.mla``, latent (one cache row of latent + rotary key per token);
- gemma2: scan over (local, global) layer pairs;
- rwkv6: scan over rwkv layers (time-mix + channel-mix);
- zamba2: scan over groups of [shared-attn block (tied, alternating) +
  `shared_attn_every` mamba2 layers].

Modes: "train" (no cache), "prefill" (fresh cache write + causal attn),
"extend" (chunked prefill against an existing cache), "decode".
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist.sharding import constrain
from repro.models import attention as attn
from repro.models import mamba2, moe as moe_mod, rwkv6
from repro.models.common import (ParamSpec, abstract_params, init_params,
                                 rms_norm, sinusoidal_emb, softcap, spec_tree_map,
                                 take_layer)
from repro.models.mlp import mlp_apply, mlp_specs


def _norm_spec(D, dtype):
    return ParamSpec((D,), ("embed",), init="zeros", dtype=dtype)


def _stack_specs(specs: dict, n: int) -> dict:
    """Prepend a stacked 'layers' dim to every spec in the tree."""
    def one(s: ParamSpec) -> ParamSpec:
        return ParamSpec((n,) + s.shape, ("layers",) + s.axes, init=s.init,
                         scale=s.scale, dtype=s.dtype, fan_in=s.fan_in)
    return spec_tree_map(one, specs)


@dataclasses.dataclass
class Model:
    cfg: ModelConfig

    # ------------------------------------------------------------------ specs
    def specs(self) -> dict:
        cfg = self.cfg
        dt = cfg.param_dtype
        D, V = cfg.d_model, cfg.vocab_size
        tree: dict = {
            "embed": ParamSpec((V, D), ("vocab", "embed"), init="embed", dtype=dt),
            "final_norm": _norm_spec(D, dt),
        }
        if not cfg.tie_embeddings:
            tree["lm_head"] = ParamSpec((D, V), ("embed", "vocab"), dtype=dt)

        if cfg.family == "ssm":
            layer = {
                "ln1": _norm_spec(D, dt), "ln2": _norm_spec(D, dt),
                **rwkv6.rwkv_specs(cfg, dt),
            }
            tree["blocks"] = _stack_specs(layer, cfg.num_layers)
            return tree

        if cfg.family == "hybrid":
            group = {
                "mamba": _stack_specs({"ln": _norm_spec(D, dt),
                                       **mamba2.mamba_specs(cfg, dt)},
                                      cfg.shared_attn_every),
            }
            n_groups = cfg.num_layers // cfg.shared_attn_every
            tree["blocks"] = _stack_specs(group, n_groups)
            shared = {
                "win": ParamSpec((2 * D, D), ("embed_concat", "embed"), dtype=dt),
                "ln1": _norm_spec(D, dt), "ln2": _norm_spec(D, dt),
                "attn": attn.attention_specs(cfg, dt),
                "mlp": mlp_specs(D, cfg.d_ff, dt),
            }
            tree["shared"] = _stack_specs(shared, cfg.num_shared_blocks)
            return tree

        # attention families (dense / moe / audio / vlm / gemma2)
        def attn_layer():
            specs = attn.mla_specs if cfg.mla else attn.attention_specs
            l = {"ln1": _norm_spec(D, dt), "ln2": _norm_spec(D, dt),
                 "attn": specs(cfg, dt)}
            if cfg.sandwich_norm:
                l["ln1_post"] = _norm_spec(D, dt)
                l["ln2_post"] = _norm_spec(D, dt)
            return l

        def ffn_specs(moe_layer: bool):
            if moe_layer:
                return moe_mod.moe_specs(cfg, dt)
            dff = cfg.moe.dense_d_ff if (cfg.moe and cfg.moe.dense_d_ff) else cfg.d_ff
            return mlp_specs(D, dff, dt)

        if cfg.local_global_alternating:
            group = {"local": {**attn_layer(), "mlp": ffn_specs(False)},
                     "global": {**attn_layer(), "mlp": ffn_specs(False)}}
            tree["blocks"] = _stack_specs(group, cfg.num_layers // 2)
            return tree

        first_k = cfg.moe.first_k_dense if cfg.moe else 0
        if first_k:
            tree["dense_layers"] = _stack_specs(
                {**attn_layer(), "mlp": ffn_specs(False)}, first_k)
        layer = {**attn_layer(), "mlp": ffn_specs(cfg.moe is not None)}
        tree["blocks"] = _stack_specs(layer, cfg.num_layers - first_k)
        return tree

    def init(self, rng: jax.Array):
        return init_params(self.specs(), rng)

    def abstract(self):
        return abstract_params(self.specs())

    # ------------------------------------------------------------------ cache
    def cache_shapes(self, batch: int, max_len: int) -> dict:
        """Tree of (shape, dtype) for the serving cache."""
        cfg = self.cfg
        cd = cfg.kv_cache_dtype or cfg.compute_dtype
        KV, Dh = cfg.num_kv_heads, cfg.head_dim
        if cfg.family == "ssm":
            L = cfg.num_layers
            H, K = rwkv6.rwkv_dims(cfg)
            return {"shift1": ((L, batch, cfg.d_model), cd),
                    "wkv": ((L, batch, H, K, K), "float32"),
                    "shift2": ((L, batch, cfg.d_model), cd)}
        if cfg.family == "hybrid":
            G = cfg.num_layers // cfg.shared_attn_every
            E = cfg.shared_attn_every
            ms = mamba2.mamba_state_shapes(cfg, batch)
            return {
                "conv": ((G, E) + ms["conv"][0], ms["conv"][1]),
                "ssm": ((G, E) + ms["ssm"][0], ms["ssm"][1]),
                "shared_k": ((G, batch, max_len, KV, Dh), cd),
                "shared_v": ((G, batch, max_len, KV, Dh), cd),
            }
        if cfg.local_global_alternating:
            G = cfg.num_layers // 2
            W = min(cfg.sliding_window, max_len)
            return {"k_local": ((G, batch, W, KV, Dh), cd),
                    "v_local": ((G, batch, W, KV, Dh), cd),
                    "k_global": ((G, batch, max_len, KV, Dh), cd),
                    "v_global": ((G, batch, max_len, KV, Dh), cd)}
        L = cfg.num_layers
        if cfg.mla:
            return {"c": ((L, batch, max_len, cfg.mla.cache_width), cd)}
        return {"k": ((L, batch, max_len, KV, Dh), cd),
                "v": ((L, batch, max_len, KV, Dh), cd)}

    def cache_keys(self) -> tuple[str, ...]:
        """The uniform-attention cache's leaves, in pool order: ``("k",
        "v")``, or ``("c",)`` for latent attention."""
        return ("c",) if self.cfg.mla else ("k", "v")

    def init_cache(self, batch: int, max_len: int):
        return jax.tree.map(lambda sd: jnp.zeros(sd[0], jnp.dtype(sd[1])),
                            self.cache_shapes(batch, max_len),
                            is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], tuple))

    def abstract_cache(self, batch: int, max_len: int):
        return jax.tree.map(lambda sd: jax.ShapeDtypeStruct(sd[0], jnp.dtype(sd[1])),
                            self.cache_shapes(batch, max_len),
                            is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], tuple))

    def cache_logical_axes(self) -> dict:
        """Logical axes per cache leaf (for shardings)."""
        cfg = self.cfg
        kv_axes = ("layers", "act_batch", "cache_seq", "cache_kv_heads", None)
        if cfg.family == "ssm":
            return {"shift1": ("layers", "act_batch", None),
                    "wkv": ("layers", "act_batch", "rwkv_heads", "rwkv_k", "rwkv_v"),
                    "shift2": ("layers", "act_batch", None)}
        if cfg.family == "hybrid":
            return {"conv": ("layers", None, "act_batch", None, "conv_dim"),
                    "ssm": ("layers", None, "act_batch", "ssm_heads", None, "ssm_state"),
                    "shared_k": kv_axes, "shared_v": kv_axes}
        if cfg.local_global_alternating:
            return {"k_local": kv_axes, "v_local": kv_axes,
                    "k_global": kv_axes, "v_global": kv_axes}
        if cfg.mla:
            return {"c": ("layers", "act_batch", "cache_seq", "kv_latent")}
        return {"k": kv_axes, "v": kv_axes}

    # ---------------------------------------------------------------- layers
    def _attn_apply(self, p, x, kv, cache_len, mode, *, window=0):
        """One attention sublayer. kv: the layer's cache leaves in
        ``cache_keys`` order — (cache_k, cache_v), or (latent,) — or None
        (train)."""
        cfg = self.cfg
        B, S, D = x.shape
        if mode == "train" or kv is None:
            positions = jnp.arange(S)
        else:
            cl = jnp.asarray(cache_len)
            positions = (cl[..., None] if cl.ndim else cl) + jnp.arange(S)
        if cfg.mla:
            return self._mla_apply(p, x, kv, cache_len, mode, positions)
        scale = 1.0 / math.sqrt(cfg.head_dim)
        q, k, v = attn.qkv_project(p, x, cfg, positions)
        # TP head padding (§Perf): when num_heads doesn't divide the model
        # axis, pad Q heads with zeros so the attention core shards instead
        # of replicating (outputs of pad heads are sliced off before wo).
        from repro.dist.sharding import current_rules
        rules = current_rules() or {}
        pad_h = rules.get("__attn_head_pad__", 0)
        H0 = q.shape[2]
        if pad_h and H0 % pad_h:
            q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_h - H0 % pad_h), (0, 0)))
        q = constrain(q, "act_batch", None, "act_heads", None)
        k = constrain(k, "act_batch", None, "act_kv_heads", None)

        new_kv = None
        if kv is not None:
            ring = window > 0 and kv[0].shape[1] == window
            ck, cv = attn.write_cache(kv[0], kv[1], k, v, cache_len, ring=ring)
            new_kv = (ck, cv)

        if mode in ("train", "prefill"):
            if window:
                o = attn.attend_windowed(q, k, v, scale=scale, window=window,
                                         cap=cfg.attn_softcap)
            else:
                o = attn.attend_causal(q, k, v, scale=scale, cap=cfg.attn_softcap)
        else:  # extend / decode: dense against cache
            o = attn.attend_decode(q, new_kv[0], new_kv[1], cache_len,
                                   scale=scale, cap=cfg.attn_softcap,
                                   window=window)
        o = constrain(o, "act_batch", None, "act_heads", None)
        if o.shape[2] != H0:
            o = o[:, :, :H0]                       # drop TP padding heads
        return attn.out_project(p, o), new_kv

    def _mla_apply(self, p, x, kv, cache_len, mode, positions):
        """Latent attention: the layer's cache row is written, then
        expanded to per-head keys and values (the chunk's own rows in
        train/prefill, the whole cache in extend/decode)."""
        cfg = self.cfg
        scale = 1.0 / math.sqrt(cfg.mla.qk_head_dim)
        q = attn.mla_query(p, x, cfg, positions)
        lat = attn.mla_latent(p, x, cfg, positions)
        new = None if kv is None else (attn.write_rows(kv[0], lat, cache_len),)
        if mode in ("train", "prefill"):
            k, v = attn.mla_expand(p, lat, cfg)
            o = attn.attend_causal(q, k, v, scale=scale)
        else:
            k, v = attn.mla_expand(p, new[0].astype(x.dtype), cfg)
            o = attn.attend_decode(q, k, v, cache_len, scale=scale)
        return attn.out_project(p, o), new

    def _attn_layer(self, p, x, kv, cache_len, mode, window=0):
        cfg = self.cfg
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        a, new_kv = self._attn_apply(p["attn"], h, kv, cache_len, mode, window=window)
        if cfg.sandwich_norm:
            a = rms_norm(a, p["ln1_post"], cfg.norm_eps)
        x = x + a
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        if "router" in p["mlp"]:
            f = moe_mod.moe_apply(p["mlp"], h, cfg)
        else:
            f = mlp_apply(p["mlp"], h, cfg.activation)
        if cfg.sandwich_norm:
            f = rms_norm(f, p["ln2_post"], cfg.norm_eps)
        return x + f, new_kv

    def _rwkv_layer(self, p, x, st, mode):
        cfg = self.cfg
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        tm_state = None if st is None else {"shift": st["shift1"], "wkv": st["wkv"]}
        a, tm_new = rwkv6.time_mix_apply(p["tmix"], h, cfg, tm_state, mode)
        x = x + a
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        cm_state = None if st is None else st["shift2"]
        f, cm_new = rwkv6.channel_mix_apply(p["cmix"], h, cfg, cm_state, mode)
        new_st = {"shift1": tm_new["shift"], "wkv": tm_new["wkv"], "shift2": cm_new}
        return x + f, new_st

    def _mamba_layer(self, p, x, st, mode):
        cfg = self.cfg
        h = rms_norm(x, p["ln"], cfg.norm_eps)
        state = None if st is None else st
        out, new_st = mamba2.mamba_apply(p, h, cfg, state, mode)
        return x + out, new_st

    def _shared_block(self, p, x, x0, kv, cache_len, mode):
        """Zamba2 shared attn+mlp block: input concat(current, embeddings)."""
        cfg = self.cfg
        h = jnp.einsum("bsd,de->bse", jnp.concatenate([x, x0], axis=-1), p["win"])
        h1 = rms_norm(h, p["ln1"], cfg.norm_eps)
        a, new_kv = self._attn_apply(p["attn"], h1, kv, cache_len, mode)
        h = h + a
        h2 = rms_norm(h, p["ln2"], cfg.norm_eps)
        h = h + mlp_apply(p["mlp"], h2, cfg.activation)
        return x + h, new_kv

    # --------------------------------------------------------------- forward
    def forward(self, params, tokens=None, embeds=None, cache=None,
                cache_len=0, mode="train", logits_slice: int | None = None,
                logits_at=None):
        """Returns (logits, new_cache). ``logits_slice=k`` keeps only the
        last k positions' logits (serving: k=1); ``logits_at=i`` (may be
        traced) keeps only position i's, so a padded chunk compiles once
        whatever its real length."""
        cfg = self.cfg
        from repro.models.common import cast_params
        params = cast_params(params, self.specs(), cfg.compute_dtype)
        x, new_cache = self._backbone(params, tokens, embeds, cache, cache_len, mode)
        if logits_at is not None:
            x = jax.lax.dynamic_slice_in_dim(x, logits_at, 1, axis=1)
        elif logits_slice is not None:
            x = x[:, -logits_slice:]
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = jnp.einsum("bsd,dv->bsv", x, head.astype(x.dtype))
        logits = logits.astype(jnp.float32)
        if cfg.final_softcap:
            logits = softcap(logits, cfg.final_softcap)
        logits = constrain(logits, "act_batch", "act_seq", "vocab")
        return logits, new_cache

    def _backbone(self, params, tokens, embeds, cache, cache_len, mode):
        """Embedding + layer stack + final norm (params already cast)."""
        cfg = self.cfg
        if embeds is None:
            x = params["embed"][tokens].astype(cfg.compute_dtype)
            if cfg.pos_emb == "sinusoidal":
                cl = jnp.asarray(cache_len)
                pos = (cl[..., None] if cl.ndim else cl) + jnp.arange(tokens.shape[-1])
                x = x + sinusoidal_emb(pos, cfg.d_model).astype(x.dtype)
        else:
            x = embeds.astype(cfg.compute_dtype)
        if cfg.scale_embeddings:
            x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
        x = constrain(x, "act_batch", "act_seq", "act_embed")

        remat = cfg.remat != "none" and mode == "train"

        def maybe_remat(fn):
            return jax.checkpoint(fn) if remat else fn

        def tree_dus(full, upd, i):
            """Write per-layer cache `upd` into stacked cache at index i —
            carry-based so XLA updates the (donated) buffers in place."""
            return jax.tree.map(
                lambda f, u: jax.lax.dynamic_update_index_in_dim(
                    f, u.astype(f.dtype), i, 0), full, upd)

        new_cache = dict(cache) if cache is not None else None

        def scan_with_cache(layer_fn, blocks, cache_tree, n_layers,
                            extra_xs=None, offset=0):
            """Scan over stacked layers. With a cache, the full stacked cache
            rides the CARRY and each layer slice is read/written with
            dynamic (update-)slice — XLA keeps the donated buffers in place
            (xs/ys caches would force a second stacked copy). Scanned layer
            i uses cache layer ``offset + i``."""
            xs = (blocks, jnp.arange(n_layers)) if extra_xs is None \
                else (blocks, jnp.arange(n_layers), extra_xs)

            if cache_tree is None:
                def body(x, layer_in):
                    p = layer_in[0]
                    gi = layer_in[1]
                    x, _ = layer_fn(p, x, None, gi)
                    x = constrain(x, "act_batch", "act_seq", "act_embed")
                    return x, None

                x2, _ = jax.lax.scan(maybe_remat(body), x, xs)
                return x2, None

            def body(carry, layer_in):
                xc, cstack = carry
                p = layer_in[0]
                gi = layer_in[1]
                st = take_layer(cstack, gi + offset)
                xc, new_st = layer_fn(p, xc, st, gi)
                xc = constrain(xc, "act_batch", "act_seq", "act_embed")
                cstack = tree_dus(cstack, new_st, gi + offset)
                return (xc, cstack), None

            (x2, new_c), _ = jax.lax.scan(maybe_remat(body), (x, cache_tree), xs)
            return x2, new_c

        if cfg.family == "ssm":
            def layer_fn(p, xc, st, gi):
                return self._rwkv_layer(p, xc, st, mode)

            st = None
            if cache is not None:
                st = {"shift1": cache["shift1"], "wkv": cache["wkv"],
                      "shift2": cache["shift2"]}
            x, sts = scan_with_cache(layer_fn, params["blocks"], st,
                                     cfg.num_layers)
            if cache is not None:
                new_cache = sts
        elif cfg.family == "hybrid":
            x0 = x
            G = cfg.num_layers // cfg.shared_attn_every
            nshared = cfg.num_shared_blocks

            def layer_fn(p, xc, st, gi):
                sp = take_layer(params["shared"], gi % nshared)
                kv = None if st is None else (st["shared_k"], st["shared_v"])
                xc, new_kv = self._shared_block(sp, xc, x0, kv, cache_len, mode)

                if st is None:
                    def mamba_body(xm, m_in):
                        xm, _ = self._mamba_layer(m_in, xm, None, mode)
                        return xm, None
                    xc, _ = jax.lax.scan(mamba_body, xc, p["mamba"])
                    return xc, None

                def mamba_body(carry, m_in):
                    xm, mstack = carry
                    mp, mi = m_in
                    mst = take_layer(mstack, mi)
                    xm, new_mst = self._mamba_layer(mp, xm, mst, mode)
                    mstack = tree_dus(mstack, new_mst, mi)
                    return (xm, mstack), None

                mst = {"conv": st["conv"], "ssm": st["ssm"]}
                E = cfg.shared_attn_every
                (xc, new_mst), _ = jax.lax.scan(
                    mamba_body, (xc, mst), (p["mamba"], jnp.arange(E)))
                new_st = {"conv": new_mst["conv"], "ssm": new_mst["ssm"],
                          "shared_k": new_kv[0], "shared_v": new_kv[1]}
                return xc, new_st

            st = None
            if cache is not None:
                st = {"conv": cache["conv"], "ssm": cache["ssm"],
                      "shared_k": cache["shared_k"], "shared_v": cache["shared_v"]}
            x, sts = scan_with_cache(layer_fn, params["blocks"], st, G)
            if cache is not None:
                new_cache = sts
        elif cfg.local_global_alternating:
            def layer_fn(p, xc, st, gi):
                kvl = None if st is None else (st["k_local"], st["v_local"])
                xc, new_l = self._attn_layer(p["local"], xc, kvl, cache_len, mode,
                                             window=cfg.sliding_window)
                kvg = None if st is None else (st["k_global"], st["v_global"])
                xc, new_g = self._attn_layer(p["global"], xc, kvg, cache_len, mode)
                new_st = None
                if st is not None:
                    new_st = {"k_local": new_l[0], "v_local": new_l[1],
                              "k_global": new_g[0], "v_global": new_g[1]}
                return xc, new_st

            st = None
            if cache is not None:
                st = {k: cache[k] for k in
                      ("k_local", "v_local", "k_global", "v_global")}
            x, sts = scan_with_cache(layer_fn, params["blocks"], st,
                                     cfg.num_layers // 2)
            if cache is not None:
                new_cache = sts
        else:
            first_k = cfg.moe.first_k_dense if cfg.moe else 0
            keys = self.cache_keys()

            def layer_fn(p, xc, st, gi):
                kv = None if st is None else tuple(st[k] for k in keys)
                xc, new_kv = self._attn_layer(p, xc, kv, cache_len, mode)
                return xc, None if st is None else dict(zip(keys, new_kv))

            st = None if cache is None else {k: cache[k] for k in keys}
            for i in range(first_k):         # leading dense layers
                x, new_st = layer_fn(take_layer(params["dense_layers"], i), x,
                                     None if st is None else take_layer(st, i),
                                     i)
                if st is not None:
                    st = tree_dus(st, new_st, i)
            x, sts = scan_with_cache(layer_fn, params["blocks"], st,
                                     cfg.num_layers - first_k, offset=first_k)
            if cache is not None:
                new_cache.update(sts)

        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return x, new_cache

    # ------------------------------------------------------------------ loss
    def loss(self, params, tokens, labels, mask=None, loss_chunk: int = 1024):
        """Cross entropy with seq-chunked logits: the (B, S, V) fp32 logits
        tensor is never materialized — each chunk's logits are computed,
        reduced, and discarded (recomputed in backward via remat)."""
        hidden = self.hidden_states(params, tokens)
        cfg = self.cfg
        from repro.models.common import cast_params
        cparams = cast_params(params, self.specs(), cfg.compute_dtype)
        head = (cparams["embed"].T if cfg.tie_embeddings else cparams["lm_head"])
        # keep the head's cotangent sharded (unconstrained scan-accumulated
        # grads default to replicated — 2.3 GB fp32 for 150k vocabs)
        head = constrain(head, "embed", "vocab")
        B, S, D = hidden.shape
        C = min(loss_chunk, S)
        if S % C:
            C = S  # fallback: no chunking for ragged lengths
        nch = S // C

        def chunk_nll(h, lab):
            logits = jnp.einsum("bsd,dv->bsv", h, head).astype(jnp.float32)
            if cfg.final_softcap:
                logits = softcap(logits, cfg.final_softcap)
            logits = constrain(logits, "act_batch", "act_seq", "vocab")
            lse = jax.nn.logsumexp(logits, axis=-1)
            ll = jnp.take_along_axis(logits, lab[..., None], axis=-1)[..., 0]
            return lse - ll

        def body(_, xs):
            h, lab = xs
            return None, jax.checkpoint(chunk_nll)(h, lab)

        hs = hidden.reshape(B, nch, C, D).transpose(1, 0, 2, 3)
        ls = labels.reshape(B, nch, C).transpose(1, 0, 2)
        _, nll = jax.lax.scan(body, None, (hs, ls))
        nll = nll.transpose(1, 0, 2).reshape(B, S)
        if mask is not None:
            nll = nll * mask
            return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1.0)
        return jnp.mean(nll)

    def hidden_states(self, params, tokens):
        """Final-norm hidden states for the training path (no logits)."""
        cfg = self.cfg
        from repro.models.common import cast_params
        params = cast_params(params, self.specs(), cfg.compute_dtype)
        return self._backbone(params, tokens=tokens, embeds=None, cache=None,
                              cache_len=0, mode="train")[0]
