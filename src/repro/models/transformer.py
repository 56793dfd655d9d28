"""Model assembly: heterogeneous block stacks, scan-over-layers, decode caches.

Layer patterns (dense / SWA / local-attn / RG-LRU / sLSTM / mLSTM, with dense
or MoE FFNs interleaved per ``MoEConfig.layer_step``) are compiled into a
*stack plan*: the smallest repeating unit of per-layer signatures is scanned
with stacked parameters (keeps HLO compact for 88-layer models) and any
remainder layers run unrolled.  Sliding-window long-context variants reuse the
same parameters — only the attention mask/window changes — so the plan is
always derived from the training pattern (DESIGN.md §4).

Whisper-style encoder-decoder is assembled from the same blocks plus
cross-attention; sinusoidal positions are used for both encoder and decoder
(simplification of Whisper's learned decoder positions — parameter-free and
length-generic; noted in DESIGN.md §8).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import (ATTN_FULL, ATTN_LOCAL, ATTN_SWA, MLSTM,
                                RECURRENT, SLSTM, ModelConfig)
from repro.models import layers as L
from repro.models import moe as moe_lib
from repro.models import rglru as rglru_lib
from repro.models import xlstm as xlstm_lib
from repro.models.attention import (chunked_attention,
                                    context_parallel_attention,
                                    decode_attention)


# ---------------------------------------------------------------------------
# run context


@dataclasses.dataclass(frozen=True)
class RunCtx:
    """Execution context: mesh/sharding mode + perf knobs."""
    mesh: Any = None
    tp_axis: str = "model"
    dp_axes: Tuple[str, ...] = ("data",)
    attn_mode: str = "local"        # local | megatron | context
    chunk_q: int = 512
    chunk_k: int = 512
    remat: bool = True
    loss_chunk: int = 512
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.float32
    seq_sharded: bool = False       # context-parallel activations (b, s@tp, d)
    # Pallas hot-path dispatch (DESIGN.md §15): "jax" = XLA-default paths,
    # "pallas" = flash_decode / flash_attention kernels.  kernel_interpret
    # None = autodetect (interpret off-TPU, compiled on TPU).
    decode_backend: str = "jax"
    prefill_backend: str = "jax"
    kernel_interpret: Any = None

    def constrain(self, x, spec_axes: Tuple[Any, ...]):
        """with_sharding_constraint, dropping axes that don't divide.

        Sharding propagation across vocab-sharded gathers/scans can silently
        drop the batch axis (replicating all compute across 'data'); explicit
        activation constraints pin the intended layout (DESIGN.md §5).
        """
        if self.mesh is None:
            return x
        from jax.sharding import NamedSharding, PartitionSpec
        resolved = []
        for dim, ax in zip(x.shape, spec_axes):
            if ax is None:
                resolved.append(None)
                continue
            axes = (ax,) if isinstance(ax, str) else tuple(ax)
            n = 1
            for a in axes:
                n *= self.mesh.shape[a]
            resolved.append(ax if dim % n == 0 else None)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, PartitionSpec(*resolved)))

    def act(self, x):
        """Constrain (b, s, d) activations: batch over fsdp, sequence over tp
        (Megatron-style sequence parallelism — inter-block residuals and the
        remat carry stack shard 16-way; blocks internally gather the sequence
        and emit reduce-scatters, same wire bytes as the all-reduces they
        replace).  Non-divisible dims drop automatically (decode s=1)."""
        return self.constrain(x, (self.dp_axes, self.tp_axis, None))


# ---------------------------------------------------------------------------
# stack plan


def layer_sigs(cfg: ModelConfig) -> List[Tuple[str, str]]:
    """Per-layer (kind, ffn_kind) signatures from the *training* pattern."""
    sigs = []
    for li, kind in enumerate(cfg.pattern):
        if kind in (SLSTM, MLSTM) or cfg.d_ff == 0:
            ffn = "none"
        elif cfg.moe is not None and li % cfg.moe.layer_step == cfg.moe.layer_step - 1:
            ffn = "moe"
        elif cfg.moe is not None and cfg.moe.dense_d_ff:
            ffn = "dense_alt"
        else:
            ffn = "dense"
        sigs.append((kind, ffn))
    return sigs


def stack_plan(sigs: Sequence[Tuple[str, str]]) -> Tuple[int, int, int]:
    """-> (unit_len, repeats, remainder). Smallest unit with >=2 repeats."""
    n = len(sigs)
    for u in range(1, n // 2 + 1):
        k = n // u
        if all(sigs[i] == sigs[i % u] for i in range(u * k)):
            return u, k, n - u * k
    return n, 1, 0


# ---------------------------------------------------------------------------
# block init / apply


def _init_norm(key, cfg: ModelConfig, dtype):
    if cfg.family == "audio":
        return {"scale": jnp.ones((cfg.d_model,), dtype),
                "bias": jnp.zeros((cfg.d_model,), dtype)}
    return {"scale": jnp.zeros((cfg.d_model,), dtype)}


def _norm(p, x, cfg: ModelConfig):
    if "bias" in p:
        return L.layer_norm(x, p["scale"], p["bias"], eps=1e-5)
    return L.rms_norm(x, p["scale"], eps=cfg.norm_eps)


def init_block(key, cfg: ModelConfig, sig: Tuple[str, str], dtype,
               cross_attn: bool = False):
    kind, ffn = sig
    ks = jax.random.split(key, 6)
    p: Dict[str, Any] = {"norm1": _init_norm(ks[0], cfg, dtype)}
    if kind in (ATTN_FULL, ATTN_SWA, ATTN_LOCAL):
        p["attn"] = L.init_attention(ks[1], cfg, dtype)
    elif kind == RECURRENT:
        p["rglru"] = rglru_lib.init_rglru(ks[1], cfg, dtype)
    elif kind == MLSTM:
        p["mlstm"] = xlstm_lib.init_mlstm(ks[1], cfg, dtype)
    elif kind == SLSTM:
        p["slstm"] = xlstm_lib.init_slstm(ks[1], cfg, dtype)
    if cross_attn:
        p["cross"] = L.init_attention(ks[2], cfg, dtype)
        p["norm_cross"] = _init_norm(ks[3], cfg, dtype)
    if ffn != "none":
        p["norm2"] = _init_norm(ks[4], cfg, dtype)
        if ffn == "moe":
            p["moe"] = moe_lib.init_moe(ks[5], cfg, dtype)
        elif ffn == "dense_alt":
            p["mlp"] = L.init_mlp(ks[5], cfg.d_model, cfg.moe.dense_d_ff, dtype)
        else:
            p["mlp"] = L.init_mlp(ks[5], cfg.d_model, cfg.d_ff, dtype)
    return p


def _attention_fwd(p, x, cfg: ModelConfig, ctx: RunCtx, eff_kind: str,
                   window: int, rope):
    cos, sin = rope
    q, k, v = L.qkv_proj(p, x, cfg)
    if cos is not None:
        q = L.apply_rotary(q, cos, sin)
        k = L.apply_rotary(k, cos, sin)
    mask_kind = {"attn_full": "causal", "attn_swa": "swa",
                 "attn_local": "swa", "bidir": "bidir"}[eff_kind]
    if ctx.attn_mode == "context" and ctx.mesh is not None and x.shape[1] > 1:
        o = context_parallel_attention(q, k, v, ctx.mesh, ctx.tp_axis,
                                       kind=mask_kind, window=window,
                                       chunk_q=ctx.chunk_q, chunk_k=ctx.chunk_k)
    else:
        # Megatron path: residuals arrive sequence-sharded — gather the
        # sequence and shard heads here, otherwise the static q-block loop
        # would slice a sharded dim (a collective per slice).  KV heads may
        # not divide TP (GQA) and stay replicated.
        q = ctx.constrain(q, (ctx.dp_axes, None, ctx.tp_axis, None))
        k = ctx.constrain(k, (ctx.dp_axes, None, None, None))
        v = ctx.constrain(v, (ctx.dp_axes, None, None, None))
        o = chunked_attention(q, k, v, kind=mask_kind, window=window,
                              chunk_q=ctx.chunk_q, chunk_k=ctx.chunk_k)
        o = ctx.constrain(o, (ctx.dp_axes, None, ctx.tp_axis, None))
    return L.out_proj(p, o)


def _cross_attention_fwd(p, x, enc_kv, cfg: ModelConfig, ctx: RunCtx):
    q, _, _ = L.qkv_proj(p, x, cfg)
    k, v = enc_kv
    # chunk_q = full length: queries may be sequence-sharded (context mode) and
    # a single q block avoids slicing the sharded dim; K/V stay replicated.
    o = chunked_attention(q, k, v, kind="bidir", window=0,
                          chunk_q=q.shape[1], chunk_k=ctx.chunk_k)
    return L.out_proj(p, o)


def block_fwd(p, x, cfg: ModelConfig, ctx: RunCtx, sig: Tuple[str, str],
              eff_kind: str, window: int, rope, enc_kv=None):
    """One block, training/prefill path. x (b, s, d) -> (x, aux_loss)."""
    kind, ffn = sig
    aux = jnp.zeros((), jnp.float32)
    # the named scopes label the device ops in a profile; the residual adds
    # stay outside them
    if kind in (ATTN_FULL, ATTN_SWA, ATTN_LOCAL):
        with jax.named_scope("attention"):
            h = _norm(p["norm1"], x, cfg)
            y = _attention_fwd(p["attn"], h, cfg, ctx, eff_kind, window, rope)
        x = x + y
    else:
        h = _norm(p["norm1"], x, cfg)
        if kind == RECURRENT:
            # recurrent scans need the sequence local; features shard instead
            h = ctx.constrain(h, (ctx.dp_axes, None, None))
            x = x + rglru_lib.rglru_block(p["rglru"], h)
        elif kind == MLSTM:
            h = ctx.constrain(h, (ctx.dp_axes, None, None))
            x = x + xlstm_lib.mlstm_chunked(p["mlstm"], h, cfg,
                                            chunk=min(256, h.shape[1]))
        elif kind == SLSTM:
            h = ctx.constrain(h, (ctx.dp_axes, None, None))
            x = x + xlstm_lib.slstm_block(p["slstm"], h, cfg)
    if enc_kv is not None:
        with jax.named_scope("attention"):
            hc = _norm(p["norm_cross"], x, cfg)
            y = _cross_attention_fwd(p["cross"], hc, enc_kv, cfg, ctx)
        x = x + y
    if ffn != "none":
        with jax.named_scope("mlp"):
            h2 = _norm(p["norm2"], x, cfg)
            if ffn == "moe":
                y, aux = moe_lib.moe_ffn(p["moe"], h2, cfg, ctx)
            else:
                y = L.mlp(p["mlp"], h2, ctx)
        x = x + y
    return x, aux


# ---------------------------------------------------------------------------
# model init


def init_params(key, cfg: ModelConfig, dtype=jnp.float32):
    sigs = layer_sigs(cfg)
    u, reps, rem = stack_plan(sigs)
    ks = jax.random.split(key, 8)
    params: Dict[str, Any] = {
        "embed": L.embed_init(ks[0], cfg.padded_vocab_size, cfg.d_model, dtype),
        "final_norm": _init_norm(ks[1], cfg, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(ks[2], cfg.d_model,
                                         cfg.padded_vocab_size, dtype)
    cross = cfg.encoder_layers > 0
    unit: Dict[str, Any] = {}
    for j in range(u):
        kj = jax.random.fold_in(ks[3], j)
        keys = jax.random.split(kj, reps)
        unit[f"p{j}"] = jax.vmap(
            lambda k: init_block(k, cfg, sigs[j], dtype, cross_attn=cross))(keys)
    params["unit"] = unit
    rest: Dict[str, Any] = {}
    for i in range(rem):
        li = u * reps + i
        rest[f"l{li}"] = init_block(jax.random.fold_in(ks[4], i), cfg,
                                    sigs[li], dtype, cross_attn=cross)
    params["rest"] = rest
    if cross:
        enc = {}
        ekeys = jax.random.split(ks[5], cfg.encoder_layers)
        enc["blocks"] = jax.vmap(
            lambda k: init_block(k, cfg, (ATTN_FULL, "dense"), dtype))(ekeys)
        enc["final_norm"] = _init_norm(ks[6], cfg, dtype)
        params["encoder"] = enc
    return params


def param_count_tree(params) -> int:
    return int(sum(x.size for x in jax.tree.leaves(params)))


# ---------------------------------------------------------------------------
# positions / rope helpers


def _rope_for(cfg: ModelConfig, positions, mrope_positions=None):
    hd = cfg.resolved_head_dim
    if cfg.family == "audio":
        return (None, None)  # whisper: sinusoidal absolute, added at embed
    if cfg.use_mrope and mrope_positions is not None:
        return L.mrope_angles(mrope_positions, hd, cfg.mrope_sections,
                              cfg.rope_theta)
    return L.rope_angles(positions, hd, cfg.rope_theta)


def _sinusoidal(s: int, d: int, offset=0):
    pos = jnp.arange(s) + offset
    half = d // 2
    freq = 10000.0 ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freq
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# encoder (whisper)


def encode(params, feats, cfg: ModelConfig, ctx: RunCtx):
    """feats (b, enc_s, d_model) — stubbed conv frontend output."""
    x = feats.astype(ctx.compute_dtype)
    x = x + _sinusoidal(x.shape[1], cfg.d_model).astype(x.dtype)[None]
    enc = params["encoder"]

    def body(x, bp):
        x, _ = block_fwd(bp, x, cfg, ctx, (ATTN_FULL, "dense"),
                         "bidir", 0, (None, None))
        return ctx.act(x), None

    if ctx.remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, enc["blocks"])
    return _norm(enc["final_norm"], x, cfg)


# ---------------------------------------------------------------------------
# forward (train / prefill)


def forward_hidden(params, tokens, cfg: ModelConfig, ctx: RunCtx,
                   pattern: Optional[Sequence[str]] = None,
                   mrope_positions=None, patch_embeds=None, audio_feats=None,
                   positions=None):
    """tokens (b, s) -> hidden (b, s, d), aux_loss."""
    sigs = layer_sigs(cfg)
    u, reps, rem = stack_plan(sigs)
    pattern = tuple(pattern) if pattern is not None else cfg.pattern
    b, s = tokens.shape

    with jax.named_scope("vocab"):
        x = jnp.take(params["embed"], tokens, axis=0).astype(ctx.compute_dtype)
    x = ctx.act(x)
    if cfg.family == "hybrid":  # gemma-style embedding scale
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    if patch_embeds is not None:
        npk = patch_embeds.shape[1]
        x = jnp.concatenate([patch_embeds.astype(x.dtype), x[:, npk:]], axis=1)
    if cfg.family == "audio":
        x = x + _sinusoidal(s, cfg.d_model).astype(x.dtype)[None]

    if positions is None:
        positions = jnp.arange(s)
    rope = _rope_for(cfg, positions, mrope_positions)

    enc_kv = None
    if cfg.encoder_layers:
        # cross K/V are projected per decoder block from the encoder output
        # (each block has its own wk/wv), so enc_kv is the raw encoder output.
        enc_kv = encode(params, audio_feats, cfg, ctx)

    # Resolve per-unit-position behaviour (kind may differ between the train
    # pattern and a long-context variant; params are identical).
    def pos_info(li):
        kind = pattern[li]
        base = cfg.pattern[li]
        window = cfg.window_size
        if base == ATTN_FULL and kind == ATTN_SWA:
            window = cfg.long_context_variant_window
        return sigs[li], kind, window

    aux_total = jnp.zeros((), jnp.float32)

    def unit_body(carry, unit_p):
        x, aux = carry
        for j in range(u):
            sig, kind, window = pos_info(j)  # periodic: li % u == j
            x, a = block_fwd(unit_p[f"p{j}"], x, cfg, ctx, sig, kind, window,
                             rope, enc_kv=_proj_cross(unit_p[f"p{j}"], enc_kv, cfg)
                             if enc_kv is not None else None)
            x = ctx.act(x)
            aux = aux + a
        return (x, aux), None

    body = jax.checkpoint(unit_body) if ctx.remat else unit_body
    (x, aux_total), _ = jax.lax.scan(body, (x, aux_total), params["unit"])

    for i in range(rem):
        li = u * reps + i
        sig, kind, window = (sigs[li], pattern[li],
                             cfg.long_context_variant_window
                             if cfg.pattern[li] == ATTN_FULL and pattern[li] == ATTN_SWA
                             else cfg.window_size)
        x, a = block_fwd(params["rest"][f"l{li}"], x, cfg, ctx, sig, kind,
                         window, rope,
                         enc_kv=_proj_cross(params["rest"][f"l{li}"], enc_kv, cfg)
                         if enc_kv is not None else None)
        aux_total = aux_total + a

    with jax.named_scope("vocab"):
        x = _norm(params["final_norm"], x, cfg)
    return ctx.act(x), aux_total


def _proj_cross(bp, enc_out, cfg):
    if enc_out is None:
        return None
    b, s, _ = enc_out.shape
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    ck = jnp.dot(enc_out, bp["cross"]["wk"]).reshape(b, s, kv, hd)
    cv = jnp.dot(enc_out, bp["cross"]["wv"]).reshape(b, s, kv, hd)
    return (ck, cv)


# ---------------------------------------------------------------------------
# loss


def lm_loss(params, hidden, labels, cfg: ModelConfig, ctx: RunCtx,
            loss_mask=None, normalize: bool = True):
    """Chunked softmax cross-entropy; full (b, s, V) logits never materialise.

    hidden (b, s, d); labels (b, s) int32. Returns mean nll over valid tokens.
    """
    with jax.named_scope("vocab"):
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        b, s, d = hidden.shape
        c = min(ctx.loss_chunk, s)
        assert s % c == 0
        nchunk = s // c
        hs = hidden.reshape(b, nchunk, c, d).swapaxes(0, 1)
        ls = labels.reshape(b, nchunk, c).swapaxes(0, 1)
        if loss_mask is None:
            loss_mask = jnp.ones((b, s), jnp.float32)
        ms = loss_mask.reshape(b, nchunk, c).swapaxes(0, 1)

        # checkpointed: the backward recomputes each chunk's logits instead
        # of stashing (b, c, V) probability tensors per chunk (the
        # flash-attention argument, applied to the LM head)
        @jax.checkpoint
        def chunk_nll(carry, inp):
            h, lab, m = inp
            logits = ctx.constrain(jnp.dot(h, head).astype(jnp.float32),
                                   (ctx.dp_axes, None, ctx.tp_axis))
            lse = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, lab[..., None],
                                       axis=-1)[..., 0]
            nll = (lse - gold) * m
            return carry + jnp.sum(nll), None

        total, _ = jax.lax.scan(chunk_nll, jnp.zeros((), jnp.float32),
                                (hs, ls, ms))
        if not normalize:
            return total
        return total / jnp.maximum(jnp.sum(loss_mask), 1.0)


def logits_fn(params, hidden, cfg: ModelConfig):
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return jnp.dot(hidden, head).astype(jnp.float32)
