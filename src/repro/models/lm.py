"""Model assembly: init / forward / decode for every assigned family.

Layer parameters are *stacked* along a leading ``[L, ...]`` axis and the
forward pass runs ``lax.scan`` over them: one traced/compiled copy of the
layer body regardless of depth — the in-program realization of the paper's
hierarchical "compile each definition once" insight (core/hier_compile.py).
``scan_layers=False`` switches to an unrolled Python loop, which is the
monolithic baseline measured in benchmarks/codegen_time.py.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from . import layers as L
from .config import ModelConfig

Params = Any


def _cdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def _stack_layers(rng, n: int, init_one):
    """Initialize n layers and stack leaves along axis 0."""
    ks = jax.random.split(rng, n)
    trees = [init_one(k) for k in ks]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_params(cfg: ModelConfig, rng: jax.Array) -> Params:
    dt = _cdtype(cfg)
    d = cfg.d_model
    r = jax.random.split(rng, 8)
    p: dict = {"embed": L._embed_init(r[0], cfg.vocab, d, dt),
               "final_norm": L.init_rmsnorm(d, dt)}
    if not cfg.tie_embeddings:
        p["lm_head"] = L._dense_init(r[1], d, cfg.vocab, dt)

    if cfg.family in ("dense", "vlm"):
        def one(k):
            ka, km = jax.random.split(k)
            return {"attn_norm": L.init_rmsnorm(d, dt),
                    "attn": L.init_attention(ka, cfg, dt),
                    "mlp_norm": L.init_rmsnorm(d, dt),
                    "mlp": L.init_mlp(km, d, cfg.d_ff, dt)}
        p["layers"] = _stack_layers(r[2], cfg.n_layers, one)
        if cfg.vlm is not None:
            p["patch_proj"] = L._dense_init(r[3], cfg.vlm.d_patch, d, dt)

    elif cfg.family == "moe":
        def one(k):
            ka, km = jax.random.split(k)
            return {"attn_norm": L.init_rmsnorm(d, dt),
                    "attn": L.init_attention(ka, cfg, dt),
                    "mlp_norm": L.init_rmsnorm(d, dt),
                    "moe": L.init_moe(km, cfg, dt)}
        p["layers"] = _stack_layers(r[2], cfg.n_layers, one)

    elif cfg.family == "ssm":
        def one(k):
            return {"norm": L.init_rmsnorm(d, dt),
                    "mamba": L.init_mamba2(k, cfg, dt)}
        p["layers"] = _stack_layers(r[2], cfg.n_layers, one)

    elif cfg.family == "hybrid":
        def one(k):
            return {"norm": L.init_rmsnorm(d, dt),
                    "mamba": L.init_mamba2(k, cfg, dt)}
        p["layers"] = _stack_layers(r[2], cfg.n_layers, one)
        ka, km = jax.random.split(r[3])
        p["shared_attn"] = {          # ONE set of weights, many call sites
            "attn_norm": L.init_rmsnorm(d, dt),
            "attn": L.init_attention(ka, cfg, dt),
            "mlp_norm": L.init_rmsnorm(d, dt),
            "mlp": L.init_mlp(km, d, cfg.d_ff, dt)}

    elif cfg.family == "audio":
        ed = cfg.encdec
        full = dataclasses.replace(cfg, n_kv_heads=cfg.n_heads)
        def enc_one(k):
            ka, km = jax.random.split(k)
            return {"attn_norm": L.init_layernorm(d, dt),
                    "attn": L.init_attention(ka, full, dt),
                    "mlp_norm": L.init_layernorm(d, dt),
                    "mlp": L.init_mlp2(km, d, cfg.d_ff, dt)}
        def dec_one(k):
            ka, kx, km = jax.random.split(k, 3)
            return {"attn_norm": L.init_layernorm(d, dt),
                    "attn": L.init_attention(ka, full, dt),
                    "xattn_norm": L.init_layernorm(d, dt),
                    "xattn": L.init_attention(kx, full, dt),
                    "mlp_norm": L.init_layernorm(d, dt),
                    "mlp": L.init_mlp2(km, d, cfg.d_ff, dt)}
        p["enc_layers"] = _stack_layers(r[2], ed.n_encoder_layers, enc_one)
        p["layers"] = _stack_layers(r[4], cfg.n_layers, dec_one)
        p["enc_pos"] = (jax.random.normal(
            r[5], (ed.n_audio_ctx, d), jnp.float32) * 0.01).astype(dt)
        p["enc_final_norm"] = L.init_layernorm(d, dt)
    else:
        raise ValueError(f"unknown family {cfg.family!r}")
    return p


def abstract_params(cfg: ModelConfig) -> Params:
    """Shape/dtype skeleton (no allocation) — used by the dry-run."""
    return jax.eval_shape(partial(init_params, cfg),
                          jax.ShapeDtypeStruct((2,), jnp.uint32))


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------

def _dense_block(lp, cfg: ModelConfig, h, positions, use_kernel):
    h = h + L.attention(lp["attn"], cfg,
                        L.rms_norm(lp["attn_norm"], h, cfg.norm_eps),
                        positions, use_kernel=use_kernel)
    h = h + L.mlp(lp["mlp"], L.rms_norm(lp["mlp_norm"], h, cfg.norm_eps))
    return h


def _moe_block(lp, cfg: ModelConfig, h, positions, use_kernel):
    h = h + L.attention(lp["attn"], cfg,
                        L.rms_norm(lp["attn_norm"], h, cfg.norm_eps),
                        positions, use_kernel=use_kernel)
    y, aux = L.moe_layer(lp["moe"],
                         cfg, L.rms_norm(lp["mlp_norm"], h, cfg.norm_eps))
    return h + y, aux


def _mamba_block(lp, cfg: ModelConfig, h, use_kernel):
    return h + L.mamba2_layer(lp["mamba"],
                              cfg, L.rms_norm(lp["norm"], h, cfg.norm_eps),
                              use_kernel=use_kernel)


def forward(params: Params, cfg: ModelConfig, tokens: jax.Array, *,
            extra: Optional[dict] = None, scan_layers: bool = True,
            remat: bool = False, use_kernel: bool = False) -> jax.Array:
    """Token logits for a full sequence (training / prefill).

    tokens: [B, S] int32.  ``extra`` carries modality-stub inputs:
    ``patches`` [B, n_patches, d_patch] (vlm) or ``frames`` [B, Ta, d]
    (audio).  Returns logits [B, S, vocab].
    """
    extra = extra or {}
    B, S = tokens.shape
    h = params["embed"][tokens]                     # [B, S, d]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    if cfg.vlm is not None and "patches" in extra:
        pe = (extra["patches"] @ params["patch_proj"]).astype(h.dtype)
        npatch = min(cfg.vlm.n_patches, S)
        h = jax.lax.dynamic_update_slice(h, pe[:, :npatch], (0, 0, 0))

    enc_out = None
    if cfg.family == "audio":
        enc_out = _encode_audio(params, cfg, extra["frames"],
                                scan_layers=scan_layers)

    aux_total = jnp.zeros((), jnp.float32)

    if cfg.family in ("dense", "vlm"):
        def body(hh, lp):
            return _dense_block(lp, cfg, hh, positions, use_kernel), None
        h = _run_layers(params["layers"], h, body, scan_layers, remat)

    elif cfg.family == "moe":
        def body(hh, lp):
            hh, aux = _moe_block(lp, cfg, hh, positions, use_kernel)
            return hh, aux
        h, auxs = _run_layers(params["layers"], h, body, scan_layers, remat,
                              collect=True)
        aux_total = jnp.sum(auxs)

    elif cfg.family == "ssm":
        def body(hh, lp):
            return _mamba_block(lp, cfg, hh, use_kernel), None
        h = _run_layers(params["layers"], h, body, scan_layers, remat)

    elif cfg.family == "hybrid":
        period = cfg.hybrid.attn_period
        shared = params["shared_attn"]

        def body(carry, xs):
            hh = carry
            lp, idx = xs
            hh = _mamba_block(lp, cfg, hh, use_kernel)
            def with_attn(v):
                return _dense_block(shared, cfg, v, positions, use_kernel)
            hh = jax.lax.cond((idx % period) == period - 1,
                              with_attn, lambda v: v, hh)
            return hh, None
        idxs = jnp.arange(cfg.n_layers)
        bfn = jax.checkpoint(body) if remat else body
        if scan_layers:
            h, _ = jax.lax.scan(bfn, h, (params["layers"], idxs))
        else:
            for i in range(cfg.n_layers):
                lp = jax.tree.map(lambda x: x[i], params["layers"])
                h, _ = bfn(h, (lp, jnp.asarray(i)))

    elif cfg.family == "audio":
        def body(hh, lp):
            hh = hh + L.attention(
                lp["attn"], cfg,
                L.layer_norm(lp["attn_norm"], hh, cfg.norm_eps), positions)
            q_in = L.layer_norm(lp["xattn_norm"], hh, cfg.norm_eps)
            ek = (enc_out @ lp["xattn"]["wk"]).reshape(
                B, -1, cfg.n_heads, cfg.hd)
            ev = (enc_out @ lp["xattn"]["wv"]).reshape(
                B, -1, cfg.n_heads, cfg.hd)
            hh = hh + L.attention(lp["xattn"], cfg, q_in, positions,
                                  causal=False, kv=(ek, ev))
            hh = hh + L.mlp2(lp["mlp"],
                             L.layer_norm(lp["mlp_norm"], hh, cfg.norm_eps))
            return hh, None
        h = _run_layers(params["layers"], h, body, scan_layers, remat)

    h = L.rms_norm(params["final_norm"], h, cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = h @ head
    return logits, aux_total



def _scan_over(body, carry, xs, scan: bool):
    """``lax.scan`` or a Python-unrolled loop over stacked [L, ...] pytrees.

    The unrolled form re-inlines the body L times — the monolithic
    compilation baseline (and the exact-cost lowering used by the roofline
    fit, since XLA's cost analysis counts a while-loop body once regardless
    of trip count)."""
    if scan:
        return jax.lax.scan(body, carry, xs)
    L = jax.tree.leaves(xs)[0].shape[0]
    ys = []
    for i in range(L):
        x_i = jax.tree.map(lambda v: v[i], xs)
        carry, y = body(carry, x_i)
        ys.append(y)
    if ys and ys[0] is not None:
        ys = jax.tree.map(lambda *zs: jnp.stack(zs), *ys)
    else:
        ys = None
    return carry, ys


def _run_layers(stacked, h, body, scan_layers, remat, collect=False):
    bfn = jax.checkpoint(body) if remat else body
    if scan_layers:
        h, ys = jax.lax.scan(bfn, h, stacked)
        return (h, ys) if collect else h
    n = jax.tree.leaves(stacked)[0].shape[0]
    ys = []
    for i in range(n):
        lp = jax.tree.map(lambda x: x[i], stacked)
        h, y = bfn(h, lp)
        ys.append(y)
    return (h, jnp.stack(ys)) if collect else h


def _encode_audio(params, cfg: ModelConfig, frames: jax.Array, *,
                  scan_layers: bool = True) -> jax.Array:
    """Whisper encoder over precomputed (stub) frame embeddings."""
    B, Ta, d = frames.shape
    h = frames.astype(_cdtype(cfg)) + params["enc_pos"][None, :Ta]
    positions = jnp.broadcast_to(jnp.arange(Ta, dtype=jnp.int32), (B, Ta))

    def body(hh, lp):
        hh = hh + L.attention(lp["attn"], cfg,
                              L.layer_norm(lp["attn_norm"], hh, cfg.norm_eps),
                              positions, causal=False)
        hh = hh + L.mlp2(lp["mlp"],
                         L.layer_norm(lp["mlp_norm"], hh, cfg.norm_eps))
        return hh, None

    h = _run_layers(params["enc_layers"], h, body, scan_layers, False)
    return L.layer_norm(params["enc_final_norm"], h, cfg.norm_eps)


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------

def loss_fn(params: Params, cfg: ModelConfig, batch: dict, *,
            scan_layers: bool = True, remat: bool = False,
            use_kernel: bool = False) -> jax.Array:
    logits, aux = forward(params, cfg, batch["tokens"],
                          extra={k: v for k, v in batch.items()
                                 if k in ("patches", "frames")},
                          scan_layers=scan_layers, remat=remat,
                          use_kernel=use_kernel)
    return L.softmax_xent(logits, batch["labels"], z_loss=1e-4) + aux


# ---------------------------------------------------------------------------
# prefill (full-sequence forward that also populates the decode cache)
# ---------------------------------------------------------------------------

def prefill(params: Params, cfg: ModelConfig, tokens: jax.Array, *,
            extra: Optional[dict] = None, max_seq: Optional[int] = None,
            use_kernel: bool = False, scan_layers: bool = True,
            true_len: Optional[jax.Array] = None) -> tuple[jax.Array, dict]:
    """Process a prompt; return (last-token logits [B, vocab], cache).

    The cache layout matches ``init_decode_cache(cfg, B, max_seq)`` so
    ``decode_step`` continues from it directly.

    ``true_len`` ([B] int32) enables *bucketed* prefill: ``tokens`` is
    right-padded to a shared bucket length, logits are gathered at each
    row's last real token, and ``cache["len"]`` becomes the per-row vector.
    Right padding is sound for attention-cache families because causal
    attention never lets a real token see a later pad position, and decode
    masks cache slots >= len — so the pad rows of K/V are dead weight, not
    wrong values.  (Recurrent families fold pads into their state, so the
    serving adapter keeps them on the per-slot path.)
    """
    extra = extra or {}
    B, S = tokens.shape
    max_seq = max_seq or S
    dt = _cdtype(cfg)
    h = params["embed"][tokens]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    if cfg.vlm is not None and "patches" in extra:
        pe = (extra["patches"] @ params["patch_proj"]).astype(h.dtype)
        npatch = min(cfg.vlm.n_patches, S)
        h = jax.lax.dynamic_update_slice(h, pe[:, :npatch], (0, 0, 0))

    enc_out = None
    if cfg.family == "audio":
        enc_out = _encode_audio(params, cfg, extra["frames"],
                                scan_layers=scan_layers)

    def pad_kv(k):   # [B, S, n, hd] -> [B, max_seq, n, hd]
        if max_seq == S:
            return k
        return jnp.pad(k, ((0, 0), (0, max_seq - S), (0, 0), (0, 0)))

    def pad_scale(sc):   # [B, S, n] -> [B, max_seq, n]
        if max_seq == S:
            return sc
        return jnp.pad(sc, ((0, 0), (0, max_seq - S), (0, 0)))

    cache: dict = {"len": jnp.asarray(S, jnp.int32) if true_len is None
                   else jnp.asarray(true_len, jnp.int32)}

    if cfg.family in ("dense", "vlm", "moe"):
        def body(hh, lp):
            x = L.rms_norm(lp["attn_norm"], hh, cfg.norm_eps)
            q, k, v = L._qkv(lp["attn"], cfg, x, positions)
            if use_kernel or cfg.attn_impl == "kernel":
                from ..kernels import ops as kops
                o = kops.flash_attention(q, k, v, causal=True,
                                         window=cfg.sliding_window)
            elif cfg.attn_impl == "chunked":
                o = L.sdpa_chunked(q, k, v, causal=True,
                                   window=cfg.sliding_window)
            else:
                o = L.sdpa(q, k, v, causal=True, window=cfg.sliding_window)
            hh = hh + o.reshape(B, S, cfg.n_heads * cfg.hd) @ lp["attn"]["wo"]
            if cfg.family == "moe":
                m, _ = L.moe_layer(
                    lp["moe"], cfg,
                    L.rms_norm(lp["mlp_norm"], hh, cfg.norm_eps))
                hh = hh + m
            else:
                hh = hh + L.mlp(lp["mlp"],
                                L.rms_norm(lp["mlp_norm"], hh, cfg.norm_eps))
            if cfg.kv_quant:
                qk, sk = L.quantize_kv(k)
                qv, sv = L.quantize_kv(v)
                return hh, (pad_kv(qk), pad_kv(qv),
                            pad_scale(sk), pad_scale(sv))
            return hh, (pad_kv(k.astype(dt)), pad_kv(v.astype(dt)))
        if cfg.kv_quant:
            h, (ck, cv, ks, vs) = _scan_over(body, h, params["layers"],
                                             scan_layers)
            cache.update(k=ck, v=cv, k_scale=ks, v_scale=vs)
        else:
            h, (ck, cv) = _scan_over(body, h, params["layers"], scan_layers)
            cache.update(k=ck, v=cv)

    elif cfg.family == "ssm":
        def body(hh, lp):
            x = L.rms_norm(lp["norm"], hh, cfg.norm_eps)
            y, st, conv = _mamba_prefill(lp["mamba"], cfg, x, use_kernel)
            return hh + y, (st, conv)
        h, (st, conv) = _scan_over(body, h, params["layers"], scan_layers)
        cache.update(ssm=st, conv=conv)

    elif cfg.family == "hybrid":
        period = cfg.hybrid.attn_period
        shared = params["shared_attn"]
        n_attn = cfg.n_layers // period
        kall = jnp.zeros((n_attn, B, max_seq, cfg.n_kv_heads, cfg.hd), dt)
        vall = jnp.zeros_like(kall)

        def body(carry, xs):
            hh, kall, vall = carry
            lp, idx = xs
            x = L.rms_norm(lp["norm"], hh, cfg.norm_eps)
            y, st, conv = _mamba_prefill(lp["mamba"], cfg, x, use_kernel)
            hh = hh + y

            def with_attn(op):
                hh, kall, vall = op
                g = idx // period
                x2 = L.rms_norm(shared["attn_norm"], hh, cfg.norm_eps)
                q, k, v = L._qkv(shared["attn"], cfg, x2, positions)
                o = L.sdpa(q, k, v, causal=True)
                hh = hh + o.reshape(B, S, cfg.n_heads * cfg.hd) \
                    @ shared["attn"]["wo"]
                hh = hh + L.mlp(
                    shared["mlp"],
                    L.rms_norm(shared["mlp_norm"], hh, cfg.norm_eps))
                kall = jax.lax.dynamic_update_index_in_dim(
                    kall, pad_kv(k.astype(dt)), g, 0)
                vall = jax.lax.dynamic_update_index_in_dim(
                    vall, pad_kv(v.astype(dt)), g, 0)
                return hh, kall, vall

            hh, kall, vall = jax.lax.cond(
                (idx % period) == period - 1, with_attn, lambda op: op,
                (hh, kall, vall))
            return (hh, kall, vall), (st, conv)

        idxs = jnp.arange(cfg.n_layers)
        (h, kall, vall), (st, conv) = _scan_over(
            body, (h, kall, vall), (params["layers"], idxs), scan_layers)
        cache.update(ssm=st, conv=conv, k=kall, v=vall)

    elif cfg.family == "audio":
        ed = cfg.encdec
        def body(hh, lp):
            x = L.layer_norm(lp["attn_norm"], hh, cfg.norm_eps)
            q, k, v = L._qkv(lp["attn"], cfg, x, positions)
            o = L.sdpa(q, k, v, causal=True)
            hh = hh + o.reshape(B, S, cfg.n_heads * cfg.hd) @ lp["attn"]["wo"]
            q_in = L.layer_norm(lp["xattn_norm"], hh, cfg.norm_eps)
            xk = (enc_out @ lp["xattn"]["wk"]).reshape(
                B, -1, cfg.n_heads, cfg.hd)
            xv = (enc_out @ lp["xattn"]["wv"]).reshape(
                B, -1, cfg.n_heads, cfg.hd)
            hh = hh + L.attention(lp["xattn"], cfg, q_in, positions,
                                  causal=False, kv=(xk, xv))
            hh = hh + L.mlp2(lp["mlp"],
                             L.layer_norm(lp["mlp_norm"], hh, cfg.norm_eps))
            return hh, (pad_kv(k.astype(dt)), pad_kv(v.astype(dt)),
                        xk.astype(dt), xv.astype(dt))
        h, (ck, cv, xk, xv) = _scan_over(body, h, params["layers"],
                                         scan_layers)
        cache.update(k=ck, v=cv, xk=xk, xv=xv)

    if true_len is None:
        h = h[:, -1:]
    else:
        # gather each row's last *real* token (bucket pad sits after it);
        # an empty row (len 0) clamps to position 0 — the caller treats it
        # as a dead row and discards its logits
        idx = jnp.clip(jnp.asarray(true_len, jnp.int32) - 1, 0, S - 1)
        h = jnp.take_along_axis(h, idx[:, None, None], axis=1)
    h = L.rms_norm(params["final_norm"], h, cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return (h[:, 0] @ head), cache


def _mamba_prefill(p, cfg: ModelConfig, x, use_kernel):
    """Mamba2 block that also returns (ssm_state, conv_state)."""
    s = cfg.ssm
    d = cfg.d_model
    di, nh = s.d_inner(d), s.n_heads(d)
    G, N = s.n_groups, s.d_state
    B, S, _ = x.shape
    zxbcdt = x @ p["in_proj"]
    z, xin, Bc, Cc, dtv = jnp.split(
        zxbcdt, [di, 2 * di, 2 * di + G * N, 2 * di + 2 * G * N], axis=-1)
    conv_in = jnp.concatenate([xin, Bc, Cc], axis=-1)
    conv_out, conv_state = L._causal_conv(conv_in, p["conv_w"], p["conv_b"])
    xin, Bc, Cc = jnp.split(conv_out, [di, di + G * N], axis=-1)
    dtv = jax.nn.softplus(dtv.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    y, state = L.ssd_chunked(
        xin.reshape(B, S, nh, s.head_dim), dtv, A,
        Bc.reshape(B, S, G, N), Cc.reshape(B, S, G, N), p["D"],
        chunk=min(s.chunk, S), use_kernel=use_kernel)
    y = y.reshape(B, S, di)
    y = L.rms_norm(p["norm"], y * jax.nn.silu(z), cfg.norm_eps)
    return y @ p["out_proj"], state.astype(jnp.float32), conv_state


# ---------------------------------------------------------------------------
# decode (one new token against a cache)
# ---------------------------------------------------------------------------

def init_decode_cache(cfg: ModelConfig, batch: int, max_seq: int,
                      abstract: bool = False) -> dict:
    """Cache pytree for serve_step.  With ``abstract=True`` returns
    ShapeDtypeStructs (dry-run, no allocation)."""
    dt = _cdtype(cfg)
    mk = (lambda s, d: jax.ShapeDtypeStruct(s, d)) if abstract \
        else (lambda s, d: jnp.zeros(s, d))
    c: dict = {"len": mk((), jnp.int32)}
    Lc, d = cfg.n_layers, cfg.d_model
    if cfg.family in ("dense", "vlm", "moe"):
        if cfg.kv_quant:
            c["k"] = mk((Lc, batch, max_seq, cfg.n_kv_heads, cfg.hd),
                        jnp.int8)
            c["v"] = mk((Lc, batch, max_seq, cfg.n_kv_heads, cfg.hd),
                        jnp.int8)
            c["k_scale"] = mk((Lc, batch, max_seq, cfg.n_kv_heads),
                              jnp.float16)
            c["v_scale"] = mk((Lc, batch, max_seq, cfg.n_kv_heads),
                              jnp.float16)
        else:
            c["k"] = mk((Lc, batch, max_seq, cfg.n_kv_heads, cfg.hd), dt)
            c["v"] = mk((Lc, batch, max_seq, cfg.n_kv_heads, cfg.hd), dt)
    elif cfg.family == "ssm":
        s = cfg.ssm
        c["ssm"] = mk((Lc, batch, s.n_heads(d), s.head_dim, s.d_state),
                      jnp.float32)
        c["conv"] = mk((Lc, batch, s.conv_width - 1,
                        s.d_inner(d) + 2 * s.n_groups * s.d_state), dt)
    elif cfg.family == "hybrid":
        s = cfg.ssm
        n_attn = cfg.n_layers // cfg.hybrid.attn_period
        c["ssm"] = mk((Lc, batch, s.n_heads(d), s.head_dim, s.d_state),
                      jnp.float32)
        c["conv"] = mk((Lc, batch, s.conv_width - 1,
                        s.d_inner(d) + 2 * s.n_groups * s.d_state), dt)
        c["k"] = mk((n_attn, batch, max_seq, cfg.n_kv_heads, cfg.hd), dt)
        c["v"] = mk((n_attn, batch, max_seq, cfg.n_kv_heads, cfg.hd), dt)
    elif cfg.family == "audio":
        c["k"] = mk((Lc, batch, max_seq, cfg.n_heads, cfg.hd), dt)
        c["v"] = mk((Lc, batch, max_seq, cfg.n_heads, cfg.hd), dt)
        ed = cfg.encdec
        c["xk"] = mk((Lc, batch, ed.n_audio_ctx, cfg.n_heads, cfg.hd), dt)
        c["xv"] = mk((Lc, batch, ed.n_audio_ctx, cfg.n_heads, cfg.hd), dt)
    return c


def decode_step(params: Params, cfg: ModelConfig, token: jax.Array,
                cache: dict, *, scan_layers: bool = True) -> tuple:
    """serve_step: one new token for every sequence in the batch.

    token: [B] int32.  Returns (logits [B, vocab], new cache).  Runs a
    ``lax.scan`` over the stacked per-layer cache slices so the decode body
    is compiled once per *definition*, not per layer.
    """
    B = token.shape[0]
    h = params["embed"][token][:, None, :]           # [B, 1, d]
    clen = cache["len"]
    new_cache = dict(cache)

    if cfg.family in ("dense", "vlm", "moe"):
        def body(hh, xs):
            if cfg.kv_quant:
                lp, ck, cv, ks, vs = xs
                y, nk, nv, nks, nvs = L.attention_decode(
                    lp["attn"], cfg,
                    L.rms_norm(lp["attn_norm"], hh, cfg.norm_eps), ck, cv,
                    clen, k_scale=ks, v_scale=vs)
            else:
                lp, ck, cv = xs
                y, nk, nv = L.attention_decode(
                    lp["attn"], cfg,
                    L.rms_norm(lp["attn_norm"], hh, cfg.norm_eps), ck, cv,
                    clen)
            hh = hh + y
            if cfg.family == "moe":
                m, _ = L.moe_layer(lp["moe"], cfg,
                                   L.rms_norm(lp["mlp_norm"], hh,
                                              cfg.norm_eps))
                hh = hh + m
            else:
                hh = hh + L.mlp(lp["mlp"],
                                L.rms_norm(lp["mlp_norm"], hh, cfg.norm_eps))
            return hh, ((nk, nv, nks, nvs) if cfg.kv_quant else (nk, nv))
        if cfg.kv_quant:
            h, (nk, nv, nks, nvs) = _scan_over(
                body, h, (params["layers"], cache["k"], cache["v"],
                          cache["k_scale"], cache["v_scale"]), scan_layers)
            new_cache.update(k=nk, v=nv, k_scale=nks, v_scale=nvs)
        else:
            h, (nk, nv) = _scan_over(
                body, h, (params["layers"], cache["k"], cache["v"]),
                scan_layers)
            new_cache.update(k=nk, v=nv)

    elif cfg.family == "ssm":
        def body(hh, xs):
            lp, ss, cs = xs
            y, nss, ncs = L.mamba2_decode(
                lp["mamba"], cfg,
                L.rms_norm(lp["norm"], hh, cfg.norm_eps), ss, cs)
            return hh + y, (nss, ncs)
        h, (nss, ncs) = _scan_over(
            body, h, (params["layers"], cache["ssm"], cache["conv"]),
            scan_layers)
        new_cache.update(ssm=nss, conv=ncs)

    elif cfg.family == "hybrid":
        period = cfg.hybrid.attn_period
        shared = params["shared_attn"]

        # Interleave shared-attn blocks exactly as in forward(): after mamba
        # layers period-1, 2*period-1, ...  The per-block KV caches ride in
        # the scan carry and are dynamically indexed by block id.
        def body(carry, xs):
            hh, kall, vall = carry
            lp, ss, cs, idx = xs
            y, nss, ncs = L.mamba2_decode(
                lp["mamba"], cfg,
                L.rms_norm(lp["norm"], hh, cfg.norm_eps), ss, cs)
            hh = hh + y

            def with_attn(op):
                hh, kall, vall = op
                g = idx // period                    # block id
                ck = jax.lax.dynamic_index_in_dim(kall, g, 0, False)
                cv = jax.lax.dynamic_index_in_dim(vall, g, 0, False)
                y2, nk, nv = L.attention_decode(
                    shared["attn"], cfg,
                    L.rms_norm(shared["attn_norm"], hh, cfg.norm_eps),
                    ck, cv, clen)
                hh = hh + y2
                hh = hh + L.mlp(
                    shared["mlp"],
                    L.rms_norm(shared["mlp_norm"], hh, cfg.norm_eps))
                kall = jax.lax.dynamic_update_index_in_dim(kall, nk, g, 0)
                vall = jax.lax.dynamic_update_index_in_dim(vall, nv, g, 0)
                return hh, kall, vall

            hh, kall, vall = jax.lax.cond(
                (idx % period) == period - 1, with_attn, lambda op: op,
                (hh, kall, vall))
            return (hh, kall, vall), (nss, ncs)

        idxs = jnp.arange(cfg.n_layers)
        (h, nk, nv), (nss, ncs) = _scan_over(
            body, (h, cache["k"], cache["v"]),
            (params["layers"], cache["ssm"], cache["conv"], idxs),
            scan_layers)
        new_cache.update(ssm=nss, conv=ncs, k=nk, v=nv)

    elif cfg.family == "audio":
        def body(hh, xs):
            lp, ck, cv, xk, xv = xs
            y, nk, nv = L.attention_decode(
                lp["attn"], cfg,
                L.layer_norm(lp["attn_norm"], hh, cfg.norm_eps), ck, cv,
                clen)
            hh = hh + y
            q_in = L.layer_norm(lp["xattn_norm"], hh, cfg.norm_eps)
            hh = hh + L.attention(lp["xattn"], cfg, q_in,
                                  jnp.zeros((B, 1), jnp.int32),
                                  causal=False, kv=(xk, xv))
            hh = hh + L.mlp2(lp["mlp"],
                             L.layer_norm(lp["mlp_norm"], hh, cfg.norm_eps))
            return hh, (nk, nv)
        h, (nk, nv) = _scan_over(
            body, h, (params["layers"], cache["k"], cache["v"],
                      cache["xk"], cache["xv"]), scan_layers)
        new_cache.update(k=nk, v=nv)

    h = L.rms_norm(params["final_norm"], h, cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = (h[:, 0] @ head)
    new_cache["len"] = clen + 1
    return logits, new_cache


# ---------------------------------------------------------------------------
# packed-slot serving: one batched decode step for the whole slot array
# ---------------------------------------------------------------------------
#
# The serving engine keeps ONE cache pytree of shape [..., slots, ...] (the
# batch axis of every leaf is axis 1, mirroring init_decode_cache) plus a
# per-slot ``len`` vector.  Admission writes a prefilled request's rows into
# a slot, retirement zeroes its length, and the decode step runs once per
# iteration over all slots — live or dead — with dead slots masked by
# ``len == 0``.  See docs/serving.md.

def init_packed_cache(cfg: ModelConfig, slots: int, max_seq: int,
                      abstract: bool = False) -> dict:
    """Decode cache for ``slots`` packed sequences with per-slot lengths."""
    c = init_decode_cache(cfg, slots, max_seq, abstract=abstract)
    c["len"] = (jax.ShapeDtypeStruct((slots,), jnp.int32) if abstract
                else jnp.zeros((slots,), jnp.int32))
    return c


def write_slot(packed: dict, cache: dict, row: jax.Array,
               slot: jax.Array) -> dict:
    """Copy row ``row`` of a prefill ``cache`` into slot ``slot`` of the
    packed cache.  ``cache["len"]`` must be the per-row vector form
    (``prefill(..., true_len=...)``).  Pure; jit with the packed cache
    donated so XLA updates the slot in place."""
    out = {}
    for key, dst in packed.items():
        if key == "len":
            val = jax.lax.dynamic_index_in_dim(
                jnp.asarray(cache["len"], jnp.int32), row, 0, False)
            out[key] = jax.lax.dynamic_update_index_in_dim(dst, val, slot, 0)
        else:
            src = jax.lax.dynamic_slice_in_dim(cache[key], row, 1, axis=1)
            out[key] = jax.lax.dynamic_update_slice_in_dim(
                dst, src.astype(dst.dtype), slot, axis=1)
    return out


def retire_slot(packed: dict, slot: jax.Array) -> dict:
    """Free a slot: zero its length.  The stale K/V rows become dead weight
    (masked by ``len``) until the next admission overwrites them."""
    return dict(packed, len=packed["len"].at[slot].set(0))


def sample_tokens(logits: jax.Array, key: Optional[jax.Array] = None,
                  temperature: float = 0.0, top_k: int = 0) -> jax.Array:
    """On-device sampling epilogue: [B, V] logits -> [B] int32 tokens.

    ``temperature <= 0`` (or no key) is greedy argmax; otherwise
    temperature-scaled categorical, optionally truncated to the top-k
    logits.  Runs inside the jitted decode step so the host fetches one
    small token vector per step instead of per-slot logits."""
    if temperature <= 0.0 or key is None:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits.astype(jnp.float32) / temperature
    if top_k and top_k < scaled.shape[-1]:
        kth = jax.lax.top_k(scaled, top_k)[0][..., -1:]
        scaled = jnp.where(scaled < kth, -1e30, scaled)
    return jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)


@dataclasses.dataclass
class ServingAdapter:
    """The batched-decode protocol consumed by ``ServingEngine``.

    ``prefill_fn(params, tokens[B,S], true_len[B], step)
    -> (first_tok[B], cache)``
    ``step_fn(params, tokens[slots], packed, step) -> (next_tok[slots],
    packed)``
    ``write_slot_fn(packed, cache, row, slot) -> packed``
    ``retire_fn(packed, slot) -> packed``

    All four are pure jax functions (NOT pre-jitted): the engine compiles
    them through the persistent compile cache so a fresh process resolves
    every previously-seen shape from disk.  The weights ride as the
    ``params`` argument, never as closure constants: an executable is
    keyed and serialized by their shapes, not their values, and weights
    of any size stay device buffers.  ``step`` is a traced int32 scalar
    (the global step counter) feeding the sampler's fold_in — it does not
    trigger recompiles.
    """
    cfg: ModelConfig
    max_seq: int
    prefill_fn: Any
    step_fn: Any
    write_slot_fn: Any
    retire_fn: Any
    temperature: float = 0.0
    top_k: int = 0
    params: Params = None

    def init_slots(self, slots: int, abstract: bool = False) -> dict:
        return init_packed_cache(self.cfg, slots, self.max_seq,
                                 abstract=abstract)


def serving_adapter(params: Params, cfg: ModelConfig, *, max_seq: int,
                    temperature: float = 0.0, top_k: int = 0, seed: int = 0,
                    scan_layers: bool = True) -> ServingAdapter:
    """Build the packed-slot batched decode adapter for a model.

    Only attention-cache families qualify: right-padded bucketed prefill is
    exact for them (see ``prefill``).  Recurrent state (ssm/hybrid) and
    encoder-decoder extras (audio) would absorb pad tokens, so those
    families stay on the engine's per-slot fallback.
    """
    if cfg.family not in ("dense", "vlm", "moe"):
        raise ValueError(
            f"batched serving supports attention-cache families "
            f"(dense/vlm/moe), not {cfg.family!r}; use the per-slot path")
    base_key = jax.random.PRNGKey(seed)

    def _sample(logits, step):
        key = jax.random.fold_in(base_key, step)
        return sample_tokens(logits, key, temperature, top_k)

    def prefill_fn(params, tokens, true_len, step):
        logits, cache = prefill(params, cfg, tokens, max_seq=max_seq,
                                true_len=true_len, scan_layers=scan_layers)
        return _sample(logits, step), cache

    def step_fn(params, tokens, packed, step):
        live = packed["len"] > 0
        logits, ncache = decode_step(params, cfg, tokens, packed,
                                     scan_layers=scan_layers)
        # dead slots must stay at len 0 (liveness is derived from it) and
        # emit a harmless pad token
        ncache["len"] = jnp.where(live, packed["len"] + 1, 0)
        nxt = _sample(logits, step)
        return jnp.where(live, nxt, 0).astype(jnp.int32), ncache

    return ServingAdapter(cfg=cfg, max_seq=max_seq,
                          prefill_fn=prefill_fn, step_fn=step_fn,
                          write_slot_fn=write_slot, retire_fn=retire_slot,
                          temperature=temperature, top_k=top_k,
                          params=params)
