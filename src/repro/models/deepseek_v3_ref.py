"""Plain reference of DeepSeek-V3's layers for one device of an
expert-parallel deployment: ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, seeded random weights, one
sequence at a time through attention, no kernels.

It follows the model's published description (DeepSeek-V3 technical
report, arXiv:2412.19437, §2.1, and its ``config.json``):

* multi-head latent attention: queries through the ``q_lora_rank``
  bottleneck (RMS-normed), keys and values up-projected from the
  RMS-normed ``kv_lora_rank`` latent, a per-head RoPE query part and one
  RoPE key part shared by the heads. ``mla_naive`` up-projects every
  cached latent to per-head keys and values and attends causally, query by
  query over its visible keys; ``mla_absorbed`` (one new token a sequence)
  folds ``W_UK`` into the query and ``W_UV`` after attending the latent
  cache itself;
* MoE: sigmoid scores, a bias added for the choice only, each group scored
  by its two best biased scores, the top-k drawn from the best
  ``topk_group`` groups, the chosen unbiased scores normalised and scaled
  by ``routed_scaling_factor``; the routed experts computed only for the
  experts this device holds (its part of the output), the shared expert on
  every token; every FFN a SwiGLU.

Departures: RoPE is plain at ``rope_theta`` (no YaRN interpolation or
attention-scale correction); the multi-token-prediction module is left
out; the embedding lookup is left out (layers take hidden states).

Beside its outputs it tallies, per op class of
``repro.dvfs_runtime.telemetry.step_ops``, the FLOPs and bytes of every
matmul it performs (an (m x k) @ (k x n) product: 2mkn FLOPs; its input,
weight and output read or written once at the deployment's byte widths),
the RoPE elements it rotates (3 FLOPs each), the dispatch and combine
bytes of the (token, expert) pairs it routes, and how many of those pairs
landed on the experts held here.
"""
from __future__ import annotations

import collections
import functools
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.dvfs_runtime.telemetry import Deployment


def _highest(fn):
    """Run ``fn`` with float32 matmuls at full precision (a TPU's default
    is lower)."""
    @functools.wraps(fn)
    def wrapped(*args, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kw)
    return wrapped


class Tally:
    """``ops[name] = [flops, bytes, collective bytes]`` and the routed
    (token, expert) pairs on held experts."""

    def __init__(self, dep: Deployment, prefix: str = ""):
        self.dep, self.prefix = dep, prefix
        self.ops: Dict[str, list] = collections.defaultdict(
            lambda: [0, 0, 0])
        self.routed = 0

    def add(self, name: str, flops: int, bytes_: int, coll: int = 0):
        op = self.ops[self.prefix + name]
        op[0] += int(flops)
        op[1] += int(bytes_)
        op[2] += int(coll)

    def mm(self, name: str, x, w, a: int, wb: int, o: int):
        """``x @ w`` tallied at input width ``a``, weight width ``wb`` and
        output width ``o``."""
        (m, k), n = x.shape, w.shape[1]
        self.add(name, 2 * m * k * n, m * k * a + k * n * wb + m * n * o)
        return x @ w


def _w(key, shape):
    return jax.random.normal(key, shape, jnp.float32) / np.sqrt(shape[-2])


def init_layer(cfg: ModelConfig, key, moe: bool) -> dict:
    """One layer's weights: MLA, then a dense FFN or the MoE block."""
    d, H = cfg.d_model, cfg.n_heads
    nope, rope, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, \
        cfg.v_head_dim
    qr, kr = cfg.q_lora_rank, cfg.kv_lora_rank
    ks = iter(jax.random.split(key, 16))
    p = {"wq_a": _w(next(ks), (d, qr)), "wq_b": _w(next(ks), (qr, H * (nope + rope))),
         "wkv_a": _w(next(ks), (d, kr + rope)),
         "wkv_b": _w(next(ks), (kr, H * (nope + dv))),
         "wo": _w(next(ks), (H * dv, d))}
    if not moe:
        p["ffn"] = (_w(next(ks), (d, 2 * cfg.d_ff)), _w(next(ks), (cfg.d_ff, d)))
        return p
    e = cfg.moe
    E, ff = e.num_experts, e.expert_d_ff
    ffs = e.num_shared * (e.shared_d_ff or ff)
    p["gate"] = _w(next(ks), (d, E))
    p["bias"] = 0.1 * jax.random.normal(next(ks), (E,), jnp.float32)
    p["experts"] = [(_w(k1, (d, 2 * ff)), _w(k2, (ff, d))) for k1, k2 in
                    zip(jax.random.split(next(ks), E),
                        jax.random.split(next(ks), E))]
    p["shared"] = (_w(next(ks), (d, 2 * ffs)), _w(next(ks), (ffs, d)))
    return p


def init_cache(cfg: ModelConfig, key, batch: int, length: int):
    """A latent cache of ``length`` tokens a sequence: RMS-normed latents
    ``(batch, length, kv_lora_rank)`` and RoPE'd shared keys ``(batch,
    length, qk_rope_head_dim)``."""
    k1, k2 = jax.random.split(key)
    c = _rms(jax.random.normal(k1, (batch, length, cfg.kv_lora_rank)),
             cfg.norm_eps)
    return c, jax.random.normal(k2, (batch, length, cfg.qk_rope_head_dim))


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def _rope(x, pos, theta, t: Tally):
    """Rotate the last axis of ``x`` (``(..., tokens, [heads,] rope)``
    with token positions ``pos``) in two halves."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None] * freq[None, :]
    if x.ndim == 3:
        ang = ang[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    t.add("rope", 3 * x.size, 2 * x.size * t.dep.combine_bytes)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _queries_and_latents(p, cfg, x, pos, t: Tally):
    """Per-head queries ``(B, C, H, nope + rope)`` and the new tokens'
    latents ``(B, C, kr)`` and RoPE keys ``(B, C, rope)``."""
    dep = t.dep
    a, w, o, c = dep.dispatch_bytes, dep.weight_bytes, dep.combine_bytes, \
        dep.cache_bytes
    B, C, d = x.shape
    H, nope, rope = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    kr = cfg.kv_lora_rank
    xs = x.reshape(B * C, d)
    ql = _rms(t.mm("q_down", xs, p["wq_a"], a, w, o), cfg.norm_eps)
    q = t.mm("q_up", ql, p["wq_b"], a, w, o).reshape(B, C, H, nope + rope)
    kv = t.mm("kv_down", xs, p["wkv_a"], a, w, c).reshape(B, C, kr + rope)
    q_pe = jnp.stack([_rope(q[b, :, :, nope:], pos[b], cfg.rope_theta, t)
                      for b in range(B)])
    k_pe = jnp.stack([_rope(kv[b, :, kr:], pos[b], cfg.rope_theta, t)
                      for b in range(B)])
    q = jnp.concatenate([q[..., :nope], q_pe], -1)
    return q, _rms(kv[..., :kr], cfg.norm_eps), k_pe


@_highest
def mla_naive(p, cfg: ModelConfig, x, cache, t: Tally):
    """Attention of ``x`` ``(B, C, d)``, new tokens after the cached
    ``cache`` = (latents, RoPE keys) of ``P`` tokens a sequence: every
    latent up-projected to per-head keys and values; each query attends
    the keys up to itself. Returns ``(B, C, d)`` and the grown cache."""
    dep = t.dep
    a, w, o, c = dep.dispatch_bytes, dep.weight_bytes, dep.combine_bytes, \
        dep.cache_bytes
    B, C, _ = x.shape
    H, nope, dv = cfg.n_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    P = cache[0].shape[1]
    pos = jnp.broadcast_to(jnp.arange(P, P + C, dtype=jnp.float32), (B, C))
    q, lat, k_pe = _queries_and_latents(p, cfg, x, pos, t)
    lat = jnp.concatenate([cache[0], lat], 1)
    k_pe = jnp.concatenate([cache[1], k_pe], 1)
    S = P + C
    kv = t.mm("kv_up", lat.reshape(B * S, -1), p["wkv_b"], c, w, o)
    kv = kv.reshape(B, S, H, nope + dv)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_pe[:, :, None, :], (B, S, H, k_pe.shape[-1]))], -1)
    v = kv[..., nope:]
    scale = 1.0 / np.sqrt(q.shape[-1])
    outs, flops = [], 0
    for b in range(B):
        for i in range(C):
            kk, vv = k[b, :P + i + 1], v[b, :P + i + 1]
            s = jnp.einsum("hd,shd->hs", q[b, i], kk) * scale
            outs.append(jnp.einsum("hs,shd->hd", jax.nn.softmax(s, -1), vv))
            flops += 2 * H * kk.shape[0] * kk.shape[-1] \
                + 2 * H * vv.shape[0] * vv.shape[-1]
    out = jnp.stack(outs).reshape(B * C, H * dv)
    t.add("attention", flops,
          (q.size + kv[..., :nope].size + k_pe.size + v.size + out.size) * o)
    return t.mm("o_proj", out, p["wo"], a, w, o).reshape(B, C, -1), \
        (lat, k_pe)


@_highest
def mla_absorbed(p, cfg: ModelConfig, x, cache, t: Tally):
    """Attention of one new token a sequence (``x`` ``(B, 1, d)``) in the
    absorbed form: the query folded through ``W_UK`` into latent space,
    scored against the latent cache and its RoPE keys, the latent context
    taken through ``W_UV``. Returns ``(B, 1, d)`` and the grown cache."""
    dep = t.dep
    a, w, o, c = dep.dispatch_bytes, dep.weight_bytes, dep.combine_bytes, \
        dep.cache_bytes
    B = x.shape[0]
    H, nope, dv = cfg.n_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    kr = cfg.kv_lora_rank
    P = cache[0].shape[1]
    pos = jnp.full((B, 1), P, jnp.float32)
    q, lat, k_pe = _queries_and_latents(p, cfg, x, pos, t)
    lat = jnp.concatenate([cache[0], lat], 1)
    k_pe = jnp.concatenate([cache[1], k_pe], 1)
    wkv = p["wkv_b"].reshape(kr, H, nope + dv)
    w_uk, w_uv = wkv[..., :nope], wkv[..., nope:]
    q_nope, q_pe = q[:, 0, :, :nope], q[:, 0, :, nope:]
    q_lat = jnp.einsum("bhn,khn->bhk", q_nope, w_uk)
    t.add("absorb_uk", 2 * B * H * nope * kr,
          (q_nope.size + q_lat.size) * o + w_uk.size * w)
    scale = 1.0 / np.sqrt(nope + q_pe.shape[-1])
    ctx = []
    for b in range(B):
        s = (q_lat[b] @ lat[b].T + q_pe[b] @ k_pe[b].T) * scale
        ctx.append(jax.nn.softmax(s, -1) @ lat[b])
        S = lat.shape[1]
        t.add("attention", 2 * H * S * (kr + k_pe.shape[-1]) + 2 * H * S * kr,
              (q_lat[b].size + q_pe[b].size + ctx[-1].size) * o
              + (lat[b].size + k_pe[b].size) * c)
    ctx = jnp.stack(ctx)
    out = jnp.einsum("bhk,khv->bhv", ctx, w_uv)
    t.add("absorb_uv", 2 * B * H * kr * dv,
          (ctx.size + out.size) * o + w_uv.size * w)
    return t.mm("o_proj", out.reshape(B, H * dv), p["wo"], a, w, o
                ).reshape(B, 1, -1), (lat, k_pe)


@_highest
def ffn(name: str, wts, x, t: Tally):
    """SwiGLU: the fused gate/up matmul, then the down matmul."""
    dep = t.dep
    a, w, o = dep.dispatch_bytes, dep.weight_bytes, dep.combine_bytes
    gu = t.mm(name, x, wts[0], a, w, o)
    g, u = jnp.split(gu, 2, -1)
    return t.mm(name, jax.nn.silu(g) * u, wts[1], a, w, o)


@_highest
def route(p, cfg: ModelConfig, x, t: Tally) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Each token's ``top_k`` experts and their weights."""
    e, o = cfg.moe, t.dep.combine_bytes
    T = x.shape[0]
    scores = jax.nn.sigmoid(t.mm("router", x, p["gate"], o, o, o))
    biased = scores + p["bias"]
    groups = biased.reshape(T, e.n_group, -1)
    g_score = jax.lax.top_k(groups, 2)[0].sum(-1)
    g_keep = jax.lax.top_k(g_score, e.topk_group)[1]
    mask = jnp.zeros((T, e.n_group), bool).at[
        jnp.arange(T)[:, None], g_keep].set(True)
    masked = jnp.where(jnp.repeat(mask, e.num_experts // e.n_group, -1),
                       biased, -jnp.inf)
    idx = jax.lax.top_k(masked, e.top_k)[1]
    wts = jnp.take_along_axis(scores, idx, -1)
    wts = wts / wts.sum(-1, keepdims=True)
    return idx, wts * e.routed_scaling_factor


@_highest
def moe(p, cfg: ModelConfig, x, held: Sequence[int], t: Tally):
    """The MoE block on ``x`` ``(T, d)`` for the experts ``held`` here:
    ``(routed part, shared part)``, each ``(T, d)``."""
    dep = t.dep
    T, d = x.shape
    k = cfg.moe.top_k
    idx, wts = route(p, cfg, x, t)
    t.add("dispatch", 0, T * k * d * dep.dispatch_bytes,
          T * k * d * dep.dispatch_bytes)
    idx_np = np.asarray(idx)
    out = jnp.zeros_like(x)
    for ex in held:
        tok, slot = np.nonzero(idx_np == ex)
        t.routed += tok.size
        y = ffn("experts", p["experts"][ex], x[tok], t)
        out = out.at[tok].add(wts[tok, slot][:, None] * y)
    shared = ffn("shared", p["shared"], x, t)
    t.add("combine", 0, T * k * d * dep.combine_bytes,
          T * k * d * dep.combine_bytes)
    return out, shared


@_highest
def layer(p, cfg: ModelConfig, x, cache, t: Tally, absorbed: bool,
          held: Sequence[int] = ()):
    """One pre-norm layer on ``x`` ``(B, C, d)``: attention (absorbed or
    naive), then the dense FFN or, where ``p`` has a router, the MoE
    block over ``held``. Returns the output and the grown cache."""
    B, C, d = x.shape
    attn = mla_absorbed if absorbed else mla_naive
    a, cache = attn(p, cfg, _rms(x, cfg.norm_eps), cache, t)
    h = x + a
    hn = _rms(h, cfg.norm_eps).reshape(B * C, d)
    if "gate" in p:
        routed, shared = moe(p, cfg, hn, held, t)
        f = routed + shared
    else:
        f = ffn("ffn", p["ffn"], hn, t)
    return h + f.reshape(B, C, d), cache


@_highest
def head(w, cfg: ModelConfig, x, t: Tally):
    """Logits of each sequence's last token."""
    o = t.dep.combine_bytes
    return t.mm("head", _rms(x[:, -1], cfg.norm_eps), w, o, o, o)
