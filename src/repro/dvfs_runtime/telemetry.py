"""Arch-derived workload traces: map a model's train/serve step onto a
PCSTALL program (the beyond-paper TPU integration, DESIGN.md §3).

Each op in the step program becomes a run of PC blocks whose frequency
sensitivity comes from its arithmetic intensity relative to the device's
ridge point (peak_flops / hbm_bw; ~240 flops/byte on a TPU v5e):
compute-bound ops scale with core frequency, HBM-bound ops don't (the
`s_waitcnt` analogue is DMA wait). Collectives map to near-zero-sensitivity
"async" blocks.

The resulting Program plugs straight into repro.core.simulate — PCSTALL
predicts the per-device phase schedule of the training step, which is
*exactly* the paper's insight transplanted: a training step is a small,
iteratively re-executed program, so a PC-indexed table converges within a
handful of steps.

Where the step runs is a :class:`Deployment`. The default is a TPU v5e
with the whole model's op list split evenly over 256 chips. A deployment
with an expert-parallel degree ``ep`` describes one device of a serving
unit instead (DeepSeek-V3's EP32 prefill and EP144 decode units, say):
attention and the shared expert run data-parallel on the device's own
sequences, the routed experts are spread over the ``ep`` devices, each
computes only the (token, expert) pairs routed to the experts it holds
and reads only their weights, and the dispatch and combine all-to-all
are collective bytes.
"""
from __future__ import annotations

import dataclasses
from typing import List, Mapping, NamedTuple, Tuple, Union

import numpy as np

from repro.configs.base import ModelConfig, ShapeConfig
from repro.core.workloads import Program, _finalize
from repro.roofline.hlo_analysis import HBM_BW, ICI_BW, PEAK_FLOPS


@dataclasses.dataclass(frozen=True)
class Deployment:
    """One device of a deployment: its peaks, the byte widths of what it
    moves, and how the model is spread over devices."""
    peak_flops: float = PEAK_FLOPS   # dense FLOP/s of BF16 matmuls
    fp8_flops: float = PEAK_FLOPS    # dense FLOP/s of FP8 matmuls
    hbm_bw: float = HBM_BW           # bytes/s
    coll_bw: float = ICI_BW          # all-to-all bytes/s per device
    # devices the op list is split over evenly (1 where the op list is
    # already one device's own, as under expert parallelism)
    chips: int = 256
    # expert-parallel degree (0: the whole model's op list); data-parallel
    # degree of attention; redundant expert copies over the EP group
    ep: int = 0
    dp: int = 1
    redundant: int = 0
    # routed (token, expert) pairs on this device's experts over the
    # balanced share
    load: float = 1.0
    # bytes per element: matmul weights; FP8 matmul inputs and the
    # dispatch; matmul outputs, the attention core and the combine; the
    # KV cache
    weight_bytes: int = 2
    dispatch_bytes: int = 2
    combine_bytes: int = 2
    cache_bytes: int = 2

    def __post_init__(self):
        if self.ep and self.chips != 1:
            raise ValueError("an expert-parallel deployment gives one "
                             "device's ops: chips must be 1")

    def experts_held(self, n_experts: int) -> int:
        """Routed experts on this device, redundant copies included."""
        total = n_experts + self.redundant
        if not self.ep or total % self.ep:
            raise ValueError(f"{total} experts do not split over EP "
                             f"{self.ep}")
        return total // self.ep


DEFAULT_DEPLOYMENT = Deployment()

# one Deployment for every step, or one per step kind ("prefill",
# "decode", "train"): a serving system's prefill and decode units
Deployments = Union[Deployment, Mapping[str, Deployment]]


def unit(dep: Deployments, kind: str) -> Deployment:
    """The deployment that runs steps of ``kind``."""
    return dep[kind] if isinstance(dep, Mapping) else dep


@dataclasses.dataclass(frozen=True)
class Step:
    """One device step as a serving loop describes it: ``batch``
    sequences on this device, ``chunk`` new tokens each (1 for decode),
    the last of which attends ``context`` keys, itself included; ``load``
    is the routed load on this device's experts over the balanced share."""
    kind: str  # 'prefill' | 'decode'
    batch: int
    context: int
    chunk: int = 1
    load: float = 1.0

    @property
    def name(self) -> str:
        return (f"{self.kind}.b{self.batch}.c{self.context}.k{self.chunk}"
                f".l{self.load:g}")

    @property
    def shape(self) -> ShapeConfig:
        if self.kind == "decode":
            return ShapeConfig(self.name, self.context, self.batch, "decode")
        return ShapeConfig(self.name, self.chunk, self.batch, self.kind,
                           prefix=self.context - self.chunk)


class Op(NamedTuple):
    """One op class of a step: FLOPs, HBM bytes, collective bytes, and
    whether its FLOPs run at the FP8 peak."""
    name: str
    flops: float
    bytes: float
    coll_bytes: float = 0.0
    fp8: bool = False


def step_ops(cfg: ModelConfig, shape: ShapeConfig,
             dep: Deployment = DEFAULT_DEPLOYMENT) -> List[Op]:
    """Analytic (flops, hbm bytes, collective bytes) per op class for one
    step of this (arch x shape) cell: whole-model (per layer x L) by
    default, one device's own ops under an expert-parallel ``dep``."""
    if dep.ep:
        return _device_ops(cfg, shape, dep)
    B, S = shape.global_batch, shape.seq_len
    if shape.kind == "decode":
        S_ctx, S = shape.seq_len, 1
    else:
        S_ctx = S
    T = B * S  # tokens touched this step
    d, L = cfg.d_model, cfg.n_layers
    hd = cfg.resolved_head_dim
    H, Hkv = max(cfg.n_heads, 1), max(cfg.n_kv_heads, 1)
    bt = 2  # bf16
    ops: List[Op] = []
    train_mult = 3.0 if shape.kind == "train" else 1.0  # fwd+bwd

    if cfg.attn_kind != "none":
        qkv_p = d * (H + 2 * Hkv) * hd
        ops.append(Op("qkv_proj", 2 * T * qkv_p * train_mult * L,
                       (qkv_p * bt + T * d * bt) * L, 0))
        eff_ctx = min(cfg.window, S_ctx) if cfg.attn_kind == "swa" else S_ctx
        attn_f = 4 * T * eff_ctx * H * hd * train_mult * L
        attn_b = (T * H * hd * bt + B * eff_ctx * Hkv * hd * 2 * bt) * L
        ops.append(Op("attention", attn_f, attn_b, 0))
        o_p = H * hd * d
        ops.append(Op("o_proj", 2 * T * o_p * train_mult * L,
                       (o_p * bt + T * d * bt) * L, 0))
    if cfg.family in ("ssm", "hybrid"):
        n = cfg.ssm.state_size if cfg.ssm else 16
        ssm_f = T * d * n * 8 * train_mult * L
        ops.append(Op("ssm_scan", ssm_f, (T * d * bt * 3 + d * d * bt) * L, 0))
        ops.append(Op("mix_proj", 2 * T * 4 * d * d * train_mult * L,
                       4 * d * d * bt * L, 0))
    if cfg.moe is not None:
        e = cfg.moe
        ef = 2 * T * e.top_k * 3 * d * e.expert_d_ff * train_mult * L
        ew = e.num_experts * 3 * d * e.expert_d_ff * bt * L
        # all-to-all dispatch+combine over the EP axis
        a2a = 2 * T * d * bt * L
        ops.append(Op("moe_ffn", ef, ew + T * d * bt * L, 0))
        ops.append(Op("moe_a2a", T * d * 0.1, T * d * bt * L, a2a))
        if e.num_shared:
            fs = e.num_shared * (e.shared_d_ff or e.expert_d_ff)
            ops.append(Op("shared_ffn", 2 * T * 3 * d * fs * train_mult * L,
                           3 * d * fs * bt * L, 0))
    else:
        ops.append(Op("ffn", 2 * T * 3 * d * cfg.d_ff * train_mult * L,
                       (3 * d * cfg.d_ff * bt + T * d * bt) * L, 0))
    ops.append(Op("norms_rope", T * d * 20 * L, T * d * bt * 4 * L, 0))
    ops.append(Op("logits", 2 * T * d * cfg.vocab * train_mult,
                   cfg.vocab * d * bt + T * cfg.vocab * 4, 0))
    if shape.kind == "train":
        # gradient reduce-scatter/all-gather over DP axes
        pbytes = cfg.n_params * 4
        ops.append(Op("grad_reduce", pbytes * 0.01, pbytes, pbytes))
        ops.append(Op("optimizer", cfg.n_params * 8, cfg.n_params * 16, 0))
    return ops


# ---------------------------------------------------------------------------
# one device's step under expert parallelism
# ---------------------------------------------------------------------------


def _mm(name: str, m: int, k: int, n: int, a: int, w: int, o: int,
        fp8: bool = True) -> Op:
    """An (m x k) @ (k x n) matmul that reads its input at ``a`` and its
    weight at ``w`` bytes an element and writes its output at ``o``."""
    return Op(name, 2 * m * k * n, m * k * a + k * n * w + m * n * o, 0, fp8)


def _ffn(name: str, m: int, d: int, ff: int, dep: Deployment,
         n_weights: int = 1) -> Op:
    """SwiGLU over ``m`` tokens: the fused gate/up matmul and the down
    matmul, reading ``n_weights`` FFNs' weights (routed experts: the ones
    held here)."""
    a, w, o = dep.dispatch_bytes, dep.weight_bytes, dep.combine_bytes
    up = _mm(name, m, d, 2 * ff, a, w, o)
    down = _mm(name, m, ff, d, a, w, o)
    weights = (d * 2 * ff + ff * d) * w
    return Op(name, up.flops + down.flops,
              up.bytes + down.bytes + (n_weights - 1) * weights, 0, True)


def _causal_pairs(B: int, C: int, S: int) -> int:
    """(query, key) pairs of ``B`` sequences whose ``C`` new tokens each
    attend the ``S - C`` cached ones and the new ones up to themselves."""
    return B * (C * (S - C) + C * (C + 1) // 2)


def _rope(T: int, H: int, rope: int, dep: Deployment) -> Op:
    """Rotary embedding of ``T`` tokens' per-head query parts and shared
    key part: 3 FLOPs an element, read and written once."""
    n = T * (H + 1) * rope
    return Op("rope", 3 * n, 2 * n * dep.combine_bytes)


def _mla_ops(cfg: ModelConfig, B: int, C: int, S: int, decode: bool,
             dep: Deployment) -> List[Op]:
    """Multi-head latent attention over ``B`` sequences of ``C`` new
    tokens whose last attends ``S`` keys. Prefill takes the naive form
    (every cached latent up-projected to per-head keys and values);
    decode the absorbed one (``W_UK`` into the query, ``W_UV`` after
    attending the latent cache of ``kv_lora_rank + qk_rope_head_dim`` a
    token)."""
    d, H, T = cfg.d_model, cfg.n_heads, B * C
    nope, rope, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, \
        cfg.v_head_dim
    kr, qr = cfg.kv_lora_rank, cfg.q_lora_rank
    a, w, o, c = dep.dispatch_bytes, dep.weight_bytes, dep.combine_bytes, \
        dep.cache_bytes
    qk = nope + rope
    if qr:
        ops = [_mm("q_down", T, d, qr, a, w, o),
               _mm("q_up", T, qr, H * qk, a, w, o)]
    else:
        ops = [_mm("q_proj", T, d, H * qk, a, w, o)]
    # the new tokens' latents and RoPE keys, written to the cache
    ops.append(_mm("kv_down", T, d, kr + rope, a, w, c))
    ops.append(_rope(T, H, rope, dep))
    pairs = _causal_pairs(B, C, S)
    if decode:
        ops.append(Op("absorb_uk", 2 * T * H * nope * kr,
                      (T * H * nope + T * H * kr) * o + H * nope * kr * w))
        ops.append(Op("attention", 2 * pairs * H * (kr + rope)
                      + 2 * pairs * H * kr,
                      (T * H * (kr + rope) + T * H * kr) * o
                      + B * S * (kr + rope) * c))
        ops.append(Op("absorb_uv", 2 * T * H * kr * dv,
                      (T * H * kr + T * H * dv) * o + H * kr * dv * w))
    else:
        ops.append(_mm("kv_up", B * S, kr, H * (nope + dv), c, w, o))
        ops.append(Op("attention", 2 * pairs * H * qk + 2 * pairs * H * dv,
                      (T * H * qk + B * S * (H * nope + rope)
                       + B * S * H * dv + T * H * dv) * o))
    ops.append(_mm("o_proj", T, H * dv, d, a, w, o))
    return ops


def _moe_ops(cfg: ModelConfig, T: int, dep: Deployment) -> List[Op]:
    """Router, dispatch, this device's routed experts, shared expert and
    combine for the ``T`` tokens of this data-parallel rank. The routed
    (token, expert) pairs here are the rank's share of the EP group's
    ``dp * T * top_k``, times ``dep.load``, rounded to whole pairs."""
    e, d = cfg.moe, cfg.d_model
    o = dep.combine_bytes
    held = dep.experts_held(e.num_experts)
    routed = dep.dp * T * e.top_k * held * dep.load / (e.num_experts
                                                       + dep.redundant)
    pairs = int(round(routed))
    send = T * e.top_k * d
    ops = [_mm("router", T, d, e.num_experts, o, o, o, fp8=False),
           Op("dispatch", 0, send * dep.dispatch_bytes,
              send * dep.dispatch_bytes),
           _ffn("experts", pairs, d, e.expert_d_ff, dep, n_weights=held)]
    if e.num_shared:
        ops.append(_ffn("shared", T, d,
                        e.num_shared * (e.shared_d_ff or e.expert_d_ff), dep))
    ops.append(Op("combine", 0, send * o, send * o))
    return ops


def _device_ops(cfg: ModelConfig, shape: ShapeConfig,
                dep: Deployment) -> List[Op]:
    """One device's step, in step order: the leading dense layers' ops,
    then the MoE layers' ops (each op class summed over its layers and
    named ``dense.<op>`` / ``moe.<op>``), then the output head over one
    token a sequence."""
    if shape.kind not in ("prefill", "decode"):
        raise ValueError(f"no per-device {shape.kind!r} step")
    if not cfg.mla:
        raise ValueError(f"{cfg.name}: the per-device step models MLA "
                         "attention only")
    B, decode = shape.global_batch, shape.kind == "decode"
    C = 1 if decode else shape.seq_len
    S = shape.seq_len if decode else shape.prefix + shape.seq_len
    attn = _mla_ops(cfg, B, C, S, decode, dep)
    n_moe = cfg.n_moe_layers
    stages = [("dense", cfg.n_layers - n_moe,
               attn + [_ffn("ffn", B * C, cfg.d_model, cfg.d_ff, dep)])]
    if n_moe:
        stages.append(("moe", n_moe, attn + _moe_ops(cfg, B * C, dep)))
    o = dep.combine_bytes
    return [Op(f"{stage}.{op.name}", op.flops * n, op.bytes * n,
               op.coll_bytes * n, op.fp8)
            for stage, n, ops in stages if n for op in ops] + \
        [_mm("head", B, cfg.d_model, cfg.vocab, o, o, o, fp8=False)]


# ---------------------------------------------------------------------------
# op list -> PCSTALL program
# ---------------------------------------------------------------------------


def arch_program(cfg: ModelConfig, shape: ShapeConfig,
                 dep: Deployment = DEFAULT_DEPLOYMENT,
                 n_blocks: int = 1024) -> Program:
    """Compile the step op list into a PCSTALL Program: block counts by op
    time share; sensitivity by arithmetic intensity."""
    return ops_program(f"{cfg.name}:{shape.name}", step_ops(cfg, shape, dep),
                       dep, n_blocks)


def step_program(cfg: ModelConfig, step: Step, dep: Deployments
                 ) -> Tuple[List[Op], Program]:
    """The op list and Program of one serving step on the unit of
    ``dep`` that runs ``step.kind``, at the step's routed load."""
    d = dataclasses.replace(unit(dep, step.kind), load=step.load)
    ops = step_ops(cfg, step.shape, d)
    return ops, ops_program(f"{cfg.name}:{step.name}", ops, d)


def ops_program(name: str, ops: List[Op], dep: Deployment,
                n_blocks: int = 1024) -> Program:
    """Blocks by each op's share of the step's time on ``dep`` (the
    longest of its compute, HBM and collective times); sensitivity by
    its arithmetic intensity against the ridge of the peak it runs at."""
    chips = dep.chips
    times, core_shares, mem_fracs = [], [], []
    for _, f, b, cb, fp8 in ops:
        peak = dep.fp8_flops if fp8 else dep.peak_flops
        t_comp = f / (chips * peak)
        t_mem = b / (chips * dep.hbm_bw)
        t_coll = cb / (chips * dep.coll_bw)
        t = max(t_comp, t_mem, t_coll, 1e-12)
        times.append(t)
        ai = f / max(b, 1.0)
        core = float(ai / (ai + peak / dep.hbm_bw))
        if t_coll == t:  # collective-bound: async, frequency-insensitive
            core *= 0.1
        core_shares.append(core)
        mem_fracs.append(min(max(t_mem, t_coll) / t, 1.0))
    times = np.asarray(times)
    shares = times / times.sum()
    i0 = np.zeros(n_blocks)
    sens = np.zeros(n_blocks)
    mem = np.zeros(n_blocks)
    pos = 0
    rate = 100.0
    for share, core, mf in zip(shares, core_shares, mem_fracs):
        ln = max(int(round(share * n_blocks)), 1)
        r = rate  # uniform instruction rate; sensitivity split by core share
        sens[pos:pos + ln] = core * r / 1.7
        i0[pos:pos + ln] = (1 - core) * r
        mem[pos:pos + ln] = mf
        pos += ln
        if pos >= n_blocks:
            break
    if pos < n_blocks:  # pad with the last op's character
        sens[pos:] = sens[pos - 1]
        i0[pos:] = i0[pos - 1]
        mem[pos:] = mem[pos - 1]
    return _finalize(name, i0, sens, mem)
