"""Program library ``deepseek_v3``: one GPU's step of DeepSeek-V3 on the
prefill (EP32) or decode (EP144) unit of the deployment file, as a
PCSTALL program of ``cfg["program_blocks"]`` blocks. The benchmark's own
copy of the step count (``ops``) and of the block mapping (``arrays``),
read from the file's widths, unit degrees, byte widths and GPU peaks.

A name is ``<kind>.b<batch>.c<context>.k<chunk>.l<load>``: ``batch``
sequences on the GPU, ``chunk`` new tokens each (1 for decode) whose last
attends ``context`` keys, itself included, and the routed load on the
GPU's experts over the balanced share.

Per layer: multi-head latent attention (prefill naive: every cached latent
up-projected; decode absorbed: ``W_UK``/``W_UV`` folded round attention
over the latent cache), then a dense SwiGLU (the leading
``first_k_dense_replace`` layers) or router, dispatch, the GPU's routed
experts over its share of the (token, expert) pairs, the shared expert
and combine; op classes summed over their layers; then the head over one
token a sequence. A matmul (m x k) @ (k x n) costs 2mkn FLOPs and reads
its input and weight and writes its output once; RoPE 3 FLOPs an
element. An op takes the longest of its compute, HBM and all-to-all
times; its blocks are its share of the step's time, its sensitivity its
arithmetic intensity against the ridge of the peak it runs at, a tenth
of that where the all-to-all bounds it."""
import numpy as np

DECODE_CONTEXTS = (1024, 2048, 4096, 8192, 16384, 32768)
PREFILL_PREFIXES = (0, 8192, 24576)
LOADS = (0.5, 1.0, 2.0)
DECODE_BATCH, PREFILL_BATCH, PREFILL_CHUNK = 128, 1, 4096


def name_of(kind, batch, context, chunk, load):
    return f"{kind}.b{batch}.c{context}.k{chunk}.l{load:g}"


NAMES = tuple(
    [name_of("decode", DECODE_BATCH, c, 1, x)
     for c in DECODE_CONTEXTS for x in LOADS]
    + [name_of("prefill", PREFILL_BATCH, p + PREFILL_CHUNK, PREFILL_CHUNK, x)
       for p in PREFILL_PREFIXES for x in LOADS])


def step(name):
    """``(kind, batch, context, chunk, load)`` of a step's name."""
    kind, b, c, k, x = name.split(".", 4)
    return kind, int(b[1:]), int(c[1:]), int(k[1:]), float(x[1:])


def _mm(name, m, k, n, a, w, o, fp8=True):
    return (name, 2 * m * k * n, m * k * a + k * n * w + m * n * o, 0, fp8)


def _ffn(name, m, d, ff, by, held=1):
    a, w, o = by["dispatch"], by["weight"], by["combine"]
    flops = 2 * m * d * 2 * ff + 2 * m * ff * d
    nbytes = m * d * a + d * 2 * ff * w + m * 2 * ff * o \
        + m * ff * a + ff * d * w + m * d * o \
        + (held - 1) * (d * 2 * ff + ff * d) * w
    return (name, flops, nbytes, 0, True)


def _attention(cfg, B, C, S, decode, by):
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, kr, qr = cfg["v_head_dim"], cfg["kv_lora_rank"], cfg["q_lora_rank"]
    a, w, o, c = by["dispatch"], by["weight"], by["combine"], by["cache"]
    T, qk = B * C, nope + rope
    pairs = B * (C * (S - C) + C * (C + 1) // 2)
    n_rope = T * (H + 1) * rope
    ops = [_mm("q_down", T, d, qr, a, w, o),
           _mm("q_up", T, qr, H * qk, a, w, o),
           _mm("kv_down", T, d, kr + rope, a, w, c),
           ("rope", 3 * n_rope, 2 * n_rope * o, 0, False)]
    if decode:
        ops += [("absorb_uk", 2 * T * H * nope * kr,
                 (T * H * nope + T * H * kr) * o + H * nope * kr * w, 0, False),
                ("attention", 2 * pairs * H * (kr + rope) + 2 * pairs * H * kr,
                 (T * H * (kr + rope) + T * H * kr) * o
                 + B * S * (kr + rope) * c, 0, False),
                ("absorb_uv", 2 * T * H * kr * dv,
                 (T * H * kr + T * H * dv) * o + H * kr * dv * w, 0, False)]
    else:
        ops += [_mm("kv_up", B * S, kr, H * (nope + dv), c, w, o),
                ("attention", 2 * pairs * H * qk + 2 * pairs * H * dv,
                 (T * H * qk + B * S * (H * nope + rope) + B * S * H * dv
                  + T * H * dv) * o, 0, False)]
    return ops + [_mm("o_proj", T, H * dv, d, a, w, o)]


def _moe(cfg, T, unit, by):
    d, E, k = cfg["hidden_size"], cfg["n_routed_experts"], \
        cfg["num_experts_per_tok"]
    total = E + cfg["redundant_experts"]
    held = total // unit["ep"]
    pairs = int(round(unit["dp"] * T * k * held * unit["load"] / total))
    send = T * k * d
    ff_shared = cfg["n_shared_experts"] * cfg["moe_intermediate_size"]
    return [_mm("router", T, d, E, by["combine"], by["combine"],
                by["combine"], fp8=False),
            ("dispatch", 0, send * by["dispatch"], send * by["dispatch"],
             False),
            _ffn("experts", pairs, d, cfg["moe_intermediate_size"], by, held),
            _ffn("shared", T, d, ff_shared, by),
            ("combine", 0, send * by["combine"], send * by["combine"], False)]


def ops(name, cfg):
    """One GPU's op list of step ``name``: ``(op, FLOPs, HBM bytes,
    all-to-all bytes, runs at the FP8 peak)``."""
    kind, B, S, C, load = step(name)
    unit = dict(cfg["units"][kind], load=load)
    by = cfg["bytes"]
    attn = _attention(cfg, B, C, S, kind == "decode", by)
    L, k = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    dense = attn + [_ffn("ffn", B * C, cfg["hidden_size"],
                         cfg["intermediate_size"], by)]
    moe = attn + _moe(cfg, B * C, unit, by)
    o = by["combine"]
    return [(f"dense.{n}", f * k, b * k, cb * k, fp8)
            for n, f, b, cb, fp8 in dense] + \
        [(f"moe.{n}", f * (L - k), b * (L - k), cb * (L - k), fp8)
         for n, f, b, cb, fp8 in moe] + \
        [_mm("head", B, cfg["hidden_size"], cfg["vocab_size"], o, o, o,
             fp8=False)]


def arrays(name, cfg):
    gpu = cfg["gpu"]
    n_blocks = cfg["program_blocks"]
    times, cores, mems = [], [], []
    for _, f, b, cb, fp8 in ops(name, cfg):
        peak = gpu["fp8_flops"] if fp8 else gpu["bf16_flops"]
        t_comp = f / (1 * peak)
        t_mem = b / (1 * gpu["hbm_bw"])
        t_coll = cb / (1 * gpu["all_to_all_bw"])
        t = max(t_comp, t_mem, t_coll, 1e-12)
        times.append(t)
        ai = f / max(b, 1.0)
        core = float(ai / (ai + peak / gpu["hbm_bw"]))
        if t_coll == t:
            core *= 0.1
        cores.append(core)
        mems.append(min(max(t_mem, t_coll) / t, 1.0))
    times = np.asarray(times)
    shares = times / times.sum()
    i0, sens, mem = np.zeros(n_blocks), np.zeros(n_blocks), np.zeros(n_blocks)
    pos = 0
    for share, core, mf in zip(shares, cores, mems):
        ln = max(int(round(share * n_blocks)), 1)
        sens[pos:pos + ln] = core * 100.0 / 1.7
        i0[pos:pos + ln] = (1 - core) * 100.0
        mem[pos:pos + ln] = mf
        pos += ln
        if pos >= n_blocks:
            break
    if pos < n_blocks:
        sens[pos:], i0[pos:], mem[pos:] = sens[pos - 1], i0[pos - 1], \
            mem[pos - 1]
    return {"i0": i0.astype(np.float32), "sens": sens.astype(np.float32),
            "mem": mem.astype(np.float32)}
