"""DeepSeek-V3 on the DVFS step path: the config against the published
``config.json``, one device's ``step_ops`` against what the plain
reference (``repro.models.deepseek_v3_ref``) computes at a small size,
absorbed against naive MLA, and the expert-parallel shares against the
uncut layer; the ten other configs' programs stay bitwise as they were."""
import dataclasses
import hashlib
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import ALL_SHAPES, ARCH_IDS, get_config, get_smoke_config
from repro.configs.base import ShapeConfig
from repro.dvfs_runtime.telemetry import (Deployment, Step, arch_program,
                                          step_ops, step_program)
from repro.models import deepseek_v3_ref as REF

# https://huggingface.co/deepseek-ai/DeepSeek-V3/blob/main/config.json: the
# keys the repo models (its ``scoring_func`` "sigmoid" and
# ``norm_topk_prob`` true are what the reference's router always does, not
# fields)
PUBLISHED = {
    "num_hidden_layers": 61, "hidden_size": 7168, "num_attention_heads": 128,
    "num_key_value_heads": 128, "intermediate_size": 18432,
    "vocab_size": 129280, "rope_theta": 10000, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "q_lora_rank": 1536, "kv_lora_rank": 512,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "first_k_dense_replace": 3, "n_routed_experts": 256,
    "num_experts_per_tok": 8, "n_shared_experts": 1,
    "moe_intermediate_size": 2048, "routed_scaling_factor": 2.5,
    "n_group": 8, "topk_group": 4,
}

# the H800 deployment's byte widths (FP8 weights and dispatch, BF16 core,
# combine and cache); one device holds 4 of the smoke config's 16 experts
WIDTHS = dict(weight_bytes=1, dispatch_bytes=1, combine_bytes=2,
              cache_bytes=2)
SMOKE = get_smoke_config("deepseek-v3")
HELD = list(range(4, 8))
EP = SMOKE.moe.num_experts // len(HELD)


def _modelled(cfg):
    e = cfg.moe
    return {
        "num_hidden_layers": cfg.n_layers, "hidden_size": cfg.d_model,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "intermediate_size": cfg.d_ff,
        "vocab_size": cfg.vocab, "rope_theta": cfg.rope_theta,
        "rms_norm_eps": cfg.norm_eps, "tie_word_embeddings": cfg.tie_embeddings,
        "q_lora_rank": cfg.q_lora_rank, "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim,
        "first_k_dense_replace": cfg.first_k_dense_replace,
        "n_routed_experts": e.num_experts, "num_experts_per_tok": e.top_k,
        "n_shared_experts": e.num_shared,
        "moe_intermediate_size": e.expert_d_ff,
        "routed_scaling_factor": e.routed_scaling_factor,
        "n_group": e.n_group, "topk_group": e.topk_group,
    }


def test_config_matches_published():
    cfg = get_config("deepseek-v3")
    assert _modelled(cfg) == PUBLISHED
    assert cfg.moe.shared_d_ff == PUBLISHED["moe_intermediate_size"]
    # 671B parameters, 37B active a token (MTP module left out)
    assert round(cfg.n_params / 1e9) == 671
    assert round(cfg.n_active_params / 1e9) == 38
    assert "deepseek-v3" not in ARCH_IDS


def _dep(**kw):
    return Deployment(**{"chips": 1, "ep": EP, **WIDTHS, **kw})


@pytest.fixture(scope="module")
def weights():
    k = jax.random.split(jax.random.key(7), 4)
    return {"dense": REF.init_layer(SMOKE, k[0], moe=False),
            "moe": REF.init_layer(SMOKE, k[1], moe=True),
            "head": REF._w(k[2], (SMOKE.d_model, SMOKE.vocab))}


def _reference_step(weights, B, C, P, absorbed):
    """The smoke config's dense layer, MoE layer and head over ``B``
    sequences of ``C`` new tokens after ``P`` cached ones."""
    t = REF.Tally(_dep())
    ks = jax.random.split(jax.random.key(11), 3)
    x = jax.random.normal(ks[0], (B, C, SMOKE.d_model))
    for stage, key in (("dense", ks[1]), ("moe", ks[2])):
        t.prefix = stage + "."
        x, _ = REF.layer(weights[stage], SMOKE, x,
                         REF.init_cache(SMOKE, key, B, P), t, absorbed, HELD)
    t.prefix = ""
    REF.head(weights["head"], SMOKE, x, t)
    return t


@pytest.mark.parametrize("kind,B,C,P", [("prefill", 2, 5, 7),
                                        ("decode", 3, 1, 8)])
def test_step_ops_equal_reference_tally(weights, kind, B, C, P):
    t = _reference_step(weights, B, C, P, absorbed=kind == "decode")
    expected = B * C * SMOKE.moe.top_k * len(HELD) / SMOKE.moe.num_experts
    assert t.routed > 0
    shape = ShapeConfig(kind, P + C, B, kind) if kind == "decode" \
        else ShapeConfig(kind, C, B, kind, prefix=P)
    ops = step_ops(SMOKE, shape, _dep(load=t.routed / expected))
    got = {o.name: [o.flops, o.bytes, o.coll_bytes] for o in ops}
    assert got == dict(t.ops)


def test_absorbed_mla_matches_naive(weights):
    B, P = 3, 9
    x = jax.random.normal(jax.random.key(3), (B, 1, SMOKE.d_model))
    cache = REF.init_cache(SMOKE, jax.random.key(4), B, P)
    naive, c1 = REF.mla_naive(weights["moe"], SMOKE, x, cache,
                              REF.Tally(_dep()))
    absorbed, c2 = REF.mla_absorbed(weights["moe"], SMOKE, x, cache,
                                    REF.Tally(_dep()))
    # float32 accumulation over different summation orders
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(naive),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(c1[0]), np.asarray(c2[0]))


def test_ep_shares_sum_to_uncut_layer(weights):
    """Two DP ranks of ``B`` tokens and ``EP`` expert ranks: the routed
    parts of all EP ranks sum to the uncut layer's; the shared expert and
    attention, computed by each DP rank on its own tokens, add up to the
    uncut layer's over all tokens."""
    dp, B = 2, 6
    x = jax.random.normal(jax.random.key(5), (dp * B, SMOKE.d_model))
    uncut = REF.Tally(Deployment(chips=1, ep=1, **WIDTHS))
    routed_u, shared_u = REF.moe(weights["moe"], SMOKE, x,
                                 range(SMOKE.moe.num_experts), uncut)
    shape = ShapeConfig("d", 4, B, "decode")
    uncut_ops = {o.name: o for o in step_ops(
        SMOKE, dataclasses.replace(shape, global_batch=dp * B),
        Deployment(chips=1, ep=1, **WIDTHS))}
    total, flops, byts, op_flops = 0.0, 0, 0, 0
    expected = dp * B * SMOKE.moe.top_k * len(HELD) / SMOKE.moe.num_experts
    for r in range(EP):
        t = REF.Tally(_dep(dp=dp))
        part, shared = REF.moe(weights["moe"], SMOKE, x,
                               range(r * len(HELD), (r + 1) * len(HELD)), t)
        total = total + part
        np.testing.assert_array_equal(np.asarray(shared), np.asarray(shared_u))
        flops += t.ops["experts"][0]
        byts += t.ops["experts"][1]
        ops = {o.name: o for o in step_ops(
            SMOKE, shape, _dep(dp=dp, load=t.routed / expected))}
        op_flops += ops["moe.experts"].flops
        for name in ("moe.attention", "moe.shared", "moe.q_down", "head"):
            assert dp * ops[name].flops == uncut_ops[name].flops, name
    np.testing.assert_allclose(np.asarray(total), np.asarray(routed_u),
                               rtol=1e-5, atol=1e-6)
    assert (flops, byts) == tuple(uncut.ops["experts"][:2])
    assert op_flops == uncut_ops["moe.experts"].flops == flops


def test_step_program_per_unit():
    """A step runs on the unit of its kind, at its own routed load, and
    reads only the experts that unit holds."""
    cfg = get_config("deepseek-v3")
    gpu = dict(chips=1, redundant=32, **WIDTHS)
    dep = {"prefill": Deployment(ep=32, dp=32, **gpu),
           "decode": Deployment(ep=144, dp=144, **gpu)}
    w1 = 3 * cfg.d_model * cfg.moe.expert_d_ff
    for step, held in ((Step("decode", 128, 4096), 2),
                       (Step("prefill", 1, 12288, chunk=4096), 9)):
        ops, prog = step_program(cfg, step, dep)
        ex = {o.name: o for o in ops}["moe.experts"]
        pairs = step.batch * step.chunk * cfg.moe.top_k
        assert ex.flops == 2 * 3 * pairs * cfg.d_model \
            * cfg.moe.expert_d_ff * cfg.n_moe_layers
        assert ex.bytes > held * w1 * cfg.n_moe_layers > ex.bytes - \
            pairs * cfg.n_moe_layers * 8 * cfg.d_model
        assert prog.n_blocks == 1024 and prog.name.endswith(step.name)
    hi, _ = step_program(cfg, Step("decode", 128, 4096, load=2.0), dep)
    lo, _ = step_program(cfg, Step("decode", 128, 4096, load=0.5), dep)
    assert {o.name: o for o in hi}["moe.experts"].flops == \
        4 * {o.name: o for o in lo}["moe.experts"].flops


def test_registered_configs_programs_unchanged():
    want = json.loads((Path(__file__).parent / "data"
                       / "arch_program_digests.json").read_text())
    got = {}
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        for shape in ALL_SHAPES:
            p = arch_program(cfg, shape)
            h = hashlib.sha256()
            for a in (p.i0_rate, p.sens_rate, p.mem_frac):
                h.update(np.asarray(a).tobytes())
            h.update(repr([tuple(float(x) for x in o[1:4])
                           for o in step_ops(cfg, shape)]).encode())
            got[f"{arch}:{shape.name}"] = h.hexdigest()
    assert got == want
