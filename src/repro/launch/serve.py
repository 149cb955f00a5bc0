"""Serving driver: batched prefill + decode loop with KV/SSM-state cache.

  PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-3b --smoke \
      --prompt-len 64 --gen 32 --batch 4

The decode loop's per-token greedy sampling lives INSIDE the jitted step
(one dispatch per token; the argmax rides the same executable as the
model math instead of paying an extra un-jitted dispatch between calls),
and with ``--dvfs`` the loop's per-step telemetry streams through the
long-lived :class:`repro.dvfs_runtime.service.DVFSService` — periodic
async report requests for the loop's own decode step (its batch, its
context) overlap decode compute instead of a single fresh one-shot report
after the loop.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config, get_smoke_config
from repro.models.model import decode_step, init_cache, init_params, prefill


def serve(cfg, *, batch: int, prompt_len: int, gen: int, seed: int = 0,
          dvfs: bool = False, dvfs_stride: int = 16):
    key = jax.random.key(seed)
    params = init_params(cfg, key)
    St = prompt_len - cfg.n_patches if cfg.frontend == "vision" else prompt_len
    toks = jax.random.randint(key, (batch, St), 0, cfg.vocab)
    pbatch = {"tokens": toks}
    if cfg.frontend == "vision":
        pbatch["patch_embeds"] = jax.random.normal(
            key, (batch, cfg.n_patches, cfg.d_model), jnp.bfloat16)

    prefill_fn = jax.jit(lambda p, b: prefill(p, cfg, b))

    def _decode_argmax(p, c, t):
        logits, c = decode_step(p, cfg, c, t)
        return jnp.argmax(logits, -1).astype(jnp.int32), c

    decode_fn = jax.jit(_decode_argmax, donate_argnums=(1,))

    t0 = time.perf_counter()
    logits = prefill_fn(params, pbatch)
    logits.block_until_ready()
    t_prefill = time.perf_counter() - t0

    svc = futs = window = None
    if dvfs:
        from repro.dvfs_runtime.service import DVFSService
        from repro.dvfs_runtime.telemetry import Step
        svc = DVFSService.for_model(cfg, coalesce_s=0.001)
        futs, window = [], []

    cache = init_cache(cfg, batch, prompt_len + gen, fill=prompt_len)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    out = [tok]
    t0 = time.perf_counter()
    t_prev = t0
    for step in range(gen):
        tok, cache = decode_fn(params, cache, tok)
        out.append(tok)
        if svc is not None:
            # dispatch-cadence telemetry: wall time between async decode
            # dispatches, no extra device syncs on the decode hot loop
            t_now = time.perf_counter()
            window.append((step, t_now - t_prev))
            t_prev = t_now
            if (step + 1) % dvfs_stride == 0 or step == gen - 1:
                # async: the service derives the window's last step (its
                # batch, its context) and dispatches off-thread,
                # overlapping the remaining decode steps
                futs.append(svc.submit_step(
                    Step("decode", batch, prompt_len + step + 1),
                    telemetry=window))
                window = []
    jax.block_until_ready(out[-1])
    t_decode = (time.perf_counter() - t0) / gen
    report = {"prefill_s": t_prefill, "decode_s_per_tok": t_decode,
              "tokens": jnp.stack(out, 1)}
    if svc is not None:
        with svc:
            results = [f.result() for f in futs]
        report["dvfs"] = results[-1]["report"]
        report["dvfs_requests"] = len(results)
        report["dvfs_stream"] = svc.stats()
    return report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="rwkv6-3b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--dvfs", action="store_true")
    args = ap.parse_args()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    rep = serve(cfg, batch=args.batch, prompt_len=args.prompt_len, gen=args.gen,
                dvfs=args.dvfs)
    print(f"prefill {rep['prefill_s'] * 1e3:.1f}ms  "
          f"decode {rep['decode_s_per_tok'] * 1e3:.2f}ms/tok  "
          f"out shape {rep['tokens'].shape}")
    if "dvfs" in rep:
        d, s = rep["dvfs"], rep["dvfs_stream"]
        print(f"[dvfs] energy {d['energy_norm']:.3f}x acc {d['accuracy']:.3f}  "
              f"steps {d['step_time']['n_steps']}  "
              f"stream {rep['dvfs_requests']} reqs "
              f"p99 {s['p99_latency_s'] * 1e3:.0f}ms")


if __name__ == "__main__":
    main()
