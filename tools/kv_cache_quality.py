"""int8 KV-cache quality vs bf16 — VERDICT r4 item 7.

Teacher-forces one token stream through the cached decode path twice
(cache_dtype bfloat16 vs int8) and reports, over the decoded region:

  * max / mean |logit difference| (int8 cache vs bf16 cache)
  * greedy-argmax agreement rate
  * next-token NLL -> perplexity per cache dtype, and the delta
  * the same NLL from the no-cache full forward (the cache-path sanity
    anchor: bf16-cache ppl should sit on top of it)

Weights are random-init at the requested geometry (no pretrained
checkpoints exist in this environment), so the numbers measure
QUANTIZATION error against the model's own activation statistics — the
right yardstick for "is the int8 cache numerically safe", not a claim
about downstream task quality. Reference role: the int8 CacheKV path in
fused_multi_transformer_op.cu, which the reference ships with the same
kind of numerics gate.

Run at 125M geometry:  python tools/kv_cache_quality.py
CPU smoke:             JAX_PLATFORMS=cpu python tools/kv_cache_quality.py --smoke
Decode throughput per cache dtype is for a serving cell of the
benchmark to measure (none yet); this tool is the quality half of the
table.
"""
import argparse
import json
import os
import sys

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="tiny geometry")
    ap.add_argument("--prompt", type=int, default=16)
    ap.add_argument("--steps", type=int, default=112,
                    help="teacher-forced decode steps measured")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.jit.functional import functional_call, raw_state
    from paddle_tpu.models import GPTForCausalLM, gpt_125m, gpt_tiny

    on_cpu = jax.default_backend() == "cpu"
    paddle.seed(0)
    cfg = gpt_tiny() if args.smoke else gpt_125m()
    model = GPTForCausalLM(cfg)
    model.eval()
    if not on_cpu:
        model.bfloat16()
    params, buffers = raw_state(model)

    P = args.prompt
    S = min(P + args.steps, cfg.max_seq_len)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (1, S)).astype("int64")
    ids_j = jnp.asarray(ids)

    @jax.jit
    def full_forward(params, buffers, ids):
        logits, _ = functional_call(model, params, buffers, ids,
                                    training=False)
        return logits

    @jax.jit
    def prefill(params, buffers, ids, caches):
        (logits, caches), _ = functional_call(
            model, params, buffers, ids, caches, jnp.int32(0),
            training=False)
        return logits, caches

    @jax.jit
    def step(params, buffers, tok, caches, pos):
        (logits, caches), _ = functional_call(
            model, params, buffers, tok, caches, pos, training=False)
        return logits[:, -1, :], caches

    def teacher_forced(cache_dtype):
        """Logits [T, V] at positions P-1 .. S-2 (each predicts the next
        token), produced through the cached decode path."""
        caches = model.new_cache(1, S, cache_dtype)
        pre_logits, caches = prefill(params, buffers, ids_j[:, :P], caches)
        outs = [pre_logits[0, -1, :].astype(jnp.float32)]
        for t in range(P, S - 1):
            lg, caches = step(params, buffers, ids_j[:, t:t + 1], caches,
                              jnp.int32(t))
            outs.append(lg[0].astype(jnp.float32))
        return jnp.stack(outs)  # [S-P, V]

    def nll(logits, targets):
        lse = jax.nn.log_softmax(logits, axis=-1)
        return float(-jnp.take_along_axis(
            lse, targets[:, None], axis=-1).mean())

    targets = jnp.asarray(ids[0, P:S])           # token t predicted at t-1
    lg_bf16 = teacher_forced("bfloat16")
    lg_int8 = teacher_forced("int8")
    lg_full = full_forward(params, buffers, ids_j)[0, P - 1:S - 1, :] \
        .astype(jnp.float32)

    diff = jnp.abs(lg_int8 - lg_bf16)
    agree = float((jnp.argmax(lg_int8, -1)
                   == jnp.argmax(lg_bf16, -1)).mean())
    nll_bf16, nll_int8, nll_full = (nll(lg_bf16, targets),
                                    nll(lg_int8, targets),
                                    nll(lg_full, targets))
    rec = {
        "metric": "int8_kv_cache_quality",
        "geometry": "gpt_tiny" if args.smoke else "gpt_125m",
        "positions_measured": int(S - P),
        "max_abs_logit_err_int8_vs_bf16": round(float(diff.max()), 4),
        "mean_abs_logit_err_int8_vs_bf16": round(float(diff.mean()), 5),
        "greedy_agreement_pct": round(100 * agree, 2),
        "ppl_bf16_cache": round(float(np.exp(nll_bf16)), 4),
        "ppl_int8_cache": round(float(np.exp(nll_int8)), 4),
        "ppl_nocache_fwd": round(float(np.exp(nll_full)), 4),
        "ppl_delta_int8_vs_bf16": round(
            float(np.exp(nll_int8) - np.exp(nll_bf16)), 4),
        "device_kind": getattr(jax.devices()[0], "device_kind", "cpu"),
        "weights": "f32" if on_cpu else "bf16",
    }
    print(json.dumps(rec))
    return 0


if __name__ == "__main__":
    sys.exit(main())
