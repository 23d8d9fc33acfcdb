#!/usr/bin/env python
"""Fused-kernel A/B bench + identity gate (ISSUE 19).

A/Bs the three PADDLE_TPU_FUSED_* knobs through the REAL dispatch —
the same env flip a production config would use — over the registry's
own programs and a live engine:

  1. MODELED bytes/kernels (analysis.hlo_cost over the compiled HLO):
     gpt_decode unfused vs PADDLE_TPU_FUSED_CACHE_WRITE vs
     PADDLE_TPU_MEGA_DECODE, train_step vs PADDLE_TPU_FUSED_CE.
     GATES: fused decode-tick HBM drop >= 20% (the ISSUE 19
     acceptance bar; tpucost pins the exact bytes), fused-CE strictly
     removes kernels from the backward chain at no byte cost.
  2. WALL time, interleaved best-of-N pairs (the bench_obs_overhead
     jitter recipe: host noise is correlated over seconds, so fused
     and unfused run back-to-back inside each pair and alternate who
     leads). Informational on CPU — interpret-mode Pallas is the
     portability fallback, not the fast path; the modeled gates carry.
  3. IDENTITY: a live ContinuousBatchingEngine decodes the same
     greedy workload with the knob off / fused / mega — tokens must be
     BIT-IDENTICAL across all three, round 2 must match round 1, and
     the knob must cost ZERO new traces or compiles after warmup
     (the _static_key carries the knob state, so flips can never
     poison a warm cache). Fused-CE value+grad vs the unfused chain
     bounded at GATE_CE_MAXDIFF.

Prints ONE terminal JSON record (one terminal JSON record).

CPU run: JAX_PLATFORMS=cpu python tools/bench_fusion.py --smoke
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])

GATE_DECODE_DROP = 0.20     # fused cache-write: modeled HBM drop
GATE_CE_KERNELS = -1        # fused CE: kernel-count delta bound
GATE_CE_MAXDIFF = 1e-4      # fused CE: fwd value + grad drift
GATE_CTX_DRIFT = 1e-4       # decode ctx drift (softmax reassociation)

_KNOBS = ("PADDLE_TPU_FUSED_CACHE_WRITE", "PADDLE_TPU_MEGA_DECODE",
          "PADDLE_TPU_FUSED_CE")


def _clear_knobs():
    for k in _KNOBS:
        os.environ.pop(k, None)


def _maxdiff(a, b):
    import jax
    d = 0.0
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        x = np.asarray(x).astype(np.float64)
        y = np.asarray(y).astype(np.float64)
        # NaN slots are pre-existing masked page garbage: require the
        # POSITIONS to match, compare values elsewhere
        if not np.array_equal(np.isnan(x), np.isnan(y)):
            return float("inf")
        m = ~np.isnan(x)
        if m.any():
            d = max(d, float(np.max(np.abs(x[m] - y[m]))))
    return d


def _int_leaves_equal(a, b):
    import jax
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        x, y = np.asarray(x), np.asarray(y)
        if (np.issubdtype(x.dtype, np.integer) and x.dtype != np.int8) \
                or x.dtype == np.bool_:
            if not np.array_equal(x, y):
                return False
    return True


def _site(build, name, knob=None):
    """Build one registry program (optionally under a knob), compile,
    model its cost, run once. The registry programs DONATE their
    carries, so every execution gets fresh arg copies. Returns
    (cost_rec, outputs, timer, cleanup)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.analysis.hlo_cost import program_cost
    if knob:
        os.environ[knob] = "1"
    try:
        br = build()
        proto = br.args

        def fresh():
            return jax.tree.map(
                lambda x: jnp.array(x) if hasattr(x, "dtype") else x,
                proto)

        rec = program_cost(br.fn.lower(*proto).compile().as_text(),
                           name=name)
        out = jax.block_until_ready(br.fn(*fresh()))
    finally:
        if knob:
            os.environ.pop(knob, None)

    def timer():
        a = fresh()                      # copies outside the clock
        t0 = time.perf_counter()
        jax.block_until_ready(br.fn(*a))
        return (time.perf_counter() - t0) * 1e3

    return rec, out, timer, br.cleanup


def _pair_times(t_base, t_test, reps):
    """Interleaved pairs, alternating leader; best-of over pairs."""
    base, test = [], []
    for i in range(reps):
        if i % 2 == 0:
            base.append(t_base())
            test.append(t_test())
        else:
            test.append(t_test())
            base.append(t_base())
    return round(min(base), 2), round(min(test), 2)


def _modeled(reps, include_paged):
    from paddle_tpu.compilation import sites
    out = {}
    cleanups = []

    def _site2(build, name, knob=None):
        rec, o, t, cl = _site(build, name, knob)
        if cl:
            cleanups.append(cl)
        return rec, o, t

    base, o0, tb = _site2(sites.build_gpt_decode, "gpt_decode")
    fused, o1, tf = _site2(sites.build_gpt_decode, "gpt_decode_fused",
                           knob="PADDLE_TPU_FUSED_CACHE_WRITE")
    mega, o2, tm = _site2(sites.build_gpt_decode, "gpt_decode_mega",
                          knob="PADDLE_TPU_MEGA_DECODE")
    drop = 1.0 - fused["hbm_bytes"] / base["hbm_bytes"]
    mega_ratio = mega["hbm_bytes"] / base["hbm_bytes"]
    assert _int_leaves_equal(o0, o1), \
        "fused cache-write changed an integer (token/state) leaf"
    assert _int_leaves_equal(o0, o2), \
        "mega decode changed an integer (token/state) leaf"
    d_f, d_m = _maxdiff(o0, o1), _maxdiff(o0, o2)
    assert d_f <= GATE_CTX_DRIFT, f"fused decode drift {d_f}"
    assert d_m <= GATE_CTX_DRIFT, f"mega decode drift {d_m}"
    b_ms, f_ms = _pair_times(tb, tf, reps)
    _, m_ms = _pair_times(tb, tm, reps)
    out["decode"] = {
        "hbm_bytes": [base["hbm_bytes"], fused["hbm_bytes"],
                      mega["hbm_bytes"]],
        "kernels": [base["kernel_count"], fused["kernel_count"],
                    mega["kernel_count"]],
        "fused_hbm_drop": round(drop, 4),
        "mega_hbm_ratio": round(mega_ratio, 4),
        "maxdiff": [d_f, d_m],
        "wall_ms": {"unfused": b_ms, "fused": f_ms, "mega": m_ms},
    }

    base, o0, tb = _site2(sites.build_train_step, "train_step")
    fce, o1, tf = _site2(sites.build_train_step, "train_step_fused_ce",
                         knob="PADDLE_TPU_FUSED_CE")
    d = _maxdiff(o0, o1)
    assert d <= GATE_CE_MAXDIFF, f"fused-CE train drift {d}"
    b_ms, f_ms = _pair_times(tb, tf, reps)
    out["train_ce"] = {
        "hbm_bytes": [base["hbm_bytes"], fce["hbm_bytes"]],
        "kernels": [base["kernel_count"], fce["kernel_count"]],
        "kernel_delta": fce["kernel_count"] - base["kernel_count"],
        "maxdiff": d,
        "wall_ms": {"unfused": b_ms, "fused": f_ms},
    }

    if include_paged:
        base, o0, _ = _site2(sites.build_gpt_decode_paged,
                             "gpt_decode_paged")
        fused, o1, _ = _site2(sites.build_gpt_decode_paged,
                              "gpt_decode_paged_fused",
                              knob="PADDLE_TPU_FUSED_CACHE_WRITE")
        d = _maxdiff(o0, o1)
        assert d == 0.0, f"paged fused write not bitwise (maxdiff {d})"
        out["paged"] = {
            "hbm_bytes": [base["hbm_bytes"], fused["hbm_bytes"]],
            "bitwise": True,
        }
    for cl in cleanups:
        cl()
    return out


def _engine_round(model, prompts, max_new, knob=None):
    """One engine lifetime under a knob: two identical greedy rounds.
    Returns (round-1 tokens, round-2 tokens, recompiles, retraces)."""
    from paddle_tpu.inference.engine import ContinuousBatchingEngine
    if knob:
        os.environ[knob] = "1"
    try:
        eng = ContinuousBatchingEngine(
            model, slots=len(prompts), max_len=max_new + 16,
            cache_dtype="float32", prefill_buckets=(8,),
            max_queue=2 * len(prompts))
        try:
            futs = [eng.submit(p, max_new_tokens=max_new, seed=0)
                    for p in prompts]
            t1 = [np.asarray(f.result(timeout=600)) for f in futs]
            progs, traces = eng.compiled_program_count, eng._trace_count
            futs = [eng.submit(p, max_new_tokens=max_new, seed=0)
                    for p in prompts]
            t2 = [np.asarray(f.result(timeout=600)) for f in futs]
            return (t1, t2, eng.compiled_program_count - progs,
                    eng._trace_count - traces)
        finally:
            eng.stop()
    finally:
        if knob:
            os.environ.pop(knob, None)


def _engine_identity(max_new, slots):
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
        max_seq_len=max_new + 32))
    model.eval()
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, 200, (6,)).astype("int64")
               for _ in range(slots)]

    results = {}
    base = _engine_round(model, prompts, max_new)
    for label, knob in (("fused", "PADDLE_TPU_FUSED_CACHE_WRITE"),
                        ("mega", "PADDLE_TPU_MEGA_DECODE")):
        t1, t2, rec, ret = _engine_round(model, prompts, max_new, knob)
        ident = all(np.array_equal(a, b) for a, b in zip(base[0], t1))
        stable = all(np.array_equal(a, b) for a, b in zip(t1, t2))
        results[label] = {
            "tokens_identical": bool(ident),
            "round2_identical": bool(stable),
            "recompiles_after_warmup": rec,
            "retraces_after_warmup": ret,
        }
        assert ident, f"{label}: greedy tokens diverged from unfused"
        assert stable, f"{label}: round 2 diverged from round 1"
        assert rec == 0 and ret == 0, \
            f"{label}: {rec} recompiles / {ret} retraces after warmup"
    results["tokens_per_request"] = int(base[0][0].shape[-1])
    return results


def _ce_identity():
    import jax
    import jax.numpy as jnp
    from importlib import import_module
    loss_mod = import_module("paddle_tpu.nn.functional.loss")
    rs = np.random.RandomState(5)
    lg = jnp.asarray(rs.randn(32, 512).astype("float32") * 3)
    idx = jnp.asarray(rs.randint(0, 512, 32), jnp.int32)
    w = jnp.asarray(rs.randn(32).astype("float32"))

    def loss_of(ce):
        return lambda x: jnp.sum(ce(x, idx) * w)

    v0, g0 = jax.value_and_grad(loss_of(loss_mod._fused_softmax_ce))(lg)
    v1, g1 = jax.value_and_grad(loss_of(loss_mod._pallas_softmax_ce))(lg)
    dv = float(abs(v0 - v1))
    dg = float(jnp.max(jnp.abs(g0 - g1)))
    assert dv <= GATE_CE_MAXDIFF and dg <= GATE_CE_MAXDIFF, \
        f"fused-CE drift value {dv} grad {dg}"
    return {"value_diff": dv, "grad_maxdiff": dg}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="ci.py --quick profile: best-of-1 pairs, "
                         "short decode, paged A/B skipped (gates and "
                         "identity assertions unchanged)")
    ap.add_argument("--reps", type=int, default=None,
                    help="interleaved timing pairs per A/B (default "
                         "3, smoke 1)")
    args = ap.parse_args()
    reps = args.reps or (1 if args.smoke else 3)
    max_new = 16 if args.smoke else 48

    _clear_knobs()   # the knobs under test must start from OFF
    try:
        modeled = _modeled(reps, include_paged=not args.smoke)
        engine = _engine_identity(max_new, slots=2 if args.smoke else 4)
        ce = _ce_identity()
    except AssertionError as e:
        print(json.dumps({"error": str(e)}))
        return 1

    drop = modeled["decode"]["fused_hbm_drop"]
    kdelta = modeled["train_ce"]["kernel_delta"]
    gates = {
        "decode_hbm_drop": "pass" if drop >= GATE_DECODE_DROP
        else "FAIL",
        "ce_kernels_removed": "pass" if kdelta <= GATE_CE_KERNELS
        else "FAIL",
        "ce_bytes_not_worse": "pass"
        if modeled["train_ce"]["hbm_bytes"][1]
        <= modeled["train_ce"]["hbm_bytes"][0] else "FAIL",
        "engine_identity_zero_recompile": "pass",  # asserted above
    }
    rec = {
        "metric": "fusion_ab",
        "value": drop,
        "unit": "fused_decode_hbm_drop_fraction",
        "gate_decode_drop": GATE_DECODE_DROP,
        "modeled": modeled,
        "engine": engine,
        "ce": ce,
        "reps": reps,
        "smoke": bool(args.smoke),
        "gates": gates,
    }
    print(json.dumps(rec))
    return 0 if all(v == "pass" for v in gates.values()) else 1


if __name__ == "__main__":
    sys.exit(main())
