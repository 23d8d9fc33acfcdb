"""Serving latency benchmark — BASELINE.md north-star config 5.

Measures, through the production serving path (`paddle_tpu.inference`
Config -> create_predictor -> zero-copy run; reference:
paddle/fluid/inference/api/analysis_predictor.cc + the model-bench CI
tools/ci_model_benchmark.sh):

  1. ERNIE-3.0-class encoder request latency: p50/p90/p99 over N
     single-request runs (batch 1 x seq 128, classification head input).
     Stated plainly (VERDICT r4 weak #6): "ERNIE" here is the
     BERT-geometry config models/bert.py aliases as ernie_3_* — the
     right geometry/serving-path proxy, not pretrained ERNIE weights.
  2. KV-cache autoregressive decode: ms/token through models.generate
     (greedy, cached_attention path).

Concurrent mode (--concurrent): K closed-loop clients with mixed
prompt/output lengths hammer the continuous-batching engine
(inference/engine.py), reported against the sequential generate() loop
over the identical request set — aggregate tokens/s + p50/p90/p99
per-request latency + the speedup. Both sides are compile-warmed first
so the number is steady-state serving, not XLA.

Tier mode (--tier): closed-loop clients through the multi-replica
serving tier (inference/router.py — replica subprocesses behind the
health-aware router) across three phases: steady state, a kill -9 of a
live replica mid-traffic, and a rolling restart mid-traffic. The
REPORTED GATES are p99 latency and error rate per phase — NOT
throughput (this host has one CPU core; replica processes time-slice
it). Hard asserts: zero hung requests, zero connection resets, greedy
tokens identical for identical requests across all phases/replicas,
and zero XLA compiles in the rolling-restart successors (store-warm).

Run on TPU:  python tools/bench_serving.py [--concurrent]
CPU smoke:   JAX_PLATFORMS=cpu python tools/bench_serving.py --smoke [--concurrent]
Prints ONE BENCH-style JSON line.
"""
import argparse
import json
import os
import sys
import tempfile
import threading
import time

import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])


def _percentiles(ms):
    a = np.asarray(sorted(ms))
    return (float(np.percentile(a, 50)), float(np.percentile(a, 90)),
            float(np.percentile(a, 99)))


# request phases the obs engine histograms break a request into
# (ISSUE 8): where did this request's latency go?
_PHASES = ("queue_wait", "prefill", "decode", "ttft")


def _phase_snaps():
    """Snapshot the engine phase histograms (obs registry) so a later
    delta covers exactly one measured epoch; {} when obs is off."""
    from paddle_tpu import obs
    if not obs.enabled():
        return {}
    out = {}
    for ph in _PHASES:
        h = obs.metrics.registry.get(f"ptpu_engine_{ph}_ms")
        if h is not None:
            out[ph] = (h, h.snap())
    return out


def _phase_percentiles(snaps):
    """p50/p90/p99 per phase since the snapshot (bucket-interpolated,
    obs.metrics.HistSnap)."""
    out = {}
    for ph, (h, before) in snaps.items():
        d = h.snap().minus(before)
        if d.count <= 0:
            continue
        out[ph] = {"p50_ms": round(d.percentile(0.50), 2),
                   "p90_ms": round(d.percentile(0.90), 2),
                   "p99_ms": round(d.percentile(0.99), 2),
                   "count": d.count}
    return out


def bench_encoder(smoke: bool, iters: int):
    import paddle_tpu as paddle
    from paddle_tpu.inference import Config, create_predictor
    from paddle_tpu.models import ErnieModel, ernie_3_base, ernie_3_tiny

    paddle.seed(0)
    cfg = ernie_3_tiny() if smoke else ernie_3_base()
    model = ErnieModel(cfg)
    model.eval()
    if not smoke:
        model.bfloat16()

    seq = 128
    with tempfile.TemporaryDirectory() as td:
        path = td + "/ernie"
        paddle.jit.save(model, path, input_spec=[
            paddle.jit.InputSpec([1, seq], dtype="int64")])
        pred = create_predictor(Config(path + ".pdmodel"))
        ids = np.random.RandomState(0).randint(
            0, cfg.vocab_size, (1, seq)).astype("int64")
        h = pred.get_input_handle(pred.get_input_names()[0])
        out_h = None
        lat = []
        for i in range(iters + 3):
            t0 = time.perf_counter()
            h.copy_from_cpu(ids)
            pred.run()
            out_h = pred.get_output_handle(pred.get_output_names()[0])
            out_h.copy_to_cpu()          # host sync = request complete
            dt = (time.perf_counter() - t0) * 1e3
            if i >= 3:                    # drop compile + warmup
                lat.append(dt)
    return _percentiles(lat)


def bench_decode(smoke: bool, new_tokens: int,
                 cache_dtypes=("bfloat16", "int8")):
    """{cache_dtype: decode ms/token} — ONE model build, measured per
    cache dtype (each dtype keys its own compiled program)."""
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM, gpt_125m, gpt_tiny

    paddle.seed(0)
    cfg = gpt_tiny() if smoke else gpt_125m()
    model = GPTForCausalLM(cfg)
    model.eval()
    if not smoke:
        model.bfloat16()
    prompt = paddle.to_tensor(np.random.RandomState(1).randint(
        0, cfg.vocab_size, (1, 16)).astype("int64"))
    out = {}
    for dtype in cache_dtypes:
        kw = {"cache_dtype": dtype}
        # warmup with the SAME shapes: the cache length (prompt + new
        # tokens) keys the compiled decode program, so a different token
        # budget would compile a different program and the measurement
        # would time XLA
        model.generate(prompt, max_new_tokens=new_tokens, **kw)
        model.generate(prompt, max_new_tokens=1, **kw)
        t0 = time.perf_counter()
        model.generate(prompt, max_new_tokens=new_tokens, **kw)
        dt_full = time.perf_counter() - t0
        t0 = time.perf_counter()
        model.generate(prompt, max_new_tokens=1, **kw)
        dt_one = time.perf_counter() - t0
        # subtract the prefill (the 1-token call is prefill + one
        # select) so the number reports pure per-token DECODE cost
        out[dtype] = (max(dt_full - dt_one, 0.0) * 1e3
                      / max(new_tokens - 1, 1))
    return out


def bench_concurrent(smoke: bool, clients: int, per_client: int,
                     cache_dtype: str = "bfloat16"):
    """Engine vs sequential generate() loop over the SAME mixed-length
    request stream.

    Closed-loop clients: each thread issues its next request only after
    the previous one resolved — the steady-state pressure pattern of a
    fleet of synchronous callers.

    The headline workload DRIFTS: its distinct (prompt-len,
    max-new-tokens) pairs exceed generate()'s compiled-program LRU
    (PADDLE_TPU_GEN_PROG_CACHE, 16), the regime of real mixed traffic.
    Sequential generate() keys one compiled program per exact pair, so
    the working set thrashes its LRU and re-jits continuously — even a
    full warm epoch cannot help (the measured epoch is epoch 2). The
    engine serves the identical stream through a CONSTANT program set
    (bucketed prefill + one batched decode), asserted via
    `programs_recompiled_after_warmup`. A secondary bucket-ALIGNED
    measurement (both paths fully warm, zero re-jit anywhere) isolates
    pure decode-multiplexing so the record shows where the win comes
    from on this backend.
    """
    import paddle_tpu as paddle
    from paddle_tpu.inference.engine import ContinuousBatchingEngine
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny

    paddle.seed(0)
    model = GPTForCausalLM(gpt_tiny())
    model.eval()
    rng = np.random.RandomState(0)

    # drifting mixed stream: >16 distinct (P, max_new) pairs
    if smoke:
        p_vals = list(range(4, 24, 2))            # 10 prompt lengths
        n_vals = [6, 10]
        max_len, buckets, tick = 64, (8, 16, 32), 8
    else:
        p_vals = list(range(4, 32, 2))            # 14 prompt lengths
        n_vals = [16, 24, 32]
        max_len, buckets, tick = 80, (8, 16, 32), 8
    combos = [(p, n) for n in n_vals for p in p_vals]
    prompts = {p: rng.randint(0, 250, (p,)).astype("int64")
               for p in {c[0] for c in combos}}
    reqs = [combos[(c * per_client + i) % len(combos)]
            for c in range(clients) for i in range(per_client)]

    engine = ContinuousBatchingEngine(
        model, slots=clients, max_len=max_len, cache_dtype=cache_dtype,
        prefill_buckets=buckets, tick_tokens=tick,
        max_queue=max(32, clients * per_client))

    def run_engine(request_list):
        lat_ms, lock = [], threading.Lock()

        def client(c):
            for i in range(per_client):
                P, n = request_list[c * per_client + i]
                t0 = time.perf_counter()
                engine.generate(prompts[P], max_new_tokens=n,
                                timeout=600)
                dt = (time.perf_counter() - t0) * 1e3
                with lock:
                    lat_ms.append(dt)

        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return time.perf_counter() - t0, lat_ms

    def run_sequential(request_list):
        t0 = time.perf_counter()
        for P, n in request_list:
            model.generate(prompts[P][None], max_new_tokens=n,
                           cache_dtype=cache_dtype)
        return time.perf_counter() - t0

    total_new = sum(n for _, n in reqs)

    # -- warm epoch for BOTH paths (engine compiles its constant set;
    # sequential fills — and already thrashes — its per-pair LRU)
    run_engine(reqs)
    progs_after_warmup = engine.compiled_program_count
    run_sequential(reqs)

    # obs phase histograms (paddle_tpu.obs): snapshot after the warm
    # epoch so the reported percentiles cover EXACTLY the measured one
    phase_snaps = _phase_snaps()

    # -- measured epoch 2
    wall_engine, lat_ms = run_engine(reqs)
    phase_ms = _phase_percentiles(phase_snaps)
    wall_seq = run_sequential(reqs)
    engine_tps = total_new / wall_engine
    seq_tps = total_new / wall_seq
    p50, p90, p99 = _percentiles(lat_ms)
    recompiled = engine.compiled_program_count - progs_after_warmup

    # -- secondary: bucket-aligned steady state, everything warm
    aligned = [(8, 8), (16, 12), (32, 8), (8, 12)] if smoke else \
        [(8, 24), (16, 32), (32, 16), (8, 32), (16, 16), (32, 24)]
    a_reqs = [aligned[(c * per_client + i) % len(aligned)]
              for c in range(clients) for i in range(per_client)]
    a_total = sum(n for _, n in a_reqs)
    for p, _ in aligned:
        prompts.setdefault(p, rng.randint(0, 250, (p,)).astype("int64"))
    run_engine(a_reqs)                    # warm
    run_sequential(a_reqs)                # warm
    a_wall_engine, _ = run_engine(a_reqs)
    a_wall_seq = run_sequential(a_reqs)

    # model efficiency (ISSUE 14): the engine's OWN live gauge value —
    # modeled tick HBM bytes over measured tick wall time as a fraction
    # of the efficiency chip's bandwidth (obs/efficiency.py, the same
    # formula ptpu_engine_tick_model_eff exports; chip-relative, so a
    # CPU run reads as a tiny fraction of a TPU's bandwidth)
    from paddle_tpu.obs import efficiency as _eff
    tick_model_eff = engine.stats().get("tick_model_eff")

    engine.stop()
    return {
        "tick_model_eff": tick_model_eff,
        "eff_gauge": _eff.TICK_EFF_GAUGE,
        "eff_chip": _eff.chip_spec().name,
        "engine_tokens_per_s": round(engine_tps, 1),
        "sequential_tokens_per_s": round(seq_tps, 1),
        "speedup": round(engine_tps / seq_tps, 2),
        "p50_ms": round(p50, 2), "p90_ms": round(p90, 2),
        "p99_ms": round(p99, 2),
        "clients": clients, "requests": len(reqs),
        "distinct_shape_pairs": len(combos),
        "new_tokens_total": total_new,
        "slots": engine.slots, "tick_tokens": engine.tick_tokens,
        "cache_dtype": cache_dtype,
        "phase_ms": phase_ms,
        "programs_recompiled_after_warmup": recompiled,
        "aligned_engine_tokens_per_s": round(a_total / a_wall_engine, 1),
        "aligned_sequential_tokens_per_s": round(a_total / a_wall_seq, 1),
        "aligned_speedup": round(a_wall_seq / a_wall_engine, 2),
    }


def _scrape_tier_phases(router):
    """One scrape of the router's aggregated /metrics (replica engine
    histograms summed into ptpu_tier_* series) -> bucket-interpolated
    p50/p90/p99 per request phase — where the tier's request time
    went. Degrades to an {"error": ...} dict, never raises."""
    import urllib.error
    import urllib.request

    from paddle_tpu import obs
    out = {}
    try:
        with urllib.request.urlopen(
                f"http://{router.host}:{router.port}/metrics",
                timeout=10) as r:
            samples = obs.metrics.parse_text(r.read().decode())
        for ph in _PHASES:
            edges, cum = obs.metrics.samples_to_hist(
                samples, f"ptpu_tier_engine_{ph}_ms")
            if cum and cum[-1] > 0:
                out[ph] = {
                    "p50_ms": round(obs.metrics.percentile_from_cum(
                        edges, cum, 0.50), 2),
                    "p90_ms": round(obs.metrics.percentile_from_cum(
                        edges, cum, 0.90), 2),
                    "p99_ms": round(obs.metrics.percentile_from_cum(
                        edges, cum, 0.99), 2),
                    "count": int(cum[-1])}
    except (urllib.error.URLError, OSError, ValueError) as e:
        return {"error": f"{type(e).__name__}: {e}"}
    return out


def bench_paged(smoke: bool):
    """Paged vs slot-row engine at EQUAL cache bytes (ISSUE 9).

    The claim being measured: at a fixed KV-cache byte budget, paging
    admits strictly more concurrent short requests than worst-case slot
    rows (each slot-row engine request reserves max_len tokens; each
    paged request holds ceil((P + max_new + tick)/page) pages), and
    prefix-cache hits cut admission (prefill) latency because a cached
    prompt re-prefills only its un-cached suffix — ONE token when fully
    cached.

    Setup: GPT-tiny, max_len=64. Slot engine: 4 slots = 256 token-rows.
    Paged engine: 16 slots over a 16-page x 16-token pool = the SAME
    256 token-rows (byte equality ASSERTED over the live cache
    pytrees). Workloads: a prefix-free short-request burst (P=8,
    max_new=8 -> 2 pages each -> pool caps at 8 concurrent) and a
    prefix-heavy burst (shared 16-token system prompt + distinct
    4-token tails -> 1 shared + 1 private page each -> ~15 concurrent).
    Peak concurrency is sampled from engine.stats() while the burst is
    in flight. Admission latency: max_new=1 requests (retire at the
    tick boundary without decoding), fresh prompts vs re-sent ones.
    """
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.inference.engine import ContinuousBatchingEngine
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny

    paddle.seed(0)
    model = GPTForCausalLM(gpt_tiny())
    model.eval()
    rng = np.random.RandomState(0)
    max_len, ps = 64, 16
    slot_slots, paged_slots, num_pages = 4, 16, 16
    burst = 16

    def tree_bytes(tree):
        return int(sum(x.size * x.dtype.itemsize
                       for x in jax.tree_util.tree_leaves(tree)))

    def peak_concurrency(eng, prompts, max_new):
        futs = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
        peak = 0
        while any(not f.done() for f in futs):
            peak = max(peak, eng.stats()["active"])
            time.sleep(0.001)   # don't contend the engine cv/GIL
        for f in futs:
            f.result(timeout=600)
        return peak

    def mk(paged):
        return ContinuousBatchingEngine(
            model, slots=paged_slots if paged else slot_slots,
            max_len=max_len, cache_dtype="float32",
            prefill_buckets=(8, 16, 32, 64), tick_tokens=4,
            max_queue=4 * burst, paged=paged, page_size=ps,
            num_pages=num_pages)

    shared = rng.randint(0, 250, (16,)).astype("int64")
    free_mix = [rng.randint(0, 250, (8,)).astype("int64")
                for _ in range(burst)]
    heavy_mix = [np.concatenate([shared,
                                 rng.randint(0, 250, (4,))
                                 .astype("int64")])
                 for _ in range(burst)]

    slot_eng = mk(paged=False)
    slot_bytes = tree_bytes(slot_eng._caches)
    slot_eng.warmup()
    # warm pass so admission cadence, not XLA, shapes the peak
    peak_concurrency(slot_eng, free_mix[:4], 8)
    slot_free = peak_concurrency(slot_eng, free_mix, 8)
    slot_heavy = peak_concurrency(slot_eng, heavy_mix, 8)
    slot_eng.stop()

    paged_eng = mk(paged=True)
    paged_bytes = tree_bytes(paged_eng._caches)
    paged_eng.warmup()
    peak_concurrency(paged_eng, free_mix[:4], 8)
    paged_free = peak_concurrency(paged_eng, free_mix, 8)
    paged_heavy = peak_concurrency(paged_eng, heavy_mix, 8)

    paged_eng.stop()

    # -- prefix-hit admission latency (max_new=1: pure prefill probes).
    # The shape is the million-users one: a LONG shared system prompt
    # with short distinct user tails. A miss prefills the whole 72
    # tokens (bucket 128); a hit matches the system prompt's 4 pages in
    # the trie and prefills only the 8-token tail (bucket 8) — the
    # saved PREFILL COMPUTE is the win being measured, so the probe
    # deliberately avoids the fully-cached corner where a COW page-copy
    # dispatch (not compute) dominates on this 1-core host.
    lat_eng = ContinuousBatchingEngine(
        model, slots=4, max_len=128, cache_dtype="float32",
        prefill_buckets=(8, 16, 32, 64, 128), tick_tokens=4,
        max_queue=8, paged=True, page_size=ps, num_pages=64)
    lat_eng.warmup()
    reps = 8 if smoke else 32
    miss_ms, hit_ms = [], []
    # throwaway pair primes both suffix buckets + the trie code paths
    w_sys = rng.randint(0, 250, (64,)).astype("int64")
    for _ in range(2):
        ids = np.concatenate([w_sys,
                              rng.randint(0, 250, (8,)).astype("int64")])
        lat_eng.generate(ids, max_new_tokens=1, timeout=600)
    for i in range(reps):
        system = rng.randint(0, 250, (64,)).astype("int64")
        t1 = np.concatenate([system,
                             rng.randint(0, 250, (8,)).astype("int64")])
        t2 = np.concatenate([system,
                             rng.randint(0, 250, (8,)).astype("int64")])
        t0 = time.perf_counter()
        lat_eng.generate(t1, max_new_tokens=1, timeout=600)
        miss_ms.append((time.perf_counter() - t0) * 1e3)
        t0 = time.perf_counter()
        lat_eng.generate(t2, max_new_tokens=1, timeout=600)
        hit_ms.append((time.perf_counter() - t0) * 1e3)
    pst = lat_eng.stats()
    lat_eng.stop()

    miss_p50 = float(np.percentile(miss_ms, 50))
    hit_p50 = float(np.percentile(hit_ms, 50))
    clean = (paged_bytes == slot_bytes
             and paged_free > slot_free
             and paged_heavy >= paged_free
             and hit_p50 < miss_p50
             and pst["prefix_hits"] >= reps)
    return {
        "cache_bytes": slot_bytes,
        "cache_bytes_equal": paged_bytes == slot_bytes,
        "page_size": ps,
        "num_pages": num_pages,
        "burst_requests": burst,
        "slot_engine": {
            "slots": slot_slots,
            "peak_concurrent_prefix_free": slot_free,
            "peak_concurrent_prefix_heavy": slot_heavy,
        },
        "paged_engine": {
            "slots": paged_slots,
            "peak_concurrent_prefix_free": paged_free,
            "peak_concurrent_prefix_heavy": paged_heavy,
            "prefix_hits": pst["prefix_hits"],
            "prefix_hit_rate": pst["prefix_hit_rate"],
            "prefix_tokens_saved": pst["prefix_tokens_saved"],
        },
        "concurrency_gain_prefix_free": round(
            paged_free / max(slot_free, 1), 2),
        "concurrency_gain_prefix_heavy": round(
            paged_heavy / max(slot_heavy, 1), 2),
        "admit_ms_prefix_miss_p50": round(miss_p50, 2),
        "admit_ms_prefix_hit_p50": round(hit_p50, 2),
        "prefix_hit_admit_speedup": round(miss_p50 / max(hit_p50, 1e-9),
                                          2),
        "clean": clean,
    }


def bench_spec(smoke: bool):
    """Speculative (n-gram self-drafting) vs plain decode on a
    repetitive-text mix (ISSUE 13).

    The claim being measured: with the n-gram drafter hitting, one
    verify forward emits MULTIPLE tokens (accepted prefix + correction)
    where the plain tick pays one forward per token — so end-to-end
    ms/token drops on repetitive context at bitwise-identical greedy
    output.

    Workload honesty: "repetitive text" means text whose GREEDY
    CONTINUATION is repetitive (templated continuations, quoted
    context, code — the regime speculative decoding targets). A
    random-weight tiny model doesn't speak English, so arbitrary
    prompts produce arbitrary drift — the plain-decode regime, not the
    one being measured. The bench therefore SCREENS candidate periodic
    prompts through one plain generate() each and keeps those the
    model actually continues repetitively (its attractors — the
    tiny-model stand-in for real repetitive text); the screen is
    reported in the record, not hidden.

    Hard asserts (rec["clean"]): token identity spec vs plain for
    every request, ZERO recompiles across the measured phase on BOTH
    engines, accepted-tokens-per-tick (per slot per verify forward)
    > 1, and a ms/token win for the speculative engine.
    """
    import paddle_tpu as paddle
    from paddle_tpu.inference.engine import ContinuousBatchingEngine
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny

    paddle.seed(0)
    model = GPTForCausalLM(gpt_tiny())
    model.eval()
    rng = np.random.RandomState(0)

    # slots divide reqs: waves admit and retire ALIGNED (equal budgets
    # through FIFO admission), so neither engine pays ragged-tail
    # ticks where one live slot rides a full-batch dispatch
    slots, tick, spec_k = 4, 4, 12
    reqs = 4 if smoke else 8
    max_new = 80
    rounds = 2 if smoke else 3

    def is_repetitive(out_new):
        t = out_new[2:]
        return any((t[:-g] == t[g:]).all() for g in range(1, 5))

    prompts, screened = [], 0
    while len(prompts) < reqs and screened < 32 * reqs:
        period = 3 + (screened % 3)
        pat = rng.randint(0, 250, (period,)).astype("int64")
        cand = np.tile(pat, -(-16 // period))[:16]
        screened += 1
        out = model.generate(cand[None], max_new_tokens=max_new,
                             cache_dtype="float32")[0][16:]
        if is_repetitive(out):
            prompts.append(cand)
    assert len(prompts) == reqs, \
        f"only {len(prompts)}/{reqs} repetitive prompts in " \
        f"{screened} candidates"

    def mk(spec):
        return ContinuousBatchingEngine(
            model, slots=slots, max_len=128, cache_dtype="float32",
            prefill_buckets=(8, 16), tick_tokens=tick,
            max_queue=4 * reqs,
            speculative="ngram" if spec else False, spec_k=spec_k)

    def drive(eng):
        futs = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
        return [f.result(timeout=600) for f in futs]

    # both engines live for the whole measurement; passes INTERLEAVE
    # (plain, spec, plain, spec, ...) and each side keeps its best —
    # this 1-core host's seconds-scale load jitter correlates across
    # neighbors, so interleaved best-of-N beats per-side averaging
    # (the bench_train_loop discipline)
    engines = {"plain": mk(False), "spec": mk(True)}
    results, walls = {}, {"plain": [], "spec": []}
    warm_progs = {}
    for name, eng in engines.items():
        eng.warmup()
        results[name] = drive(eng)       # warm pass: steady-state only
        warm_progs[name] = eng.compiled_program_count
    for _ in range(rounds):
        for name, eng in engines.items():
            t0 = time.perf_counter()
            outs = drive(eng)
            walls[name].append(time.perf_counter() - t0)
            results[name] = outs
    timing = {}
    tokens = reqs * max_new
    for name, eng in engines.items():
        wall = min(walls[name])
        timing[name] = {
            "wall_s": round(wall, 3),
            "ms_per_token": round(wall * 1e3 / tokens, 3),
            "recompiles_measured_phase":
                eng.compiled_program_count - warm_progs[name],
            "stats": eng.stats(),
        }
        eng.stop()

    identical = all(
        np.array_equal(a, b)
        for a, b in zip(results["plain"], results["spec"]))
    st = timing["spec"]["stats"]
    per_tick = st["accepted_tokens_per_tick"]
    plain_ms = timing["plain"]["ms_per_token"]
    spec_ms = timing["spec"]["ms_per_token"]
    clean = (identical
             and timing["plain"]["recompiles_measured_phase"] == 0
             and timing["spec"]["recompiles_measured_phase"] == 0
             and per_tick > 1.0
             and spec_ms < plain_ms)
    return {
        "requests": reqs,
        "prompts_screened": screened,
        "max_new_tokens": max_new,
        "spec_k": spec_k,
        "tick_tokens": tick,
        "tokens_identical": identical,
        "plain_ms_per_token": plain_ms,
        "spec_ms_per_token": spec_ms,
        "speedup": round(plain_ms / max(spec_ms, 1e-9), 3),
        "accepted_tokens_per_tick": per_tick,
        "acceptance_rate": st["acceptance_rate"],
        "tokens_drafted": st["tokens_drafted"],
        "tokens_accepted": st["tokens_accepted"],
        "spec_ticks": st["spec_ticks"],
        "recompiles_measured_phase": [
            timing["plain"]["recompiles_measured_phase"],
            timing["spec"]["recompiles_measured_phase"]],
        "clean": clean,
    }


def bench_tier(smoke: bool, clients: int, per_client: int):
    """Closed-loop clients through the router tier across chaos phases.

    Every client retries a 503 after the response's own
    ``retry_after_s`` hint (the Retry-After contract) and counts it as
    an error; a connection reset or a request that exceeds the client
    timeout is UNCLEAN (the tier's zero-hangs / zero-resets claim) and
    fails the bench. Greedy determinism is asserted for free: all
    replicas hold identical weights, so every 200 for the same
    (prompt, max_new) pair must carry identical tokens — across
    replicas, kills, and the rolling restart.
    """
    import os
    import signal
    import threading
    import urllib.error
    import urllib.request

    from paddle_tpu.inference.router import (ReplicaSpec, Router,
                                             single_device_child_env)

    model = {"kind": "gpt", "vocab_size": 192, "hidden_size": 32,
             "num_layers": 1, "num_heads": 2, "max_seq_len": 96}
    engine = {"slots": 4, "max_len": 80, "cache_dtype": "float32",
              "prefill_buckets": (8, 16), "tick_tokens": 4}
    # replicas are separate processes: force cpu + a single-device mesh
    # into the children whatever harness env the bench inherited
    child_env = single_device_child_env("cpu")
    store = tempfile.mkdtemp(prefix="bench_tier_store_")
    spec = ReplicaSpec(model, engine, warmup=True, drain_s=20.0, seed=0,
                       env=child_env)
    router = Router(spec, replicas=2, poll_s=0.3, deadline_s=120.0,
                    exec_store_dir=store).start()
    if not router.wait_ready(2, timeout=300):
        router.stop()
        raise RuntimeError(f"tier never ready: {router.replicas()}")
    base = f"http://{router.host}:{router.port}/generate"

    rng = np.random.RandomState(0)
    combos = [(4, 4), (7, 6), (12, 4), (6, 8)]
    prompts = {p: rng.randint(0, 150, (p,)).tolist()
               for p, _ in combos}
    tokens_seen = {}      # (P, n) -> first 200's tokens (identity oracle)
    lock = threading.Lock()

    def run_phase(name, chaos=None):
        lat_ms, errors = [], []
        resets = hangs = mismatches = gave_up = 0

        def client(c):
            nonlocal resets, hangs, mismatches, gave_up
            for i in range(per_client):
                P, n = combos[(c + i) % len(combos)]
                payload = json.dumps(
                    {"input_ids": prompts[P],
                     "max_new_tokens": n}).encode()
                t0 = time.perf_counter()
                for _ in range(12):          # closed-loop with backoff
                    try:
                        req = urllib.request.Request(
                            base, payload,
                            {"Content-Type": "application/json"})
                        with urllib.request.urlopen(
                                req, timeout=180) as r:
                            body = json.loads(r.read())
                        with lock:
                            lat_ms.append(
                                (time.perf_counter() - t0) * 1e3)
                            want = tokens_seen.setdefault(
                                (P, n), body["tokens"])
                            if want != body["tokens"]:
                                mismatches += 1
                        break
                    except urllib.error.HTTPError as e:
                        try:
                            body = json.loads(e.read())
                        except ValueError:
                            body = {}
                        with lock:
                            errors.append(body.get("error", e.code))
                        time.sleep(min(
                            float(body.get("retry_after_s", 1.0)), 2.0))
                    except (TimeoutError, OSError) as e:
                        with lock:
                            if "timed out" in str(e).lower():
                                hangs += 1
                            else:
                                resets += 1
                        break
                else:
                    # all retry attempts returned 503: this request
                    # never completed — it MUST count against the
                    # no-silent-drops gate, not vanish
                    with lock:
                        gave_up += 1

        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        chaos_result = chaos() if chaos is not None else None
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        p50, p90, p99 = _percentiles(lat_ms) if lat_ms else (0, 0, 0)
        # every issued request must be accounted: ok, hung, reset, or
        # retry-exhausted — total is the ISSUED count, not a sum of
        # the outcomes we happened to observe
        total = clients * per_client
        failed = total - len(lat_ms)
        return {
            "phase": name, "wall_s": round(wall, 1),
            "requests_issued": total,
            "requests_ok": len(lat_ms),
            "errors_503_retried": len(errors),
            "error_rate": round(len(errors) / max(
                len(lat_ms) + len(errors), 1), 3),
            "resets": resets, "hangs": hangs,
            "retry_exhausted": gave_up,
            "token_mismatches": mismatches,
            "failed_requests": failed,
            "p50_ms": round(p50, 1), "p99_ms": round(p99, 1),
            "chaos": chaos_result,
        }

    def kill_one():
        time.sleep(0.3)                 # let traffic land first
        victim = router.replicas()[0]
        os.kill(victim["pid"], signal.SIGKILL)
        return {"killed": victim["name"]}

    def rolling():
        res = router.rolling_restart(ready_timeout=300)
        return {"rolling_ok": res["ok"],
                "replaced": len(res["replaced"])}

    phases = [run_phase("steady")]
    # tier-level phase percentiles: scrape the router's aggregated
    # /metrics NOW, while the replicas that served the steady phase
    # are still alive — replica histograms die with their process, so
    # a post-chaos scrape would only see the freshly-rotated
    # successors' (near-empty) series
    tier_phase_ms = _scrape_tier_phases(router)
    phases += [run_phase("replica_kill", chaos=kill_one),
               run_phase("rolling_restart", chaos=rolling)]
    router.wait_ready(2, timeout=120)
    successor_compiles = []
    # skip replicas mid-drain (a trim/retire may still be finishing):
    # the store-warm claim is about the replicas actually serving
    for r in [x for x in router.replicas() if not x["draining"]]:
        try:
            with urllib.request.urlopen(
                    f"http://{router.host}:{r['port']}/healthz",
                    timeout=5) as resp:
                h = json.loads(resp.read())
            successor_compiles.append(
                h.get("compilation", {}).get("xla_compiles", -1))
        except (urllib.error.URLError, OSError, ValueError):
            successor_compiles.append(-1)
    stats = dict(router.stats_counters)
    router.stop()
    import shutil
    shutil.rmtree(store, ignore_errors=True)

    all_lat_p99 = max(p["p99_ms"] for p in phases)
    clean = (all(p["resets"] == 0 and p["hangs"] == 0
                 and p["token_mismatches"] == 0
                 and p["failed_requests"] == 0 for p in phases)
             and all(c == 0 for c in successor_compiles))
    return {
        "phases": phases,
        "tier_phase_ms": tier_phase_ms,
        "p99_ms_worst_phase": round(all_lat_p99, 1),
        "error_rate_overall": round(
            sum(p["errors_503_retried"] for p in phases) / max(
                sum(p["requests_ok"] + p["errors_503_retried"]
                    for p in phases), 1), 3),
        "successor_xla_compiles": successor_compiles,
        "router_stats": stats,
        "clients": clients, "per_client_per_phase": per_client,
        "clean": clean,
    }


def bench_recovery(smoke: bool):
    """Work-conserving request recovery + hedged decode chaos gates
    (ISSUE 15).

    Phase 1 — kill-mid-decode: long PAGED decodes (shared 32-token
    prompt) through a 2-replica tier; one replica is kill -9'd while
    its requests are mid-decode. Clients make EXACTLY ONE attempt
    each: the router's token journal + resume must absorb the kill —
    every client gets 200 with tokens BITWISE identical to the
    undisturbed oracle, zero client-visible errors. The resumed
    requests re-prefill only the un-cached suffix (the survivor's
    prefix trie already holds the shared prompt pages —
    prefix-hit-counter asserted), recoveries are visible in
    ptpu_router_recoveries_total and a flight_request_recovery
    artifact names the migrated request ids, and the survivor's
    compiled-program count is UNCHANGED (resume rides the registered
    admit/decode programs — zero new XLA programs). The router also
    pre-warms the journaled prefix on the standby as it grows
    (ISSUE 17): prewarms >= 1 and prewarmed_resumes >= 1 are gated —
    at least one cutover landed on a replica whose trie the router
    had warmed for that request ahead of the splice.

    Phase 2 — stall-hedge: one replica's decode loop is wedged via
    the replica_stall fault site (latency injection through
    /admin/inject — the process stays alive and ready-looking).
    Requests landing on it stall; past the hedge budget the router
    launches a backup on the healthy replica, the backup wins, and
    the stalled loser is CANCELLED. Gates: every request 200 +
    token-identical, worst-phase p99 well under the wedge duration
    (vs unbounded without hedging), hedges/hedge_wins/cancels
    counters move, and after the wedge clears both replicas end
    leak-free (active==0, pages_used back to the trie-held count).
    """
    import glob
    import os
    import signal
    import threading
    import urllib.error
    import urllib.request

    from paddle_tpu import obs
    from paddle_tpu.inference.router import (ReplicaSpec, Router,
                                             single_device_child_env)

    model = {"kind": "gpt", "vocab_size": 160, "hidden_size": 32,
             "num_layers": 1, "num_heads": 2, "max_seq_len": 160}
    engine = {"slots": 4, "max_len": 128, "cache_dtype": "float32",
              "prefill_buckets": (8, 16, 32, 64, 96), "tick_tokens": 2,
              "paged": True, "page_size": 8}
    wedge_s = 6.0 if smoke else 10.0
    clients = 4
    child_env = single_device_child_env("cpu")
    child_env["PADDLE_TPU_CHAOS_ADMIN"] = "1"   # phase 2 arms the stall
    store = tempfile.mkdtemp(prefix="bench_recovery_store_")
    spec = ReplicaSpec(model, engine, warmup=True, drain_s=20.0, seed=0,
                       env=child_env)
    router = Router(spec, replicas=2, poll_s=0.25, deadline_s=120.0,
                    exec_store_dir=store, hedge_s=1.0).start()
    if not router.wait_ready(2, timeout=300):
        router.stop()
        raise RuntimeError(f"tier never ready: {router.replicas()}")
    base = f"http://{router.host}:{router.port}"
    rng = np.random.RandomState(0)
    prompt = rng.randint(0, 150, (32,)).tolist()   # 4 shared KV pages
    max_new = 80                  # long decodes: a real kill window

    def gen(timeout=110.0):
        req = urllib.request.Request(
            base + "/generate",
            json.dumps({"input_ids": prompt,
                        "max_new_tokens": max_new}).encode(),
            {"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return json.loads(r.read())

    def replica_healthz(rep_snapshot):
        url = (f"http://{router.host}:{rep_snapshot['port']}/healthz")
        try:
            with urllib.request.urlopen(url, timeout=5) as r:
                return json.loads(r.read())
        except urllib.error.HTTPError as e:
            try:
                return json.loads(e.read())
            except (ValueError, OSError):
                return {}
        except (urllib.error.URLError, OSError, ValueError):
            return {}

    # the undisturbed oracle (also warms routes + seeds both tries as
    # traffic spreads): every later 200 must match it bitwise
    oracle = gen()["tokens"]
    assert gen()["tokens"] == oracle

    def run_phase(name, n_requests, chaos=None):
        lat_ms, bodies, errors = [], [], []

        def client(i):
            t0 = time.perf_counter()
            try:
                b = gen()
                with lock:
                    lat_ms.append((time.perf_counter() - t0) * 1e3)
                    bodies.append(b)
            except Exception as e:   # noqa: BLE001 — ANY client-visible
                with lock:           # failure breaks the gate
                    errors.append(repr(e))

        lock = threading.Lock()
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(n_requests)]
        for t in threads:
            t.start()
        chaos_result = chaos() if chaos is not None else None
        for t in threads:
            t.join(timeout=180)
        mismatches = sum(1 for b in bodies if b["tokens"] != oracle)
        p50, p90, p99 = _percentiles(lat_ms) if lat_ms else (0, 0, 0)
        return {"phase": name, "requests": n_requests,
                "ok": len(bodies), "client_errors": errors,
                "token_mismatches": mismatches,
                "recovered_responses": sum(
                    1 for b in bodies if b.get("recovered")),
                "hedged_responses": sum(
                    1 for b in bodies if b.get("hedged")),
                "p50_ms": round(p50, 1), "p99_ms": round(p99, 1),
                "chaos": chaos_result}

    # ---- phase 1: kill -9 mid-decode ---------------------------------
    pre = {r["name"]: replica_healthz(r) for r in router.replicas()}
    killed = {}
    t_phase1 = time.time()        # only THIS run's flight artifacts

    def kill_busiest():
        # kill on OBSERVED in-flight work, not a timer: warm decodes
        # finish in tens of ms on this host, so a fixed sleep lands
        # the SIGKILL on an idle tier and nothing needs recovering.
        # Waiting for >= 1 streamed forward (then a beat for tokens to
        # hit the journal) guarantees the kill is genuinely mid-decode.
        victim = None
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            snap = router.replicas()
            busiest = max(snap, key=lambda r: r["inflight"])
            if busiest["inflight"] >= 1:
                victim = busiest
                break
            time.sleep(0.002)
        if victim is None:            # no request ever took flight:
            victim = router.replicas()[0]   # kill anyway, gate fails
        time.sleep(0.03)              # a few ticks: tokens journaled
        os.kill(victim["pid"], signal.SIGKILL)
        killed["name"] = victim["name"]
        return {"killed": victim["name"],
                "inflight_at_kill": victim["inflight"]}

    kill_phase = run_phase("kill_mid_decode", clients * 2,
                           chaos=kill_busiest)
    recoveries = router.stats_counters["recoveries"]
    survivors = [r for r in router.replicas()
                 if r["name"] in pre and r["name"] != killed.get("name")
                 and r["state"] == "ready"]
    survivor_h = replica_healthz(survivors[0]) if survivors else {}
    surv_eng = survivor_h.get("engine", {})
    pre_eng = pre.get(survivors[0]["name"], {}).get("engine", {}) \
        if survivors else {}
    # resume re-prefilled only the un-cached suffix: the survivor's
    # prefix trie held the shared prompt pages
    prefix_hits_after = int(surv_eng.get("prefix_hits", 0))
    # zero new XLA programs: resume rode the registered programs
    compiles_delta = (int(surv_eng.get("compiled_programs", -1))
                      - int(pre_eng.get("compiled_programs", -2)))
    with urllib.request.urlopen(base + "/metrics", timeout=10) as r:
        metrics_text = r.read().decode()
    m_recoveries = 0.0
    for name, labels, val in obs.metrics.parse_text(metrics_text):
        if name == "ptpu_router_recoveries_total" and not labels:
            m_recoveries = val
    artifacts = sorted(
        p for p in glob.glob(os.path.join(
            obs.trace.artifact_dir(), "flight_request_recovery_*"))
        if os.path.getmtime(p) >= t_phase1)
    migrated_rids = []
    for p in artifacts:
        try:
            doc = json.load(open(p))
            # dump_flight folds `extra` into the trace metadata
            migrated_rids += [m.get("request_id") for m in
                              doc.get("metadata", {}).get("migrated",
                                                          [])]
        except (ValueError, OSError):
            pass

    # ---- phase 2: stall -> hedge -> cancel ---------------------------
    if not router.wait_ready(2, timeout=180):
        raise RuntimeError(f"tier not back to 2: {router.replicas()}")
    target = router.replicas()[0]
    req = urllib.request.Request(
        f"http://{router.host}:{target['port']}/admin/inject",
        json.dumps({"site": "replica_stall", "count": 1,
                    "wedge_s": wedge_s}).encode(),
        {"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=10):
        pass
    stall_phase = run_phase("stall_hedge", clients)
    # leak-free: after the wedge clears, every replica retires its
    # cancelled losers — active slots drain to 0 and the page pool
    # returns to exactly the trie-held (shared-prefix) pages
    leak_free = False
    deadline = time.monotonic() + wedge_s * 2 + 10
    while time.monotonic() < deadline:
        states = [replica_healthz(r).get("engine", {})
                  for r in router.replicas()]
        if states and all(
                e.get("active", 99) == 0
                and e.get("pages_used", -1)
                == int(replica_healthz(r).get("engine", {}).get(
                    "pages_used", -2))   # stable read
                for e, r in zip(states, router.replicas())):
            # pages_used must equal the cached-prefix page count once
            # nothing is active (allocator leak-free)
            full = [replica_healthz(r) for r in router.replicas()]
            if all(f.get("engine", {}).get("active", 99) == 0
                   for f in full):
                leak_free = True
                break
        time.sleep(0.5)
    pages_end = [replica_healthz(r).get("engine", {})
                 for r in router.replicas()]
    # loser-side cancels run on a router side thread: read the
    # counters only after the leak-free wait above gave them time
    hedge_stats = {k: router.stats_counters[k] for k in
                   ("hedges", "hedge_wins", "cancels_sent")}
    # standby prefix pre-warming (ISSUE 17): the router pushed the
    # journaled prefix to the standby BEFORE the kill, and at least one
    # resume cut over onto a replica it had pre-warmed for that request
    prewarm_stats = {k: router.stats_counters[k] for k in
                     ("prewarms", "prewarmed_resumes")}

    stats = dict(router.stats_counters)
    router.stop()
    import shutil
    shutil.rmtree(store, ignore_errors=True)

    phases = [kill_phase, stall_phase]
    clean = (
        all(not p["client_errors"] and p["token_mismatches"] == 0
            and p["ok"] == p["requests"] for p in phases)
        and recoveries >= 1 and m_recoveries >= 1
        and bool(artifacts) and any(migrated_rids)
        and prefix_hits_after >= 1
        and compiles_delta == 0
        and hedge_stats["hedges"] >= 1
        and hedge_stats["hedge_wins"] >= 1
        and hedge_stats["cancels_sent"] >= 1
        and prewarm_stats["prewarms"] >= 1
        and prewarm_stats["prewarmed_resumes"] >= 1
        and stall_phase["p99_ms"] < wedge_s * 1e3
        and leak_free)
    return {
        "phases": phases,
        "p99_ms_worst_phase": max(p["p99_ms"] for p in phases),
        "recoveries": recoveries,
        "metric_recoveries_total": m_recoveries,
        "recovery_artifacts": [os.path.basename(p) for p in artifacts],
        "migrated_request_ids": migrated_rids,
        "survivor_prefix_hits": prefix_hits_after,
        "survivor_compiles_delta": compiles_delta,
        "hedge": hedge_stats,
        "prewarm": prewarm_stats,
        "stall_wedge_s": wedge_s,
        "stall_p99_vs_wedge": round(
            stall_phase["p99_ms"] / (wedge_s * 1e3), 3),
        "leak_free_after_wedge": leak_free,
        "pages_end": [{k: e.get(k) for k in
                       ("active", "pages_used", "pages_free")}
                      for e in pages_end],
        "router_stats": stats,
        "clean": clean,
    }


def bench_stream(smoke: bool):
    """Streaming-first QoS front chaos gates (ISSUE 16).

    Many closed-loop STREAMING clients (NDJSON through the tier's
    /generate, "stream": true) ride four disturbance phases, with an
    undisturbed greedy oracle taken first:

    - kill_mid_stream: a replica is kill -9'd while its requests are
      streaming. The journal splice must be invisible: every client's
      concatenated token blocks are BITWISE the oracle suffix — zero
      token loss, zero duplicates — and the survivor compiles zero
      new XLA programs.
    - stall_hedge_stream: one replica's decode loop is wedged
      (replica_stall via /admin/inject). The TTFT/decode hedge bounds
      the stall: every stream completes token-identical with p99 well
      under the wedge.
    - rolling_restart_stream: every replica is replaced mid-traffic;
      successors warm from the executable store with ZERO compiles
      and streams stay bitwise-identical.
    - overload_qos: the tier is saturated far past a deliberately
      tiny QoS capacity with mixed tenants/classes. Degradation must
      be truthful PER CLASS: interactive traffic all completes, batch
      sheds with 429 + drain-derived Retry-After, and nothing hangs.

    Plus an affinity A/B: concurrent shared-prefix groups routed with
    prefix-affinity scoring vs load-only (affinity_w=0) — the tier
    prefix_hit_rate must be measurably higher with affinity on.
    """
    import os
    import signal
    import threading
    import urllib.error
    import urllib.request

    from paddle_tpu import obs
    from paddle_tpu.inference.router import (ReplicaSpec, Router,
                                             _QosScheduler,
                                             single_device_child_env)

    model = {"kind": "gpt", "vocab_size": 160, "hidden_size": 32,
             "num_layers": 1, "num_heads": 2, "max_seq_len": 160}
    engine = {"slots": 4, "max_len": 128, "cache_dtype": "float32",
              "prefill_buckets": (8, 16, 32, 64, 96), "tick_tokens": 2,
              "paged": True, "page_size": 8}
    wedge_s = 6.0 if smoke else 10.0
    clients = 3 if smoke else 5
    max_new = 40 if smoke else 80
    child_env = single_device_child_env("cpu")
    child_env["PADDLE_TPU_CHAOS_ADMIN"] = "1"
    store = tempfile.mkdtemp(prefix="bench_stream_store_")
    spec = ReplicaSpec(model, engine, warmup=True, drain_s=20.0, seed=0,
                       env=child_env)
    router = Router(spec, replicas=2, poll_s=0.25, deadline_s=120.0,
                    exec_store_dir=store, hedge_s=1.0,
                    ttft_hedge_s=1.5).start()
    if not router.wait_ready(2, timeout=300):
        router.stop()
        raise RuntimeError(f"tier never ready: {router.replicas()}")
    base = f"http://{router.host}:{router.port}"
    rng = np.random.RandomState(0)
    prompt = rng.randint(0, 150, (32,)).tolist()   # 4 shared KV pages

    def sgen(ids, n, tenant=None, qcls=None, timeout=110.0):
        """One streaming request: returns code/body plus the streamed
        token blocks, TTFT and inter-block gaps. Pre-stream refusals
        (QoS 429/503) come back as plain JSON HTTPErrors."""
        headers = {"Content-Type": "application/json"}
        if tenant:
            headers["X-PTPU-Tenant"] = tenant
        if qcls:
            headers["X-PTPU-Class"] = qcls
        req = urllib.request.Request(
            base + "/generate",
            json.dumps({"input_ids": ids, "max_new_tokens": n,
                        "stream": True}).encode(), headers)
        t0 = time.perf_counter()
        toks, gaps, ttft = [], [], None
        last = t0
        try:
            with urllib.request.urlopen(req, timeout=timeout) as r:
                for raw in r:
                    raw = raw.strip()
                    if not raw:
                        continue
                    ev = json.loads(raw)
                    now = time.perf_counter()
                    if "t" in ev:
                        if ttft is None:
                            ttft = (now - t0) * 1e3
                        else:
                            gaps.append((now - last) * 1e3)
                        last = now
                        toks.extend(ev["t"])
                        continue
                    kind = "done" if "done" in ev else "err"
                    body = ev[kind]
                    return {"code": 200 if kind == "done"
                            else int(body.get("code", 0)),
                            "body": body, "streamed": toks,
                            "ttft_ms": ttft, "gaps_ms": gaps,
                            "wall_ms": (now - t0) * 1e3,
                            "retry_after": body.get("retry_after_s")}
            raise RuntimeError("stream ended without a terminal record")
        except urllib.error.HTTPError as e:
            body = json.loads(e.read())
            return {"code": e.code, "body": body, "streamed": [],
                    "ttft_ms": None, "gaps_ms": [],
                    "wall_ms": (time.perf_counter() - t0) * 1e3,
                    "retry_after": e.headers.get("Retry-After")}

    def replica_healthz(rep_snapshot):
        url = f"http://{router.host}:{rep_snapshot['port']}/healthz"
        try:
            with urllib.request.urlopen(url, timeout=5) as r:
                return json.loads(r.read())
        except urllib.error.HTTPError as e:
            try:
                return json.loads(e.read())
            except (ValueError, OSError):
                return {}
        except (urllib.error.URLError, OSError, ValueError):
            return {}

    def tier_prefix_counters():
        hits = misses = 0
        for r in router.replicas():
            eng = replica_healthz(r).get("engine", {})
            hits += int(eng.get("prefix_hits", 0))
            misses += int(eng.get("prefix_misses", 0))
        return hits, misses

    # undisturbed oracle: a single-shot AND a streamed run must agree
    one = sgen(prompt, max_new)
    assert one["code"] == 200, one
    oracle = one["body"]["tokens"]
    assert one["streamed"] == oracle[len(prompt):]
    two = sgen(prompt, max_new)
    assert two["body"]["tokens"] == oracle

    def run_phase(name, jobs, chaos=None):
        """jobs: list of (ids, max_new, tenant, qcls, check_oracle)."""
        results, errors = [], []
        lock = threading.Lock()

        def client(job):
            ids, n, tenant, qcls, check = job
            try:
                res = sgen(ids, n, tenant, qcls)
                res["job"] = job
                with lock:
                    results.append(res)
            except Exception as e:  # noqa: BLE001 — a hang/reset
                with lock:          # breaks the gate
                    errors.append(repr(e))

        threads = [threading.Thread(target=client, args=(j,))
                   for j in jobs]
        for t in threads:
            t.start()
        chaos_result = chaos() if chaos is not None else None
        for t in threads:
            t.join(timeout=240)
        splice_breaks = 0
        for res in results:
            if res["code"] != 200:
                continue
            ids, n, _, _, check = res["job"]
            b = res["body"]
            # greedy prefix property: a shorter max_new is bitwise a
            # prefix of the undisturbed oracle run
            want_full = (oracle[:len(b["tokens"])] if check
                         else b["tokens"])
            # zero loss, zero duplicates, bitwise vs the oracle: the
            # streamed blocks ARE the done body's suffix, which IS the
            # undisturbed oracle's
            if (b["tokens"] != want_full
                    or res["streamed"]
                    != b["tokens"][len(ids):len(ids)
                                   + b["tokens_generated"]]):
                splice_breaks += 1
        oks = [r for r in results if r["code"] == 200]
        gaps = [g for r in oks for g in r["gaps_ms"]]
        ttfts = [r["ttft_ms"] for r in oks if r["ttft_ms"] is not None]
        return {"phase": name, "requests": len(jobs),
                "ok": len(oks), "client_errors": errors,
                "non_200": sorted(r["code"] for r in results
                                  if r["code"] != 200),
                "splice_breaks": splice_breaks,
                "recovered_responses": sum(
                    1 for r in oks if r["body"].get("recovered")),
                "hedged_responses": sum(
                    1 for r in oks if r["body"].get("hedged")),
                "p99_ttft_ms": round(_percentiles(ttfts)[2], 1)
                if ttfts else 0.0,
                "p99_itl_ms": round(_percentiles(gaps)[2], 1)
                if gaps else 0.0,
                "chaos": chaos_result,
                "results": results}

    shared_job = (prompt, max_new, None, None, True)

    # ---- phase 1: kill -9 mid-stream ---------------------------------
    pre = {r["name"]: replica_healthz(r) for r in router.replicas()}
    killed = {}

    def kill_busiest():
        victim = None
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            snap = router.replicas()
            busiest = max(snap, key=lambda r: r["inflight"])
            if busiest["inflight"] >= 1:
                victim = busiest
                break
            time.sleep(0.002)
        if victim is None:
            victim = router.replicas()[0]
        time.sleep(0.03)          # a few ticks: tokens on the stream
        os.kill(victim["pid"], signal.SIGKILL)
        killed["name"] = victim["name"]
        return {"killed": victim["name"],
                "inflight_at_kill": victim["inflight"]}

    kill_phase = run_phase("kill_mid_stream", [shared_job] * clients * 2,
                           chaos=kill_busiest)
    kill_phase.pop("results")
    recoveries = router.stats_counters["recoveries"]
    survivors = [r for r in router.replicas()
                 if r["name"] in pre and r["name"] != killed.get("name")
                 and r["state"] == "ready"]
    surv_eng = (replica_healthz(survivors[0]).get("engine", {})
                if survivors else {})
    pre_eng = (pre.get(survivors[0]["name"], {}).get("engine", {})
               if survivors else {})
    compiles_delta = (int(surv_eng.get("compiled_programs", -1))
                      - int(pre_eng.get("compiled_programs", -2)))

    # ---- phase 2: stall -> hedge (TTFT + decode) ---------------------
    if not router.wait_ready(2, timeout=180):
        raise RuntimeError(f"tier not back to 2: {router.replicas()}")
    # wedge the replica the affinity-scored _pick will actually route
    # the shared-prefix clients to — wedging the other one would never
    # stall anybody
    from paddle_tpu.inference.paging import chain_hashes
    victim = router._pick(set(), chain_hashes(
        prompt, int(engine["page_size"])))
    target = next(r for r in router.replicas()
                  if r["name"] == victim.name)
    req = urllib.request.Request(
        f"http://{router.host}:{target['port']}/admin/inject",
        json.dumps({"site": "replica_stall", "count": 1,
                    "wedge_s": wedge_s}).encode(),
        {"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=10):
        pass
    stall_phase = run_phase("stall_hedge_stream", [shared_job] * clients)
    stall_phase.pop("results")
    hedge_stats = {k: router.stats_counters[k] for k in
                   ("hedges", "hedge_wins", "ttft_hedges")}
    # let the wedge clear + losers cancel before the next phase
    deadline = time.monotonic() + wedge_s * 2 + 10
    while time.monotonic() < deadline:
        engs = [replica_healthz(r).get("engine", {})
                for r in router.replicas()]
        if engs and all(e.get("active", 99) == 0 for e in engs):
            break
        time.sleep(0.5)

    # ---- phase 3: rolling restart mid-stream -------------------------
    roll = {}

    def rolling():
        roll.update(router.rolling_restart(ready_timeout=240))
        return {"replaced": roll.get("replaced"), "ok": roll.get("ok")}

    roll_phase = run_phase("rolling_restart_stream",
                           [shared_job] * clients * 2, chaos=rolling)
    roll_phase.pop("results")
    successor_compiles = []
    for r in router.replicas():
        if r["draining"]:
            continue
        h = replica_healthz(r)
        successor_compiles.append(
            int(h.get("compilation", {}).get("xla_compiles", -1)))

    # ---- phase 4: overload with per-class truthful degradation -------
    saved_qos = router.qos
    router.qos = _QosScheduler(capacity=2, queue_limit=1,
                               starvation_s=3.0)
    n_i = 3 if smoke else 5
    over_jobs = []
    for i in range(n_i):
        over_jobs.append((prompt, 8, f"hi-{i % 2}", "interactive", True))
    for i in range(2 if smoke else 4):
        over_jobs.append((prompt, 8, f"mid-{i % 2}", "standard", True))
    # batch queue cap is max(1, int(queue_limit * 1.0)) = 1: with more
    # batch arrivals than capacity + that cap, at least one MUST shed
    for i in range(4 if smoke else 6):
        over_jobs.append((prompt, 8, f"lo-{i % 2}", "batch", True))
    over_phase = run_phase("overload_qos", over_jobs)
    over_results = over_phase.pop("results")
    router.qos = saved_qos
    by_class = {}
    for res in over_results:
        cls = res["job"][3]
        d = by_class.setdefault(cls, {"ok": 0, "shed_429": 0,
                                      "other": 0, "retry_after": [],
                                      "ttft_ms": []})
        if res["code"] == 200:
            d["ok"] += 1
            if res["ttft_ms"] is not None:
                d["ttft_ms"].append(round(res["ttft_ms"], 1))
        elif res["code"] == 429:
            d["shed_429"] += 1
            ra = res.get("retry_after")
            d["retry_after"].append(float(ra) if ra is not None
                                    else None)
        else:
            d["other"] += 1
    interactive_clean = (by_class.get("interactive", {}).get("ok", 0)
                         == n_i)
    batch_shed = by_class.get("batch", {}).get("shed_429", 0)
    sheds_truthful = all(
        ra is not None and float(ra) > 0
        for d in by_class.values() for ra in d["retry_after"])
    # no tenant starved: every request either completed or was shed
    # with a truthful hint — nothing hung or vanished
    no_starvation = (over_phase["ok"]
                     + sum(d["shed_429"] + d["other"]
                           for d in by_class.values())
                     == len(over_jobs)
                     and not over_phase["client_errors"])

    # ---- affinity A/B: prefix-affinity vs load-only _pick ------------
    def affinity_arm(tag, groups, per_group):
        # Seed each fresh LONG prefix (8 complete KV pages -> overlap
        # bonus affinity_w*8 = 4.0, dominating transient load diffs)
        # with one request per group, launched CONCURRENTLY so load-
        # only routing spreads the prefixes across both replicas.
        # After the router's health poll picks up the new trie
        # fingerprints, fan each group's followers out concurrently:
        # with affinity they co-locate on the replica that cached
        # their prefix (hits); load-only routing places ~half of them
        # on the other one (misses).
        seeds, prefixes = [], []
        for g in range(groups):
            gp = rng.randint(0, 150, (64,)).tolist()  # 8 KV pages
            prefixes.append(gp)
            seeds.append((gp + rng.randint(0, 150, (4,)).tolist(),
                          6, None, None, False))
        sp = run_phase(f"affinity_{tag}_seed", seeds)
        assert sp["ok"] == len(seeds), sp
        followers = [(gp + rng.randint(0, 150, (4,)).tolist(),
                      6, None, None, False)
                     for gp in prefixes for _ in range(per_group)]
        time.sleep(max(1.0, router.poll_s * 4))
        h0, m0 = tier_prefix_counters()
        ph = run_phase(f"affinity_{tag}", followers)
        ph.pop("results")
        h1, m1 = tier_prefix_counters()
        dh, dm = h1 - h0, m1 - m0
        ph["prefix_hits"] = dh
        ph["prefix_misses"] = dm
        ph["prefix_hit_rate"] = round(dh / max(1, dh + dm), 3)
        return ph

    groups, per_group = (4, 2) if smoke else (4, 3)
    aff_on = affinity_arm("on", groups, per_group)
    router.affinity_w = 0.0
    aff_off = affinity_arm("off", groups, per_group)
    router.affinity_w = 0.5

    # ---- tier metrics: per-class QoS series really exported ----------
    with urllib.request.urlopen(base + "/metrics", timeout=10) as r:
        metrics_text = r.read().decode()
    m_qos_admitted = m_ttft_count = 0.0
    for name, labels, val in obs.metrics.parse_text(metrics_text):
        if name == "ptpu_tier_qos_admitted_total":
            m_qos_admitted += val
        if (name == "ptpu_tier_ttft_ms_count"
                or (name == "ptpu_tier_ttft_ms" and
                    labels.get("le") is None and "count" in labels)):
            m_ttft_count += val

    stats = dict(router.stats_counters)
    router.stop()
    import shutil
    shutil.rmtree(store, ignore_errors=True)

    chaos_phases = [kill_phase, stall_phase, roll_phase]
    itl_bound_ms = 15000.0
    clean = (
        all(not p["client_errors"] and p["splice_breaks"] == 0
            and p["ok"] == p["requests"] for p in chaos_phases)
        and recoveries >= 1
        and compiles_delta == 0
        and roll.get("ok") and len(roll.get("replaced", [])) == 2
        and all(c == 0 for c in successor_compiles)
        and hedge_stats["hedges"] >= 1
        and hedge_stats["hedge_wins"] >= 1
        # hedge slots are budgeted (hedge_frac), so stalled streams un-
        # wedge serially: bound TTFT by the wedge plus hedge headroom,
        # not by the unbounded original
        and stall_phase["p99_ttft_ms"] < (wedge_s + 4.0) * 1e3
        and all(p["p99_itl_ms"] < itl_bound_ms for p in chaos_phases)
        and interactive_clean
        and batch_shed >= 1
        and sheds_truthful
        and no_starvation
        and over_phase["splice_breaks"] == 0
        and aff_on["prefix_hit_rate"] > aff_off["prefix_hit_rate"]
        and m_qos_admitted >= 1
        and stats["streams"] >= 1)
    return {
        "phases": chaos_phases + [over_phase, aff_on, aff_off],
        "p99_itl_ms_worst_phase": max(
            p["p99_itl_ms"] for p in chaos_phases),
        "itl_bound_ms": itl_bound_ms,
        "recoveries": recoveries,
        "survivor_compiles_delta": compiles_delta,
        "successor_compiles": successor_compiles,
        "hedge": hedge_stats,
        "stall_wedge_s": wedge_s,
        "overload_by_class": by_class,
        "interactive_all_served": interactive_clean,
        "batch_sheds": batch_shed,
        "sheds_truthful_retry_after": sheds_truthful,
        "no_starvation": no_starvation,
        "affinity_hit_rate_on": aff_on["prefix_hit_rate"],
        "affinity_hit_rate_off": aff_off["prefix_hit_rate"],
        "metric_qos_admitted_total": m_qos_admitted,
        "router_stats": stats,
        "clean": clean,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny models, few iters (CPU)")
    ap.add_argument("--iters", type=int, default=50)
    ap.add_argument("--tokens", type=int, default=64)
    ap.add_argument("--concurrent", action="store_true",
                    help="concurrent-client engine vs sequential "
                         "generate() throughput comparison")
    ap.add_argument("--tier", action="store_true",
                    help="multi-replica tier chaos bench: closed-loop "
                         "clients through replica kills + one rolling "
                         "restart; gates are p99 + error-rate")
    ap.add_argument("--paged", action="store_true",
                    help="paged vs slot-row engine at equal cache "
                         "bytes: concurrency-at-fixed-memory + "
                         "prefix-hit admission latency (ISSUE 9)")
    ap.add_argument("--spec", action="store_true",
                    help="speculative (n-gram drafter) vs plain decode "
                         "on a repetitive-text mix: accepted-tokens/"
                         "tick + ms/token, identity and zero-recompile "
                         "asserted (ISSUE 13)")
    ap.add_argument("--recovery", action="store_true",
                    help="work-conserving recovery chaos gates "
                         "(ISSUE 15): kill-mid-decode -> journaled "
                         "resume bitwise-identical with zero client "
                         "errors + prefix-hit re-prefill + zero new "
                         "compiles; replica_stall -> hedged decode "
                         "bounds p99, loser cancelled, leak-free")
    ap.add_argument("--stream", action="store_true",
                    help="streaming QoS front chaos gates (ISSUE 16): "
                         "NDJSON client streams ride kill/stall/"
                         "rolling-restart bitwise-identically (zero "
                         "loss, zero dups, zero new compiles, bounded "
                         "p99 ITL); overload degrades truthfully per "
                         "class; prefix-affinity beats load-only "
                         "routing on shared-prefix hit rate")
    ap.add_argument("--clients", type=int, default=8,
                    help="closed-loop clients (engine slots follow)")
    ap.add_argument("--per-client", type=int, default=None,
                    help="requests per client (default 6; smoke 3)")
    args = ap.parse_args()

    if args.recovery:
        rec = bench_recovery(args.smoke)
        rec.update({
            "metric": "serving_recovery_chaos",
            "value": rec["p99_ms_worst_phase"],
            "unit": "p99_ms_worst_phase",
            "smoke": bool(args.smoke),
        })
        print(json.dumps(rec))
        # bitwise failover / zero-client-errors / prefix-hit /
        # zero-new-compiles / hedge-bounded-p99 / leak-free are all
        # ASSERTED (rec["clean"]), not just reported
        return 0 if rec["clean"] else 1

    if args.stream:
        rec = bench_stream(args.smoke)
        rec.update({
            "metric": "serving_stream_qos_chaos",
            "value": rec["p99_itl_ms_worst_phase"],
            "unit": "p99_itl_ms_worst_chaos_phase",
            "smoke": bool(args.smoke),
        })
        print(json.dumps(rec))
        # bitwise splice / zero-loss-zero-dup / zero-new-compiles /
        # hedge-bounded stall / per-class truthful shed / no
        # starvation / affinity-beats-load-only are ASSERTED
        # (rec["clean"]), not just reported
        return 0 if rec["clean"] else 1

    if args.spec:
        rec = bench_spec(args.smoke)
        import jax
        rec.update({
            "metric": "serving_speculative_decode",
            "value": rec["accepted_tokens_per_tick"],
            "unit": "accepted_tokens_per_verify_tick",
            "device_kind": getattr(jax.devices()[0], "device_kind",
                                   "cpu"),
            "smoke": bool(args.smoke),
        })
        print(json.dumps(rec))
        # identity / zero-recompile / multi-token-tick / ms-per-token
        # win are ASSERTED (rec["clean"]), not just reported
        return 0 if rec["clean"] else 1

    if args.paged:
        rec = bench_paged(args.smoke)
        import jax
        rec.update({
            "metric": "serving_paged_concurrency_at_fixed_memory",
            "value": rec["concurrency_gain_prefix_free"],
            "unit": "x_concurrent_vs_slot_rows_equal_bytes",
            "device_kind": getattr(jax.devices()[0], "device_kind",
                                   "cpu"),
            "smoke": bool(args.smoke),
        })
        print(json.dumps(rec))
        # strictly-more-concurrency and hit-cuts-admission are
        # ASSERTED (rec["clean"]), not just reported
        return 0 if rec["clean"] else 1

    if args.tier:
        per_client = (args.per_client if args.per_client is not None
                      else (3 if args.smoke else 5))
        clients = min(args.clients, 4) if args.smoke else args.clients
        rec = bench_tier(args.smoke, clients, per_client)
        rec.update({
            "metric": "serving_tier_chaos",
            "value": rec["p99_ms_worst_phase"],
            "unit": "p99_ms_worst_phase",
            "smoke": bool(args.smoke),
        })
        print(json.dumps(rec))
        # the zero-hangs / zero-resets / token-identity / store-warm
        # claims are ASSERTED, not just reported
        return 0 if rec["clean"] else 1

    if args.concurrent:
        if args.clients < 2:
            ap.error("--clients must be >= 2 (engine slots follow the "
                     "client count and the engine needs >= 2 slots)")
        per_client = (args.per_client if args.per_client is not None
                      else (3 if args.smoke else 6))
        rec = bench_concurrent(args.smoke, args.clients, per_client)
        import jax
        rec.update({
            "metric": "serving_concurrent_throughput",
            "value": rec["speedup"],
            "unit": "x_vs_sequential_generate",
            "device_kind": getattr(jax.devices()[0], "device_kind",
                                   "cpu"),
            "smoke": bool(args.smoke),
        })
        print(json.dumps(rec))
        return 0

    iters = 8 if args.smoke else args.iters
    tokens = 8 if args.smoke else args.tokens
    p50, p90, p99 = bench_encoder(args.smoke, iters)
    decode = bench_decode(args.smoke, tokens)
    ms_tok = decode["bfloat16"]
    ms_tok_i8 = decode["int8"]

    import jax
    print(json.dumps({
        "metric": "ernie3_serving_latency",
        "value": round(p50, 2),
        "unit": "ms_p50_batch1_seq128",
        "p50_ms": round(p50, 2),
        "p90_ms": round(p90, 2),
        "p99_ms": round(p99, 2),
        "decode_ms_per_token": round(ms_tok, 2),
        "decode_ms_per_token_int8_cache": round(ms_tok_i8, 2),
        "iters": iters,
        "device_kind": getattr(jax.devices()[0], "device_kind", "cpu"),
        "smoke": bool(args.smoke),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
