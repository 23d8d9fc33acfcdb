"""chip_smoke.py — the bring-up of what no benchmark cell covers yet.

    python chip_smoke.py              # one TPU chip
    python chip_smoke.py --multichip  # four chips: the sharded paths only

The trainer is not here: every cell of BENCHMARK.json proves more of it
in its set-up than a smoke could (finite falling loss, one trace, no
later compile, the Pallas backend, and the step against a plain float32
reference). For the trainer run
``python3 benchmark/run.py --workload <cell> --seed <n> --seconds 10``.

What this script drives, through the entry points a user calls, at the
full width of the models the repo supports (depth may be cut; weights
are random, made from a seed), checking every result by the repo's own
means. Phases, cheap first, one printed line each as they go:

  device   jax.devices()[0].platform must be "tpu" — there is no CPU mode
  kernels  every Pallas kernel reachable from a public entry point,
           compiled (never interpreted) and run at real widths against
           its XLA reference under a stated tolerance
  serve    Router + one replica child at 1.3B widths, concurrent
           POST /generate (one streamed), /healthz truth, then a
           model.generate() reference once the tier has stopped
  --multichip
           the tp=4 engine and ZeRO-3, each against its one-chip
           reference, on four chips

The last stdout line is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``;
any failure exits non-zero and prints no such line.

ONE process owns the chip at a time: this parent imports neither jax nor
paddle_tpu; each phase is a child (``--phase``, internal) that asserts the
platform before anything else and exits before the next one starts. The
serve phase's driver child hosts the Router without ever initialising a
jax backend (asserted), so the replica child is the chip's only owner.

Sizes are arguments of the phase functions with the real sizes as their
defaults; the command line has no size option. A rehearsal imports this
module, sets ``PLATFORM = "cpu"`` and calls the phase functions at tiny
sizes (tests/test_chip_smoke.py does) — the script itself never does.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 0
# the platform every phase asserts first. Only a rehearsal (an importing
# script) ever changes it; on "cpu" the Pallas kernels run interpreted
# and the attention dispatch is expected to be XLA.
PLATFORM = "tpu"

# serve depth: the replica holds f32 weights (ReplicaSpec builds the model
# as GPTConfig does) and the scanned decode tick double-buffers its page
# pool carry — at the published 24 layers the chip's compiler counts
# arguments 8.48 G + temporaries 6.75 G of 15.75 G, under 1 G to spare
# beside whatever else the process holds, so depth is cut to 16.
SERVE = dict(
    model=dict(kind="gpt", vocab_size=50304, hidden_size=2048,
               num_layers=16, num_heads=16, max_seq_len=2048,
               scan_layers=True),
    engine=dict(slots=8, max_len=2048, cache_dtype="bfloat16",
                paged=True),
    prompt_lens=(32, 200, 512, 1024), new_tokens=64)
KERNEL_SIZES = dict(
    flash=((8, 1024, 12, 64), (4, 2048, 16, 128)),     # (b, s, h, d)
    # (b, s, h, kv heads, d, window): Trinity-Mini's window and full layers
    flash_gqa=((2, 8192, 32, 4, 128, 2048), (2, 8192, 32, 4, 128, None)),
    flash_block=((1, 12, 1024, 64), (1, 16, 2048, 128)),  # (b, h, s, d)
    cache=(8, 2048, 16, 128),                          # (B, L, nkv, hd)
    pool=(1024, 16, 16, 128),                          # (NP, PS, nkv, hd)
    ce=(8192, 50304),                                  # (N, V)
    mega=((8, 2048, 16, 128), (8, 1024, 12, 64)))
MULTI_TP = dict(
    cfg=dict(vocab_size=50304, hidden_size=2048, num_layers=4,
             num_heads=16, max_seq_len=2048),
    engine=dict(slots=4, max_len=512, cache_dtype="bfloat16"),
    prompt_lens=(32, 100, 256), new_tokens=32)
# the loss is chunked here: un-chunked, the [tokens, 50304] head/loss
# region alone costs minutes of compile (327 s at 8192 tokens; PERF.md
# section 6, PR 24/27), paid twice and on four chips' clock
MULTI_ZERO = dict(
    cfg=dict(vocab_size=50304, hidden_size=768, num_layers=2,
             num_heads=12, max_seq_len=1024, fused_loss_chunk=1024),
    batch=8, seq=1024, steps=3)


def say(phase: str, **facts) -> None:
    print(f"[{phase}] " + json.dumps(facts, default=str), flush=True)


class SmokeFailure(AssertionError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def require_device() -> dict:
    """First act of every process that may touch the chip (and the
    moment the compile counters start listening)."""
    import jax
    from paddle_tpu.compilation import counters  # noqa: F401
    dev = jax.devices()[0]
    check(dev.platform == PLATFORM,
          f"jax.devices()[0].platform is {dev.platform!r}, need "
          f"{PLATFORM!r} — chip_smoke.py has no CPU mode")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def cache_facts() -> dict:
    """Where the persistent compile cache is, and what it gave so far."""
    import jax
    from paddle_tpu import _paths
    from paddle_tpu.compilation import counters
    return {"cache_dir": _paths.jax_cache_dir(),
            "cache_dir_configured": jax.config.jax_compilation_cache_dir,
            "from_env": bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
            "persistent_cache_hits": counters.persistent_cache_hits(),
            "xla_compiles": counters.xla_compiles(),
            "compile_secs": round(counters.compile_secs(), 1)}


def _nerr(got, ref) -> float:
    """max |got - ref| / max |ref|, in f32."""
    import jax.numpy as jnp
    got = jnp.asarray(got, jnp.float32)
    ref = jnp.asarray(ref, jnp.float32)
    return float(jnp.max(jnp.abs(got - ref))
                 / jnp.maximum(jnp.max(jnp.abs(ref)), 1e-30))


# ------------------------------------------------------------------ kernels

# bf16 tolerances (normalised max error against an f32 reference computed
# from the same bf16 inputs): the kernels accumulate in f32 and round
# probabilities and outputs to bf16 (2^-8 relative each), gradients pass
# through two such roundings.
TOL_FWD, TOL_BWD, TOL_F32 = 3e-2, 6e-2, 1e-4


def _ref_attention(q, k, v, causal, scale):
    """Plain f32 softmax attention, [b, h, s, d] layout -> (out, lse)."""
    import jax
    import jax.numpy as jnp
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    lg = jnp.einsum("bhqd,bhkd->bhqk", qf, kf,
                    precision="highest") * scale
    if causal:
        s_q, s_k = lg.shape[-2:]
        lg = jnp.where(jnp.tril(jnp.ones((s_q, s_k), bool), s_k - s_q),
                       lg, -1e30)
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", jnp.exp(lg - lse[..., None]), vf,
                     precision="highest")
    return out, lse


def _ref_attention_grouped(q, k, v, window, scale):
    """Plain f32 causal attention with the mask written out, [b, s, h, d]
    q on [b, s, kv, d] keys (query head h reads key/value head
    h // group), optionally in a window of ``window`` keys: one batch row
    and key/value head at a time, recomputed in the backward pass, so
    that the scores of the real sizes fit."""
    import jax
    import jax.numpy as jnp
    b, s, h, d = q.shape
    kv = k.shape[2]
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    mask = (j <= i) if window is None else (j <= i) & (i - j < window)

    @jax.checkpoint
    def one(qg, kg, vg):            # [group, s, d], [s, d], [s, d]
        lg = jnp.einsum("gqd,kd->gqk", qg, kg, precision="highest") * scale
        p = jax.nn.softmax(jnp.where(mask, lg, -1e30), axis=-1)
        return jnp.einsum("gqk,kd->gqd", p, vg, precision="highest")

    qf = jnp.moveaxis(q.astype(jnp.float32), 1, 2).reshape(
        b * kv, h // kv, s, d)
    kf, vf = (jnp.moveaxis(x.astype(jnp.float32), 1, 2).reshape(b * kv, s, d)
              for x in (k, v))
    out = jax.lax.map(lambda a: one(*a), (qf, kf, vf))
    return jnp.moveaxis(out.reshape(b, h, s, d), 1, 2)


def phase_kernels(sizes=KERNEL_SIZES) -> None:
    dev = require_device()
    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu.nn.functional as F
    from paddle_tpu import kernels as K
    fa = importlib.import_module(
        "paddle_tpu.nn.functional.flash_attention")
    interpret = PLATFORM != "tpu"
    rs = np.random.RandomState(SEED)

    def rnd(shape, dtype=jnp.bfloat16, scale=1.0):
        return jnp.asarray(rs.standard_normal(shape) * scale, dtype)

    # F.flash_attention: the library kernel behind the public functional
    for (b, s, h, d) in sizes["flash"]:
        q, k, v, w = (rnd((b, s, h, d)) for _ in range(4))
        scale = 1.0 / d ** 0.5

        def loss(q, k, v):
            out = F.flash_attention(q, k, v, causal=True)[0].value
            return (out.astype(jnp.float32)
                    * w.astype(jnp.float32)).sum(), out

        def ref_loss(q, k, v):
            out, _ = _ref_attention(*(jnp.swapaxes(x, 1, 2)
                                      for x in (q, k, v)), True, scale)
            out = jnp.swapaxes(out, 1, 2)
            return (out * w.astype(jnp.float32)).sum(), out

        t0 = time.perf_counter()
        (_, out), g = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        jax.block_until_ready(g)
        secs = time.perf_counter() - t0
        dispatch = fa.last_attention_dispatch()
        backend = dispatch.get("backend")
        (_, rout), rg = jax.jit(jax.value_and_grad(
            ref_loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        e_f = _nerr(out, rout)
        e_b = max(_nerr(a, r) for a, r in zip(g, rg))
        say("kernels", kernel="F.flash_attention", shape=(b, s, h, d),
            backend=backend, library_kernel=dispatch.get("kernel"),
            layout=dispatch.get("layout"),
            blocks=dispatch.get("blocks"), err_fwd=e_f, err_bwd=e_b,
            tol=(TOL_FWD, TOL_BWD), compile_and_run_s=round(secs, 1))
        check(backend == ("pallas" if PLATFORM == "tpu" else "xla"),
              f"flash_attention dispatched to {backend!r}")
        check(e_f <= TOL_FWD and e_b <= TOL_BWD,
              f"F.flash_attention {b, s, h, d}: {e_f}, {e_b}")

    # the same functional with a causal window and grouped key/value
    # heads, against the plain mask
    for (b, s, h, kv, d, window) in sizes.get("flash_gqa", ()):
        q, w = rnd((b, s, h, d)), rnd((b, s, h, d))
        k, v = rnd((b, s, kv, d)), rnd((b, s, kv, d))

        def loss(q, k, v):
            out = F.flash_attention(q, k, v, causal=True,
                                    window=window)[0].value
            return (out.astype(jnp.float32)
                    * w.astype(jnp.float32)).sum(), out

        def ref_loss(q, k, v):
            out = _ref_attention_grouped(q, k, v, window, 1.0 / d ** 0.5)
            return (out * w.astype(jnp.float32)).sum(), out

        (_, out), g = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        dispatch = fa.last_attention_dispatch()
        (_, rout), rg = jax.jit(jax.value_and_grad(
            ref_loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        e_f = _nerr(out, rout)
        e_b = max(_nerr(a, r) for a, r in zip(g, rg))
        say("kernels", kernel="F.flash_attention", shape=(b, s, h, d),
            kv_heads=dispatch.get("kv_heads"), window=dispatch.get("window"),
            backend=dispatch.get("backend"),
            library_kernel=dispatch.get("kernel"),
            blocks=dispatch.get("blocks"), err_fwd=e_f, err_bwd=e_b,
            tol=(TOL_FWD, TOL_BWD))
        check(dispatch.get("backend")
              == ("pallas" if PLATFORM == "tpu" else "xla")
              and dispatch.get("kv_heads") == kv
              and dispatch.get("window") == window,
              f"windowed grouped flash_attention dispatched as {dispatch}")
        check(e_f <= TOL_FWD and e_b <= TOL_BWD,
              f"F.flash_attention {b, s, h, kv, d, window}: {e_f}, {e_b}")

    # kernels/flash_block.py: the ring/Ulysses block kernel with LSE
    for (b, h, s, d) in sizes["flash_block"]:
        q, k, v, w = (rnd((b, h, s, d)) for _ in range(4))
        scale = 1.0 / d ** 0.5

        def loss(q, k, v):
            o, lse = K.flash_block_attention(q, k, v, 0, 0, True, scale,
                                             128, 128, interpret)
            return ((o.astype(jnp.float32) * w.astype(jnp.float32)).sum()
                    + lse.sum()), (o, lse)

        def ref_loss(q, k, v):
            o, lse = _ref_attention(q, k, v, True, scale)
            return (o * w.astype(jnp.float32)).sum() + lse.sum(), (o, lse)

        (_, (o, lse)), g = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        (_, (ro, rlse)), rg = jax.jit(jax.value_and_grad(
            ref_loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        e_f = max(_nerr(o, ro), _nerr(lse, rlse))
        e_b = max(_nerr(a, r) for a, r in zip(g, rg))
        say("kernels", kernel="flash_block_attention", shape=(b, h, s, d),
            err_fwd=e_f, err_bwd=e_b, tol=(TOL_FWD, TOL_BWD))
        check(e_f <= TOL_FWD and e_b <= TOL_BWD,
              f"flash_block {b, h, s, d}: {e_f}, {e_b}")

    # fused cache writes: exact (a blend moves bits, computes nothing)
    B, L, nkv, hd = sizes["cache"]
    pos = jnp.asarray(rs.randint(0, L, B), jnp.int32)
    pos = pos.at[0].set(0).at[1].set(L - 1)
    for label, cache, rows in (
            ("bf16", rnd((B, L, nkv, hd)), rnd((B, 1, nkv, hd))),
            ("int8", jnp.asarray(rs.randint(-127, 128, (B, L, nkv, hd)),
                                 jnp.int8),
             jnp.asarray(rs.randint(-127, 128, (B, 1, nkv, hd)),
                         jnp.int8)),
            ("scale", rnd((B, L, nkv), jnp.float32),
             rnd((B, 1, nkv), jnp.float32))):
        ref = cache.at[jnp.arange(B), pos].set(rows[:, 0])
        got = jax.jit(lambda c, r, p: K.fused_slot_write(
            c, r, p, interpret=interpret, gridded=True))(cache, rows, pos)
        same = bool(jnp.array_equal(got, ref))
        say("kernels", kernel="fused_slot_write", variant=label,
            shape=cache.shape, exact=same)
        check(same, f"fused_slot_write {label} differs from .at[].set")
    NP, PS, nkv, hd = sizes["pool"]
    n = B
    pool, rows = rnd((NP, PS, nkv, hd)), rnd((n, nkv, hd))
    phys = jnp.asarray(rs.choice(NP, n, replace=False), jnp.int32)
    off = jnp.asarray(rs.randint(0, PS, n), jnp.int32)
    valid = jnp.ones((n,), jnp.int32).at[n - 1].set(0)
    ref = pool.at[phys[:-1], off[:-1]].set(rows[:-1])
    got = jax.jit(lambda *a: K.fused_paged_write(
        *a, interpret=interpret, gridded=True))(pool, rows, phys, off, valid)
    same = bool(jnp.array_equal(got, ref))
    say("kernels", kernel="fused_paged_write", shape=pool.shape, exact=same)
    check(same, "fused_paged_write differs from .at[].set")

    # fused cross-entropy: bf16 logits in, f32 math
    N, V = sizes["ce"]
    lg = rnd((N, V), scale=2.0)
    labels = jnp.asarray(rs.randint(0, V, N), jnp.int32)
    gup = rnd((N,), jnp.float32)
    per, lse = jax.jit(lambda a, b: K.ce_fwd(a, b, interpret=interpret))(
        lg, labels)
    dlg = jax.jit(lambda *a: K.ce_bwd(*a, interpret=interpret))(
        lg, labels, lse, gup)

    @jax.jit
    def ce_ref(lg, labels, gup):
        lf = lg.astype(jnp.float32)
        rl = jax.scipy.special.logsumexp(lf, axis=-1)
        gold = jnp.take_along_axis(lf, labels[:, None], 1)[:, 0]
        p = jnp.exp(lf - rl[:, None])
        onehot = jax.nn.one_hot(labels, lf.shape[-1], dtype=jnp.float32)
        return rl - gold, rl, (p - onehot) * gup[:, None]
    rper, rlse, rdlg = ce_ref(lg, labels, gup)
    e_f = max(_nerr(per, rper), _nerr(lse, rlse))
    e_b = _nerr(dlg, rdlg)
    say("kernels", kernel="ce_fwd/ce_bwd", shape=(N, V), err_fwd=e_f,
        err_bwd=e_b, tol=(TOL_F32, TOL_FWD))
    check(e_f <= TOL_F32 and e_b <= TOL_FWD, f"fused CE: {e_f}, {e_b}")

    # mega decode step, against the unfused cached_attention chain
    for (B, L, nh, hd) in sizes["mega"]:
        q, k, v = (rnd((B, 1, nh, hd)) for _ in range(3))
        kc, vc = rnd((B, L, nh, hd)), rnd((B, L, nh, hd))
        pos = jnp.asarray(rs.randint(1, L, B), jnp.int32)
        pos = pos.at[0].set(0).at[1].set(L - 1)
        ctx, kc2, vc2 = jax.jit(lambda *a: K.mega_decode_step(
            *a, interpret=interpret, gridded=True))(q, k, v, kc, vc, pos)

        def unfused(q, k, v, kc, vc, pos):
            c, a, b = fa.cached_attention(q, k, v, kc, vc, pos)
            return c.value, a.value, b.value
        rctx, rkc, rvc = jax.jit(unfused)(q, k, v, kc, vc, pos)
        e = _nerr(ctx, rctx)
        same = bool(jnp.array_equal(kc2, rkc) & jnp.array_equal(vc2, rvc))
        say("kernels", kernel="mega_decode_step", shape=(B, L, nh, hd),
            err_ctx=e, tol=TOL_FWD, caches_exact=same)
        check(e <= TOL_FWD and same, f"mega_decode {B, L, nh, hd}: {e}")
    say("kernels", ok=True, device=dev, **cache_facts())


# -------------------------------------------------------------------- serve

def _post(url: str, payload: dict, timeout: float = 600.0):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, r.read()


def _get(url: str, timeout: float = 30.0) -> dict:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read())


def _prompts(prompt_lens, vocab):
    import numpy as np
    rs = np.random.RandomState(SEED + 1)
    return [rs.randint(0, vocab, n).tolist() for n in prompt_lens]


def _generated(body: dict, prompt: list) -> list:
    """New tokens of a /generate answer (serve returns prompt + new)."""
    return body["tokens"][len(prompt):]


def phase_serve(out_path: str, spec=SERVE, ready_timeout: float = 900.0):
    """The serve DRIVER: hosts the Router, never touches a jax backend;
    the replica child (``python -m paddle_tpu.inference.router
    --replica-child``) is the chip's one owner."""
    import jax
    from paddle_tpu.inference.router import (ReplicaSpec, Router,
                                             single_device_child_env)
    rspec = ReplicaSpec(spec["model"], spec["engine"], warmup=True,
                        seed=SEED, env=single_device_child_env(PLATFORM))
    prompts = _prompts(spec["prompt_lens"], spec["model"]["vocab_size"])
    new = spec["new_tokens"]
    t0 = time.perf_counter()
    router = Router(rspec, replicas=1, poll_s=0.25,
                    deadline_s=600.0).start()
    try:
        router.wait_ready(1, timeout=ready_timeout)
        ready_s = time.perf_counter() - t0
        base = f"http://{router.host}:{router.port}"
        rep = router.replicas()[0]
        rep_base = f"http://127.0.0.1:{rep['port']}"
        hz0 = _get(rep_base + "/healthz")
        check(hz0["device"]["platform"] == PLATFORM,
              f"replica /healthz platform {hz0['device']}")

        results = [None] * len(prompts)

        def client(i):
            payload = {"input_ids": prompts[i], "max_new_tokens": new}
            if i == 1:
                payload["stream"] = True
            status, raw = _post(base + "/generate", payload)
            if i == 1:      # NDJSON: {"t": [...]} blocks, one {"done"}
                lines = [json.loads(x) for x in raw.splitlines() if x]
                check(any("done" in x for x in lines)
                      and not any("err" in x for x in lines),
                      f"stream ended badly: {lines[-1]}")
                toks = [t for x in lines for t in x.get("t", [])]
            else:
                toks = _generated(json.loads(raw), prompts[i])
            results[i] = (status, toks)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=900)
        for i, r in enumerate(results):
            check(r is not None, f"request {i} did not finish")
            check(r[0] == 200 and len(r[1]) == new,
                  f"request {i}: HTTP {r[0]}, {len(r[1])}/{new} tokens")
        # the same prompt again gives the same tokens
        status, raw = _post(base + "/generate",
                            {"input_ids": prompts[0],
                             "max_new_tokens": new})
        again = _generated(json.loads(raw), prompts[0])
        check(status == 200 and again == results[0][1],
              "the same prompt gave different tokens the second time")
        hz1 = _get(rep_base + "/healthz")
        n0 = hz0["engine"]["compiled_programs"]
        n1 = hz1["engine"]["compiled_programs"]
        say("serve", replicas=1, ready_s=round(ready_s, 1),
            widths=spec["model"], engine=spec["engine"],
            answers=[{"prompt_len": len(p), "http": r[0],
                      "new_tokens": len(r[1]), "stream": i == 1}
                     for i, (p, r) in enumerate(zip(prompts, results))],
            repeat_identical=True, device=hz1["device"],
            compiled_program_count=(n0, n1),
            compilation=hz1.get("compilation"))
        check(n0 == n1 and n0 > 0,
              f"compiled_program_count moved under traffic: {n0}->{n1}")
    finally:
        router.stop()
    check(not jax._src.xla_bridge._backends,
          "the Router's process initialised a jax backend: "
          f"{list(jax._src.xla_bridge._backends)}")
    with open(out_path, "w") as f:
        json.dump({"prompt": prompts[0], "tokens": results[0][1]}, f)
    say("serve", tier_stopped=True, parent_backends=[])


def phase_serve_ref(in_path: str, spec=SERVE, tol: float = 0.05) -> None:
    """After the tier has stopped: model.generate() on the same seeded
    model must give the replica's tokens. In bf16 a near-tie between the
    top two logits may flip a late token (the engine prefills in padded
    buckets, generate() does not), after which the sequences differ by
    construction — so the rule is: identical, OR at the first divergence
    the replica's token is within ``tol`` logits of the reference's best
    (checked with one teacher-forced forward pass)."""
    dev = require_device()
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.framework import random as _rng
    from paddle_tpu.inference.router import _build_model
    with open(in_path) as f:
        rec = json.load(f)
    prompt, served = rec["prompt"], rec["tokens"]
    _rng.seed(SEED)                       # the replica child's seeding
    model = _build_model(spec["model"])
    out = model.generate(np.asarray([prompt], np.int64),
                         max_new_tokens=len(served),
                         cache_dtype=spec["engine"]["cache_dtype"])
    ref = np.asarray(getattr(out, "value", out))[0, len(prompt):].tolist()
    div = next((i for i, (a, b) in enumerate(zip(served, ref)) if a != b),
               None)
    gap = None
    if div is not None:
        model.eval()
        ids = jnp.asarray([prompt + ref[:div]], jnp.int32)
        logits = model(ids)
        logits = getattr(logits, "value", logits)[0, -1].astype(
            jnp.float32)
        gap = float(logits[ref[div]] - logits[served[div]])
    say("serve_ref", prompt_len=len(prompt), tokens=len(served),
        first_divergence=div, logit_gap_at_divergence=gap, tol=tol,
        device=dev, **cache_facts())
    check(div is None or abs(gap) <= tol,
          f"replica and model.generate() diverge at token {div} with a "
          f"logit gap of {gap} (> {tol})")


# ---------------------------------------------------------------- multichip

def phase_multichip_tp(spec=MULTI_TP, tp: int = 4, tol: float = 0.05):
    dev = require_device()
    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.inference import ContinuousBatchingEngine
    from paddle_tpu.jit.functional import functional_call
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    check(dev["count"] >= tp, f"need {tp} devices, have {dev['count']}")
    cfg = GPTConfig(**spec["cfg"])
    paddle.seed(SEED)
    model = GPTForCausalLM(cfg)
    model.eval()
    prompts = _prompts(spec["prompt_lens"], cfg.vocab_size)
    tokens, logits = {}, {}
    for label, kw in (("tp%d" % tp, {"tp": tp}), ("one_chip", {})):
        eng = ContinuousBatchingEngine(model, **spec["engine"], **kw)
        try:
            futs = [eng.submit(np.asarray(p, np.int64),
                               max_new_tokens=spec["new_tokens"])
                    for p in prompts]
            tokens[label] = [
                np.asarray(f.result(timeout=900)).tolist()[len(p):]
                for f, p in zip(futs, prompts)]
            stats = eng.stats()
            # first-step logits of prompt 0 through the engine's own
            # (sharded / single-chip) parameters
            ids = jnp.asarray([prompts[0]], jnp.int32)

            def fwd(p, b, ids):
                out, _ = functional_call(model, p, b, ids)
                return getattr(out, "value", out)[0, -1]
            if eng._tp is not None:
                with eng._tp.activate():
                    lg = jax.jit(fwd)(eng._params, eng._buffers, ids)
            else:
                lg = jax.jit(fwd)(eng._params, eng._buffers, ids)
            logits[label] = np.asarray(lg, np.float32)
        finally:
            eng.stop()
        if "tp" in kw:
            mesh = stats["mesh"]
            say("multichip", path="tp_engine", mesh=mesh,
                compiled_programs=stats["compiled_programs"])
            check(len(set(mesh["devices"])) == tp
                  and all(PLATFORM in d.lower() for d in mesh["devices"]),
                  f"mesh devices {mesh['devices']}")
    a, b = tokens["tp%d" % tp], tokens["one_chip"]
    divs = [next((i for i, (x, y) in enumerate(zip(s, t)) if x != y), None)
            for s, t in zip(a, b)]
    err = float(np.max(np.abs(logits["tp%d" % tp] - logits["one_chip"])))
    say("multichip", path="tp_engine", widths=spec["cfg"],
        first_step_logits_max_abs_diff=err, tol=tol,
        first_divergence_per_prompt=divs, new_tokens=spec["new_tokens"],
        device=dev)
    check(err <= tol, f"tp={tp} first-step logits differ by {err}")
    check(all(d is None or d > 0 for d in divs),
          f"tp={tp} greedy tokens diverge at the first token: {divs}")


def phase_multichip_zero(spec=MULTI_ZERO, degrees=None, tol: float = 2e-2):
    dev = require_device()
    import jax
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    degrees = degrees or {"dp": 2, "sharding": 2}
    n_dev = int(np.prod(list(degrees.values())))
    check(dev["count"] >= n_dev, f"need {n_dev} devices")
    cfg = GPTConfig(**spec["cfg"])
    ids_np = np.random.RandomState(SEED).randint(
        0, cfg.vocab_size, (spec["batch"], spec["seq"])).astype("int64")

    def build():
        paddle.seed(SEED)
        model = GPTForCausalLM(cfg)
        model.bfloat16()
        opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                     multi_precision=True,
                                     parameters=model.parameters())
        return model, opt

    dist.set_mesh(None)
    model, opt = build()
    one = TrainStep(model, model.make_loss_fn(), opt)
    ids = paddle.to_tensor(ids_np)
    ref = [float(one(ids, ids)) for _ in range(spec["steps"])]
    del one, model, opt

    dist.init_mesh(degrees)
    try:
        model, opt = build()
        step = dist.ParallelTrainStep(model, model.make_loss_fn(), opt,
                                      zero_stage=3)
        ids = paddle.to_tensor(ids_np)
        got = [float(step(ids, ids)) for _ in range(spec["steps"])]
        name, big = max(step.params.items(), key=lambda kv: kv[1].size)
        leaves = [x for x in jax.tree_util.tree_leaves(step.opt_state)
                  if getattr(x, "shape", None) == big.shape]
        check(leaves, f"no optimizer-state leaf shaped like {name}")
        facts = {}
        for label, arr in (("param", big), ("opt_state", leaves[0])):
            shards = arr.addressable_shards
            devs = sorted({str(s.device) for s in shards})
            frac = [s.data.size / arr.size for s in shards]
            facts[label] = {"devices": devs, "shard_fraction": frac}
            # ZeRO shards over the "sharding" axis and replicates over
            # "dp" (the reference's sharding-group-inside-dp layout):
            # every device holds 1/sharding of the tensor
            check(len(devs) == n_dev and all(
                abs(f - 1.0 / degrees["sharding"]) < 1e-9 for f in frac),
                f"{label} {name}: shards on {devs} at {frac}")
    finally:
        dist.set_mesh(None)
    rel = max(abs(a - b) / abs(b) for a, b in zip(got, ref))
    say("multichip", path="zero3", degrees=degrees, widths=spec["cfg"],
        losses=got, one_chip_losses=ref, max_rel_diff=rel, tol=tol,
        largest_param=name, shape=big.shape, **facts, device=dev)
    check(rel <= tol, f"ZeRO-3 losses differ from one chip by {rel}")
    check(got[-1] < got[0], f"ZeRO-3 loss did not fall: {got}")


# ------------------------------------------------------------------- parent

def _run_phase(args: list, result_from: str | None = None) -> dict | None:
    """Run one phase child to its end (one chip owner at a time), echo
    its lines, and fail the run on a non-zero exit."""
    proc = subprocess.Popen(
        [sys.executable, "-u", os.path.abspath(__file__)] + args,
        cwd=HERE, stdout=subprocess.PIPE, text=True)
    last = None
    try:
        for line in proc.stdout:
            sys.stdout.write(line)
            sys.stdout.flush()
            if result_from and line.startswith(f"[{result_from}] "):
                last = json.loads(line.split("] ", 1)[1])
        rc = proc.wait()
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    if rc != 0:
        print(f"[smoke] phase {args} failed with exit code {rc}",
              flush=True)
        sys.exit(rc or 1)
    return last


def _child(args) -> int:
    sys.path.insert(0, HERE)
    phases = {
        "kernels": phase_kernels,
        "serve": lambda: phase_serve(args.file),
        "serve_ref": lambda: phase_serve_ref(args.file),
        "multichip_tp": phase_multichip_tp,
        "multichip_zero": phase_multichip_zero,
    }
    if args.phase not in phases:
        raise SystemExit(f"unknown phase {args.phase!r}")
    phases[args.phase]()
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--multichip", action="store_true",
                    help="four chips: the sharded paths and what each is "
                         "compared with, no other phase")
    ap.add_argument("--phase", help=argparse.SUPPRESS)   # internal
    ap.add_argument("--file", help=argparse.SUPPRESS)    # internal
    args = ap.parse_args()
    if args.phase:
        return _child(args)

    t0 = time.time()
    print(f"[smoke] python {sys.version.split()[0]}, checkout {HERE}, "
          f"JAX_COMPILATION_CACHE_DIR="
          f"{os.environ.get('JAX_COMPILATION_CACHE_DIR')!r}", flush=True)
    if args.multichip:
        facts = _run_phase(["--phase", "multichip_tp"], "multichip")
        facts = _run_phase(["--phase", "multichip_zero"], "multichip")
    else:
        facts = _run_phase(["--phase", "kernels"], "kernels")
        with tempfile.TemporaryDirectory() as tmp:
            handoff = os.path.join(tmp, "served.json")
            _run_phase(["--phase", "serve", "--file", handoff])
            _run_phase(["--phase", "serve_ref", "--file", handoff])
    device = facts["device"]
    print(f"[smoke] all phases passed in {time.time() - t0:.0f} s",
          flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
