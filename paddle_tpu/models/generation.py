"""Autoregressive generation with static-shape KV caches.

Serving-path role parity: the reference's inference transformer stack
(fused_multi_transformer_op.cu CacheKV decode, §2.4) and the beam/sampling
decode helpers. TPU-native design: ONE jitted prefill program + ONE jitted
whole-decode program — the entire token loop is a `lax.scan` inside the
compiled program (eos masking included), so generating N tokens costs a
single host->device dispatch instead of N round-trips (a per-step host
sync would otherwise dominate decode).
Caches are donated so XLA updates them in place in HBM.

Works with any model exposing:
  forward(ids, caches, pos) -> (logits, caches)   (cache-threaded forward)
  new_cache(batch, max_len, dtype) -> caches
where `caches` is ANY pytree the model's forward threads through —
per-layer [(k, v), ...] for unrolled stacks, a stacked
(k_stack, v_stack) pair for scan_layers models. GPTForCausalLM and
LlamaForCausalLM both do; `model.generate(...)` delegates here.
"""
from __future__ import annotations

import os
import threading
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.tensor import Tensor
from ..jit.functional import functional_call, raw_state

__all__ = ["generate", "new_kv_caches", "new_paged_kv_caches",
           "build_generate_programs"]


def _prog_cache_size() -> int:
    """Bounded-LRU size for the per-model compiled-program cache. A
    long-lived server with drifting prompt lengths must not pin
    executables forever; bucket prompt lengths server-side (the
    continuous-batching engine does) to hit this cache reliably."""
    try:
        return max(1, int(os.environ.get("PADDLE_TPU_GEN_PROG_CACHE",
                                         16)))
    except ValueError:
        return 16


def _prog_cache_for(model):
    """(OrderedDict, Lock) compiled-program LRU attached to `model`.

    The lock matters: server threads call generate() concurrently, and
    OrderedDict get/move_to_end/popitem are NOT safe under concurrent
    mutation (observed: KeyError out of move_to_end racing popitem).
    Creation is double-checked so two first-callers agree on one dict.
    """
    cache = getattr(model, "_gen_prog_cache", None)
    lock = getattr(model, "_gen_prog_lock", None)
    if cache is None or lock is None:
        with _PROG_CACHE_INIT_LOCK:
            cache = getattr(model, "_gen_prog_cache", None)
            lock = getattr(model, "_gen_prog_lock", None)
            if cache is None:
                import collections
                cache = collections.OrderedDict()
                object.__setattr__(model, "_gen_prog_cache", cache)
            if lock is None:
                lock = threading.Lock()
                object.__setattr__(model, "_gen_prog_lock", lock)
    return cache, lock


_PROG_CACHE_INIT_LOCK = threading.Lock()


def new_kv_caches(num_layers, batch, max_len, kv_heads, head_dim, dtype,
                  scan_layers):
    """KV caches for generate(): per-layer [(k, v), ...] (unrolled) or a
    stacked (k_stack, v_stack) pair (scan_layers models). dtype "int8"
    selects the dynamically-quantized cache (quantized_kv_cache) — the
    TPU-native role of the reference's int8 CacheKV
    (fused_multi_transformer_op.cu)."""
    from ..nn.functional.flash_attention import quantized_kv_cache
    if dtype == "int8":
        def one():
            return quantized_kv_cache(batch, max_len, kv_heads, head_dim)
    else:
        def one():
            return jnp.zeros((batch, max_len, kv_heads, head_dim), dtype)
    if scan_layers:
        def stack(trees):
            return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                          *trees)
        return (stack([one() for _ in range(num_layers)]),
                stack([one() for _ in range(num_layers)]))
    return [(one(), one()) for _ in range(num_layers)]


def new_paged_kv_caches(num_layers, num_pages, page_size, kv_heads,
                        head_dim, dtype, scan_layers):
    """Paged KV caches for the continuous-batching engine's paged mode:
    per-layer (k_pool, v_pool) page pools (flash_attention.paged_kv_cache
    dicts, dtype "int8" selects the quantized pool), or — scan_layers —
    ONE stacked (k_stack, v_stack) pair whose leaves carry a leading
    layer axis. A physical page id means "that page in EVERY layer's
    pool" — one shared block table indexes them all, so host-side page
    accounting stays per-request, not per-layer. Block tables are
    per-request state the engine attaches per program call; they are NOT
    part of this pytree."""
    from ..nn.functional.flash_attention import paged_kv_cache
    if scan_layers:
        # Stacked pools [L, num_pages, page_size, ...]:
        # ScannedStack.forward_cached slices every cache-dict leaf along
        # the layer axis inside its scan, so each layer's body sees an
        # ordinary per-layer pool dict. The shared block table has no
        # layer axis of its own — the ENGINE broadcasts its per-program
        # metadata (bt/live/wlen) with a leading L before attaching
        # (ISSUE 20, the PR 9 follow-up), which gives the scan a
        # per-layer [B, PM] slice of one host-side table; paging.py's
        # allocator/trie/COW accounting stays per-request, layer-blind.
        def stack(trees):
            return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                          *trees)
        return (stack([paged_kv_cache(num_pages, page_size, kv_heads,
                                      head_dim, dtype)
                       for _ in range(num_layers)]),
                stack([paged_kv_cache(num_pages, page_size, kv_heads,
                                      head_dim, dtype)
                       for _ in range(num_layers)]))
    return [(paged_kv_cache(num_pages, page_size, kv_heads, head_dim,
                            dtype),
             paged_kv_cache(num_pages, page_size, kv_heads, head_dim,
                            dtype))
            for _ in range(num_layers)]


def _select_token(logits, key, do_sample, temperature, top_k, top_p):
    """logits [B, V] -> token [B] (greedy or filtered sampling)."""
    if not do_sample:
        return jnp.argmax(logits, axis=-1)
    logits = logits.astype(jnp.float32) / jnp.maximum(temperature, 1e-6)
    if top_k:
        kth = jnp.sort(logits, axis=-1)[:, -int(top_k)][:, None]
        logits = jnp.where(logits < kth, -1e30, logits)
    if top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # smallest logit value still inside the nucleus
        keep = cum - probs < top_p
        cutoff = jnp.min(jnp.where(keep, sorted_logits, jnp.inf), axis=-1,
                         keepdims=True)
        logits = jnp.where(logits < cutoff, -1e30, logits)
    return jax.random.categorical(key, logits, axis=-1)


def build_generate_programs(model, P: int, max_new_tokens: int,
                            eos: Optional[int], do_sample: bool,
                            temperature: float, top_k: int,
                            top_p: float):
    """(prefill, decode_all) jitted programs for one generate()
    configuration — the exact programs generate() caches per prog_key.

    Module-level (not a generate() closure) so the static analyzer
    (paddle_tpu.analysis) lints the REAL serving programs by lowering
    them directly, without executing a token. Signatures:

        prefill(params, buffers, ids[B,P]i64, caches, key) -> (tok, caches)
        decode_all(params, buffers, tok0[B]i, caches, key) -> (toks, caches)

    Caches are donated (argument 3 of both).
    """
    def prefill(params, buffers, ids, caches, key):
        (logits, caches), _ = functional_call(
            model, params, buffers, ids, caches,
            jnp.int32(0), training=False)
        nxt = _select_token(logits[:, -1, :], key, do_sample,
                            temperature, top_k, top_p)
        return nxt, caches

    def decode_all(params, buffers, tok0, caches, key):
        """The whole token loop as one scan: emits tok0 then
        max_new_tokens-1 successors, eos rows frozen."""
        fin0 = (tok0 == eos) if eos is not None \
            else jnp.zeros(tok0.shape, bool)

        def body(carry, i):
            tok, caches, fin, key = carry
            key, sub = jax.random.split(key)
            (logits, caches), _ = functional_call(
                model, params, buffers, tok[:, None], caches,
                (P + i).astype(jnp.int32), training=False)
            nxt = _select_token(logits[:, -1, :], sub, do_sample,
                                temperature, top_k, top_p)
            if eos is not None:
                nxt = jnp.where(fin, eos, nxt)
                fin = fin | (nxt == eos)
            return (nxt, caches, fin, key), nxt

        (_, caches, _, _), toks = lax.scan(
            body, (tok0, caches, fin0, key),
            jnp.arange(max_new_tokens - 1))
        # [B, max_new_tokens]: the prefill token + scan
        # emissions (int32 in-program; the host widens to int64).
        # caches are returned solely so the donated inputs have
        # an output to alias — callers discard them.
        out = jnp.concatenate(
            [tok0[:, None], toks.T.astype(tok0.dtype)], axis=1)
        return out, caches

    return (jax.jit(prefill, donate_argnums=(3,)),
            jax.jit(decode_all, donate_argnums=(3,)))


def generate(model, input_ids, max_new_tokens: int = 32,
             do_sample: bool = False, temperature: float = 1.0,
             top_k: int = 0, top_p: float = 1.0,
             eos_token_id: Optional[int] = None, seed: int = 0,
             cache_dtype: str = "bfloat16"):
    """Generate up to `max_new_tokens` continuations of `input_ids`.

    Returns an int64 numpy array [B, prompt_len + max_new_tokens]; after a
    row hits eos_token_id it is padded with eos.
    """
    ids = np.asarray(input_ids.numpy() if isinstance(input_ids, Tensor)
                     else input_ids).astype(np.int64)
    if ids.ndim == 1:
        ids = ids[None]
    B, P = ids.shape
    if max_new_tokens <= 0:
        return ids
    total = P + max_new_tokens
    max_len = getattr(getattr(model, "cfg", None), "max_seq_len", None)
    if max_len is not None and total > max_len:
        # position embeddings/RoPE are undefined past max_seq_len; the
        # OOB lookup would silently clamp, not error
        raise ValueError(
            f"prompt ({P}) + max_new_tokens ({max_new_tokens}) = {total} "
            f"exceeds the model's max_seq_len {max_len}")
    was_training = model.training
    model.eval()
    try:
        params, buffers = raw_state(model)
        caches = model.new_cache(B, total, cache_dtype)

        # One compiled prefill + decode program per (shape, sampling)
        # configuration, cached ON the model — a fresh jax.jit per
        # generate() call would re-trace and re-compile every request
        # (measured: ~1.5 s per call at GPT-tiny scale, dwarfing the
        # actual decode), which is fatal for the serving path.
        prog_cache, prog_lock = _prog_cache_for(model)
        # greedy ignores the sampling knobs — don't let them split the key
        sampling = ((float(temperature), int(top_k), float(top_p))
                    if do_sample else None)
        # total already encodes max_new_tokens (= P + new); eos is baked
        # into the compiled scan, so it distinguishes programs too
        prog_key = (B, P, total, str(cache_dtype), sampling,
                    None if eos_token_id is None else int(eos_token_id))
        eos = eos_token_id
        with prog_lock:
            progs = prog_cache.get(prog_key)
            if progs is not None:
                prog_cache.move_to_end(prog_key)
        if progs is None:
            progs = build_generate_programs(
                model, P, max_new_tokens, eos, do_sample, temperature,
                top_k, top_p)
            # jit wrapper creation is cheap (compilation happens at the
            # first call, outside the lock); insertion races resolve in
            # favor of the first writer so every thread runs ONE program
            with prog_lock:
                existing = prog_cache.get(prog_key)
                if existing is not None:
                    progs = existing
                    prog_cache.move_to_end(prog_key)
                else:
                    prog_cache[prog_key] = progs
                    while len(prog_cache) > _prog_cache_size():
                        prog_cache.popitem(last=False)
        prefill_c, decode_c = progs

        key = jax.random.PRNGKey(seed)
        key, sub = jax.random.split(key)
        tok, caches = prefill_c(params, buffers, ids, caches, sub)
        if max_new_tokens == 1:
            new = np.asarray(tok)[:, None]
        else:
            toks, _ = decode_c(params, buffers, tok, caches, key)
            new = np.asarray(toks)
        return np.concatenate([ids, new.astype(np.int64)], axis=1)
    finally:
        if was_training:
            model.train()
