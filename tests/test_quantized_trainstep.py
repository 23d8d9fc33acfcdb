"""Quantized + overlapped ZeRO collectives in ParallelTrainStep
(ISSUE 17): the fp32 knob stays bitwise with the implicit-GSPMD
baseline (per-step AND scan_steps), bf16/int8 trajectories stay inside
the documented drift bounds, knob flips never recompile an
already-built program, the stage-3 chunked weight-gather leaves its
optimization_barrier chain in the lowered text (and an interleaved —
not front-loaded — compiled schedule), optimizer math stays sharded
(no replicated update, arXiv 2004.13336), and the ctor rejects the
geometries the quantized path cannot serve.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F

# loss drift allowed against fp32 collectives, per wire precision
DRIFT_BOUNDS = {"bf16": 5e-3, "int8": 2e-2}


@pytest.fixture(autouse=True)
def fresh_mesh(monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_COMM_PRECISION", raising=False)
    dist.set_mesh(None)
    yield
    dist.set_mesh(None)


def _mesh22():
    import jax
    dist.init_mesh({"dp": 2, "sharding": 2}, devices=jax.devices()[:4])


def _net():
    paddle.seed(7)
    return nn.Sequential(nn.Linear(16, 32), nn.GELU(), nn.Linear(32, 16))


def _opt(m):
    return paddle.optimizer.AdamW(learning_rate=0.05,
                                  parameters=m.parameters())


def _loss(o, y):
    return F.mse_loss(o, y)


def _batch():
    rng = np.random.RandomState(0)
    return rng.randn(8, 16).astype("float32")


def _make_step(prec, stage=3):
    paddle.seed(5)
    m = _net()
    kw = {} if prec is None else {"comm_precision": prec}
    return dist.ParallelTrainStep(m, _loss, _opt(m), zero_stage=stage,
                                  **kw)


def _run(prec, steps=4):
    step = _make_step(prec)
    x = _batch()
    return [float(step(x, x)) for _ in range(steps)], step


def _params_bitwise(a, b):
    return all(np.array_equal(np.asarray(a.params[n]),
                              np.asarray(b.params[n])) for n in a.params)


def _maxrel(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(a), 1e-8)))


# ---------------------------------------------------------------------------
# fp32 knob: bitwise with the implicit-GSPMD baseline
# ---------------------------------------------------------------------------

def test_fp32_knob_bitwise_per_step():
    """comm_precision='fp32' must keep the implicit GSPMD collectives:
    identical losses AND identical final params, to the last ulp."""
    _mesh22()
    base_losses, base = _run(None)
    knob_losses, knob = _run("fp32")
    assert np.array_equal(np.asarray(base_losses),
                          np.asarray(knob_losses))
    assert _params_bitwise(base, knob)


def test_fp32_knob_bitwise_scan():
    """The fused K-step window at comm_precision='fp32' reproduces the
    default per-step trajectory bitwise (the scan path threads the knob
    through _scan_progs)."""
    _mesh22()
    seq_losses, seq = _run(None, steps=4)
    scan_step = _make_step("fp32")
    x = _batch()
    stacked = np.stack([x] * 4)
    scan_losses = np.asarray(
        scan_step.scan_steps(4, stacked, stacked).value).tolist()
    assert np.array_equal(np.asarray(seq_losses),
                          np.asarray(scan_losses))
    assert _params_bitwise(seq, scan_step)


# ---------------------------------------------------------------------------
# bf16 / int8: bounded trajectory drift
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("prec", ["bf16", "int8"])
def test_quantized_trajectory_drift_bounded(prec):
    _mesh22()
    ref_losses, _ = _run(None)
    q_losses, _ = _run(prec)
    drift = _maxrel(ref_losses, q_losses)
    assert drift <= DRIFT_BOUNDS[prec], (prec, drift, ref_losses,
                                         q_losses)
    # and the run is actually training, not collapsing to noise
    assert q_losses[-1] < q_losses[0]


# ---------------------------------------------------------------------------
# knob flips: programs cached per precision, zero recompiles
# ---------------------------------------------------------------------------

def test_zero_recompile_knob_flips():
    _mesh22()
    step = _make_step("int8")
    x = _batch()
    step(x, x)
    assert step._trace_count == 1
    step.set_comm_precision("bf16")
    step(x, x)
    assert step._trace_count == 2          # first bf16 step compiles
    step.set_comm_precision("int8")
    step(x, x)
    assert step._trace_count == 2          # cached: NO retrace
    step.set_comm_precision("bf16")
    step(x, x)
    assert step._trace_count == 2          # cached both ways


# ---------------------------------------------------------------------------
# stage-3 chunked gather/compute overlap: lowered chain + schedule
# ---------------------------------------------------------------------------

def test_stage3_gather_chain_and_schedule():
    """GPT-tiny (real per-layer structure) at int8: the lowered text
    carries the optimization_barrier gather chain (one link per
    gathered leaf group), the compiled schedule interleaves gathers
    with compute rather than front-loading them, and the differ
    refuses unscheduled text. The fp32 lowering of the same step has
    no chain (lower only — no second compile)."""
    import jax.numpy as jnp
    from paddle_tpu.analysis.collective_schedule import (
        gather_chain_links, gather_overlap_report, schedule_events)
    from paddle_tpu.compilation.sites import (_gpt_tiny_model,
                                              _train_step_parts)

    def lower(prec):
        dist.set_mesh(None)
        _mesh22()
        model = _gpt_tiny_model()
        loss_fn, opt, _rng = _train_step_parts(model)
        step = dist.ParallelTrainStep(model, loss_fn, opt, zero_stage=3,
                                      comm_precision=prec)
        ids = np.zeros((4, 32), np.int64)
        step._build((ids, ids))
        args = (step.params, step.buffers, step.opt_state,
                jnp.asarray(1e-3, jnp.float32),
                jnp.asarray(1, jnp.float32),
                _rng.default_generator().fold_in(1), ids, ids)
        return step._jitted.lower(*args)

    lowered = lower("int8")
    links = gather_chain_links(lowered.as_text())
    assert links > 0, "no gather chain in the int8 stage-3 lowering"
    # the differ must refuse pre-scheduling text outright
    with pytest.raises(ValueError):
        schedule_events(lowered.as_text())
    rep = gather_overlap_report(lowered.compile().as_text())
    assert rep["n_gathers"] >= 1 and rep["n_compute"] >= 1
    assert not rep["front_loaded"], rep
    assert rep["interleaved_gaps"] >= 1, rep
    # fp32 keeps the implicit GSPMD gathers: no explicit chain
    assert gather_chain_links(lower("fp32").as_text()) == 0


# ---------------------------------------------------------------------------
# no replicated optimizer math (arXiv 2004.13336)
# ---------------------------------------------------------------------------

def test_optimizer_state_stays_sharded():
    """Every non-scalar optimizer slot (and every stage-3 param) lives
    1/G-sharded over the zero axis — a device holding a full copy would
    mean the update math was replicated."""
    import jax
    _mesh22()
    step = _make_step("int8")
    x = _batch()
    step(x, x)                              # one real update
    G = 2                                   # zero axis: sharding=2
    for name, arr in step.params.items():
        assert arr.addressable_shards[0].data.size * G == arr.size, name
    checked = 0
    for pname, slots in step.opt_state.items():
        for leaf in jax.tree_util.tree_leaves(slots):
            if leaf.ndim >= 1 and leaf.size > 1 \
                    and leaf.shape[0] % G == 0:
                assert (leaf.addressable_shards[0].data.size * G
                        == leaf.size), pname
                checked += 1
    assert checked >= 4                     # both weights + both biases


# ---------------------------------------------------------------------------
# geometry validation
# ---------------------------------------------------------------------------

def test_ctor_and_knob_validation():
    import jax
    _mesh22()
    with pytest.raises(ValueError):
        _make_step("fp8")                   # unknown precision
    with pytest.raises(ValueError):
        _make_step("int8", stage=1)         # no grad RS to quantize
    step = _make_step("fp32")
    with pytest.raises(ValueError):
        step.set_comm_precision("fp16")
    # hybrid mesh: quantized fwd/bwd cannot carry mp collectives
    dist.set_mesh(None)
    dist.init_mesh({"dp": 2, "mp": 2}, devices=jax.devices()[:4])
    with pytest.raises(ValueError):
        _make_step("int8", stage=2)
