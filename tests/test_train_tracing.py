"""The training program names itself (ISSUE 28): a `jax.named_scope` on
every layer, loss and optimizer that reaches the compiled text; the rule
that reads a scope path; `TrainStep.op_scopes()`; `by_scope`; the
`train.step*` spans that `TrainStep` records about itself; one span
primitive; compile seconds by phase in the ring. All CPU, tiny sizes."""
import contextlib
import gc
import os
import re
import weakref

import numpy as np
import pytest

import jax
import paddle_tpu as paddle
from paddle_tpu import obs
from paddle_tpu.analysis import runtime_profile as rp
from paddle_tpu.analysis.hlo_cost import parse_hlo_module
from paddle_tpu.compilation import counters
from paddle_tpu.framework import random as _rng
from paddle_tpu.jit import TrainStep, last_step_program
from paddle_tpu.jit import training
from paddle_tpu.models import GPTConfig, GPTForCausalLM

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tiny_step(scan: bool, accumulate_steps: int = 1):
    paddle.seed(11)
    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                    num_heads=2, max_seq_len=32, recompute=scan,
                    scan_layers=scan, fused_loss_chunk=16)
    model = GPTForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    step = TrainStep(model, model.make_loss_fn(), opt,
                     accumulate_steps=accumulate_steps)
    ids = paddle.to_tensor(np.random.default_rng(0).integers(
        0, 128, (2, 32)).astype(np.int32))
    return step, ids


@pytest.fixture(scope="module", params=[True, False],
                ids=["scanned_recomputed", "unrolled"])
def ran(request):
    """(scan?, a step that ran twice, its batch, its table)."""
    step, ids = _tiny_step(request.param)
    step(ids, ids)
    step(ids, ids)
    return request.param, step, ids, step.op_scopes()


def _since(mark: int) -> list:
    """The ring's events appended after ``obs.recorder.appended`` read
    ``mark``."""
    evs = obs.recorder.events()
    return evs[max(0, len(evs) - (obs.recorder.appended - mark)):]


def _runs_on_its_own(text: str):
    """The instructions a device trace would show an event for: those
    of the entry computation and of loop and branch bodies (not inside
    a fusion or a reducer) that compute or move something."""
    module = parse_hlo_module(text)
    inner = {i.attrs.get(k) for c in module.computations.values()
             for i in c.instrs for k in ("calls", "to_apply")}
    idle = {"parameter", "constant", "tuple", "get-tuple-element",
            "bitcast", "after-all"}
    return [i for c in module.computations.values()
            if c.name not in inner for i in c.instrs
            if i.opcode not in idle]


# ---------------------------------------------------------------- scopes
def test_scopes_reach_the_compiled_text(ran):
    scan, step, ids, table = ran
    text = step._step_program.hlo_text()
    ops = _runs_on_its_own(text)
    # of the instructions that came from an operation of the program
    # (the rest the compiler made from constants and loop counters)
    read = [rp.read_scope(table[i.name]) for i in ops if table[i.name]]
    assert len(read) > 0.85 * len(ops) > 50, (len(read), len(ops))
    unscoped = sum(r["region"] == "unscoped" for r in read)
    assert unscoped < 0.05 * len(read), (unscoped, len(read))
    passes = {r["pass"] for r in read}
    assert {"forward", "backward", "update"} <= passes
    # the chunked head+loss recomputes its logits in both; the blocks
    # only where the configuration asks for it
    block_passes = {r["pass"] for r in read
                    if "block" in r["scope"].split("/")}
    assert ("recompute" in block_passes) == scan
    regions = {r["region"] for r in read}
    # a layer's region is the name it was registered under, no list
    assert {"attn", "qkv", "fc_in", "ln", "ln_f", "word_embeddings",
            "head_loss", "optimizer"} <= regions, regions
    scopes = {r["scope"] for r in read}
    want = "gptforcausallm/gpt/blocks/block/attn/qkv" if scan \
        else "gptforcausallm/gpt/block/attn/qkv"
    assert want in scopes
    if scan:
        assert "scan_carry" in regions
    # a name holds no id, counter or address: it is part of a compiled
    # program's cache key and must be the same in every process
    assert not any(re.search(r"0x[0-9a-f]{6,}|\d{5,}", s) for s in scopes)


def test_op_scopes_names_the_compiled_instructions(ran):
    _, step, ids, table = ran
    text = step._step_program.hlo_text()
    names = set(re.findall(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = ", text, re.M))
    assert set(table) == names
    assert sum(1 for p in table.values() if p) > len(names) // 2


def test_op_scopes_moves_nothing():
    step, ids = _tiny_step(False)
    with pytest.raises(RuntimeError, match="no per-step program"):
        step.op_scopes()
    first = float(step(ids, ids))
    def state():
        return (step.step_count, step.update_count, step._trace_count,
                step.optimizer.get_lr(),
                np.asarray(jax.random.key_data(_rng.get_rng_state())).tolist())
    before = state()
    appended = obs.recorder.appended
    with counters.CompileTracker() as t:    # the record's: no compile
        table = step.op_scopes()
        assert step.op_scopes() == table
    assert t.backend_compiles == 0 and t.traces == 0
    assert state() == before
    assert not [e for e in _since(appended)
                if e["name"].startswith("train.")]
    # and the step goes on as a twin that was never asked does
    twin, _ = _tiny_step(False)
    assert float(twin(ids, ids)) == first
    assert float(twin(ids, ids)) == float(step(ids, ids))


def test_accumulating_step_has_its_scope_and_program():
    step, ids = _tiny_step(False, accumulate_steps=2)
    mark = obs.recorder.appended
    step(ids, ids)                      # micro-step: accumulate
    assert step._step_program.program == "accumulate"
    regions = {rp.read_scope(p)["region"]
               for p in step.op_scopes().values() if p}
    assert "grad_accumulate" in regions and "optimizer" not in regions
    step(ids, ids)                      # update
    assert step._step_program.program == "step"
    assert step._step_program is last_step_program()
    regions = {rp.read_scope(p)["region"]
               for p in step.op_scopes().values() if p}
    assert {"grad_accumulate", "optimizer"} <= regions
    progs = [e["args"]["program"] for e in _since(mark)
             if e["name"] == "train.step"]
    assert progs == ["accumulate", "step"]


# ------------------------------------------------- the program's record
@contextlib.contextmanager
def _compile_events():
    """[(event, seconds)] of jax's trace, lowering and backend-compile
    events fired inside the block."""
    got, on = [], [True]

    def listen(event, secs, **kw):
        if on[0] and event in counters._SPAN_OF:
            got.append((counters._SPAN_OF[event], secs))
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield got
    finally:
        on[0] = False


def _args_like(step, ids):
    """A call's arguments again, at the avals the step ran at."""
    import jax.numpy as jnp
    return (step.params, step.buffers, step.opt_state,
            jnp.asarray(1e-3, jnp.float32), jnp.asarray(1.0, jnp.float32),
            _rng.default_generator().fold_in(1), ids.value, ids.value)


def test_the_first_step_publishes_its_program_and_later_steps_nothing():
    step, ids = _tiny_step(False)
    assert step._step_program is None
    step(ids, ids)
    rec = last_step_program()
    assert rec is step._step_program
    assert (rec.program, rec.trainer, rec.traces) == ("step", "TrainStep", 1)
    assert 0 < rec.publish_s < 5
    for _ in range(9):
        step(ids, ids)
    assert last_step_program() is rec and step._step_program is rec
    assert step._trace_count == step._published_at == 1
    # a second shape compiles a second program: its record replaces the
    # first, which goes on answering whoever kept it
    table = rec.op_scopes()
    half = paddle.to_tensor(np.asarray(ids.value)[:1])
    step(half, half)
    again = last_step_program()
    assert again is not rec and again is step._step_program
    assert again.traces == step._trace_count == 2
    assert again.op_scopes() != table and rec.op_scopes() == table


def test_publishing_traces_lowers_and_compiles_nothing():
    step, ids = _tiny_step(False)
    step(ids, ids)
    first = last_step_program()
    step(ids, ids)
    args = _args_like(step, ids)
    count = step._trace_count
    with counters.CompileTracker() as t, _compile_events() as evs:
        training.publish_step_program(step, "step", step._jitted, args)
    assert last_step_program() is not first     # published again
    assert step._trace_count == count           # the step's Python did not run
    assert t.backend_compiles == 0 and t.persistent_cache_hits == 0
    # jit's cache lookup fires its trace event, a hit; nothing lowers
    assert [n for n, _ in evs if n != "compile.trace"] == []
    assert len(evs) <= 1
    assert last_step_program().op_scopes() == first.op_scopes()
    # and a later step pays an integer comparison: no event at all
    with _compile_events() as evs:
        step(ids, ids)
    assert evs == [] and last_step_program().traces == count


def test_the_record_outlives_its_trainer():
    step, ids = _tiny_step(False)
    step(ids, ids)
    table = step.op_scopes()
    dead, model = weakref.ref(step), weakref.ref(step.model)
    arrays = [weakref.ref(v) for v in step.params.values()]
    del step
    gc.collect()
    assert dead() is None and model() is None
    assert all(a() is None for a in arrays)
    rec = last_step_program()
    assert rec.op_scopes() == table
    assert "op_name=" in rec.hlo_text()
    # host data alone: no attribute of the record is a device array, a
    # trainer or an executable
    held = [getattr(rec, n) for n in rec.__slots__]
    assert all(isinstance(v, (str, int, float, dict, type(None)))
               for v in held), held


@pytest.mark.parametrize("kind", ["accumulate", "scan", "scan_accumulate"])
def test_every_per_step_program_publishes_alike(kind):
    step, ids = _tiny_step(False, accumulate_steps=1 if kind == "scan"
                           else 2)
    if kind == "accumulate":
        step(ids, ids)
        assert last_step_program().program == "accumulate"
        acc = last_step_program()
        step(ids, ids)
        rec = last_step_program()
        assert rec is not acc and rec.program == "step" and rec.traces == 2
    else:
        window = paddle.to_tensor(np.stack([np.asarray(ids.value)] * 2))
        with _compile_events() as evs:
            step.scan_steps(2, window, window)
        rec = last_step_program()
        assert rec.program == "scan" and rec.traces == 1
        assert sum(n == "compile.backend" for n, _ in evs) >= 1
        with _compile_events() as evs:
            step.scan_steps(2, window, window)
        assert evs == [] and last_step_program() is rec
    assert rec is step._step_program and rec.trainer == "TrainStep"
    regions = {rp.read_scope(p)["region"] for p in rec.op_scopes().values()
               if p}
    assert "optimizer" in regions and "head_loss" in regions
    assert ("grad_accumulate" in regions) == (kind != "scan")


def test_a_failed_publish_fails_no_step():
    step, ids = _tiny_step(False)

    class NoLower:
        def __init__(self, fn):
            self.fn = fn

        def __call__(self, *a):
            return self.fn(*a)

        def lower(self, *a):
            raise ValueError("not here")
    step._build()
    step._jitted = NoLower(step._jitted)
    before = last_step_program()
    with pytest.warns(UserWarning, match="record was not made"):
        loss = float(step(ids, ids))
    assert np.isfinite(loss) and last_step_program() is before
    with pytest.raises(RuntimeError, match="no per-step program"):
        step.op_scopes()
    float(step(ids, ids))               # asked once, not on every step
    assert step._published_at == step._trace_count == 1


def test_a_lookup_that_misses_compiles_nothing_for_the_record():
    """The record takes the executable jit already holds and never makes
    one: where the look-up misses, it warns, and the miss shows."""
    step, ids = _tiny_step(False)
    step(ids, ids)
    rec = last_step_program()
    # (a) abstract values jit never saw: the step's Python runs again
    half = paddle.to_tensor(np.asarray(ids.value)[:1])
    args = _args_like(step, half)
    with _compile_events() as evs, \
            pytest.warns(UserWarning, match="traced again"):
        training.publish_step_program(step, "step", step._jitted, args)
    assert "compile.backend" not in [n for n, _ in evs]
    assert last_step_program() is rec
    assert step._trace_count == step._published_at == 2     # not hidden
    # (b) a lowering nobody compiled (what a store hit leaves behind)
    fresh, _ = _tiny_step(False)
    fresh._build()
    args = _args_like(fresh, ids)
    fresh._jitted.lower(*args)
    with _compile_events() as evs, \
            pytest.warns(UserWarning, match="holds no executable"):
        training.publish_step_program(fresh, "step", fresh._jitted, args)
    assert "compile.backend" not in [n for n, _ in evs]
    assert fresh._step_program is None and last_step_program() is rec
    assert fresh._published_at == fresh._trace_count == 1


_STORE_WARM = """
import json, sys
import numpy as np, jax
import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.compilation import counters
from paddle_tpu.compilation.store import ExecutableStore
from paddle_tpu.jit import TrainStep, last_step_program

store = ExecutableStore(root=sys.argv[1], enabled=True)
events = []
jax.monitoring.register_event_duration_secs_listener(
    lambda event, secs, **kw: events.append(event))
x = np.ones((8, 16), np.float32)
y = np.ones((8, 4), np.float32)
out = []
for _ in range(2):          # the second trainer finds the first's entries
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 4))
    step = TrainStep(net, lambda o, t: ((o - t) ** 2).mean(),
                     paddle.optimizer.AdamW(learning_rate=1e-3,
                                            parameters=net.parameters()))
    del events[:]
    with counters.CompileTracker() as warm:
        recs = step.warm(x, y, scan_k=2, store=store)
    warm_events = list(events)
    rec = last_step_program()
    del events[:]
    with counters.CompileTracker() as first:
        loss = float(step(paddle.to_tensor(x), paddle.to_tensor(y)))
        step.scan_steps(2, paddle.to_tensor(np.stack([x, x])),
                        paddle.to_tensor(np.stack([y, y])))
    out.append({
        "sources": [r["source"] for r in recs], "loss": loss,
        "warm_backend": warm.backend_compiles,
        "warm_events": warm_events, "first_events": list(events),
        "first": [first.backend_compiles, first.persistent_cache_hits,
                  first.traces],
        "same_record": last_step_program() is rec,
        "record": [rec.program, rec.trainer, rec.traces],
        "published_at": step._published_at, "traces": step._trace_count,
        "optimizer_ops": sum("optimizer" in p
                             for p in rec.op_scopes().values())})
print(json.dumps(out))
"""


def test_a_store_warm_first_step_compiles_and_loads_nothing(tmp_path):
    """`warm()` makes the record of each program from the executable it
    compiled or LOADED; the first step and the first window then fire no
    trace, lowering, compile or cache load, for the record or otherwise.
    On one CPU device in a process of its own: the virtual mesh of these
    tests cannot run a loaded executable."""
    import json
    import subprocess
    import sys
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    done = subprocess.run(
        [sys.executable, "-c", _STORE_WARM, str(tmp_path / "exec")],
        env=env, capture_output=True, text=True, timeout=240)
    assert done.returncode == 0, done.stderr[-2000:]
    cold, hit = json.loads(done.stdout.strip().splitlines()[-1])
    assert cold["sources"] == ["compiled", "compiled"]
    assert hit["sources"] == ["store", "store"]
    for got in (cold, hit):
        assert got["first_events"] == [] and got["first"] == [0, 0, 0]
        assert got["same_record"]
        assert got["record"] == ["scan", "TrainStep", 2]
        assert got["published_at"] == got["traces"] == 2
        assert got["optimizer_ops"] > 0
    # a hit: `aot_compile` traces and lowers for its key, nothing more
    assert hit["warm_backend"] == 0
    assert not [e for e in hit["warm_events"] if "backend_compile" in e]
    assert hit["loss"] == cold["loss"]


# ------------------------------------------------------------- the rule
J = "jit(full_step)/"
RULE_CASES = {
    "forward_layer": (
        J + "jvp(gptforcausallm)/gpt/block_7/attn/qkv/dot_general",
        "forward", "gptforcausallm/gpt/block/attn/qkv", "qkv"),
    "backward_marked_by_transpose": (
        J + "transpose(jvp(gptforcausallm))/gpt/block_11/mlp/fc_in/"
        "dot_general", "backward", "gptforcausallm/gpt/block/mlp/fc_in",
        "fc_in"),
    "recompute_inside_a_scanned_block": (
        J + "transpose(jvp(gptforcausallm))/gpt/blocks/while/body/"
        "closed_call/checkpoint/rematted_computation/block/ln_1/"
        "jit(_var)/square", "recompute",
        "gptforcausallm/gpt/blocks/block/ln", "ln"),
    "backward_inside_a_checkpoint": (
        J + "transpose(jvp(gptforcausallm))/gpt/blocks/while/body/"
        "closed_call/checkpoint/block/attn/out_proj/dot_general",
        "backward", "gptforcausallm/gpt/blocks/block/attn/out_proj",
        "out_proj"),
    "first_of_joined_paths": (
        J + "transpose(jvp(gptforcausallm))/gpt/blocks/while/body/"
        "closed_call/checkpoint/rematted_computation/block/attn/vmap()/"
        "transpose;checkpoint/rematted_computation/block/mlp/"
        "vmap(BNTS,BSNH->BTNH)/transpose", "recompute",
        "gptforcausallm/gpt/blocks/block/attn", "attn"),
    "scan_carry_is_the_loops_own": (
        J + "transpose(jvp(gptforcausallm))/gpt/blocks/while/body/"
        "dynamic_update_slice", "backward", "gptforcausallm/gpt/blocks",
        "scan_carry"),
    "the_loop_instruction_itself": (
        J + "jvp(gptforcausallm)/gpt/blocks/while", "forward",
        "gptforcausallm/gpt/blocks", "scan_carry"),
    "residual_add_of_a_block": (
        J + "jvp(gptforcausallm)/gpt/blocks/while/body/closed_call/"
        "block/add", "forward", "gptforcausallm/gpt/blocks/block",
        "block"),
    "final_norm": (
        J + "jvp(gptforcausallm)/gpt/ln_f/mul", "forward",
        "gptforcausallm/gpt/ln_f", "ln_f"),
    "loss_forward_wrapped_by_the_transform": (
        J + "jvp(head_loss)/head_loss/while/body/closed_call/jvp()/"
        "dot_general", "forward", "head_loss/head_loss", "head_loss"),
    "loss_gradient_products_of_the_forward_rule": (
        J + "jvp(head_loss)/head_loss/while/body/closed_call/"
        "transpose(jvp())/dot_general", "backward", "head_loss/head_loss",
        "head_loss"),
    "optimizer_is_the_update": (
        J + "optimizer/mul", "update", "optimizer", "optimizer"),
    "optimizer_inside_a_window": (
        "jit(scan_window)/while/body/optimizer/sqrt", "update",
        "optimizer", "optimizer"),
    "gradient_merge_is_update_too": (
        "jit(apply_step)/grad_accumulate/add", "update",
        "grad_accumulate", "grad_accumulate"),
    "embedding_lookup": (
        J + "jvp(gptforcausallm)/gpt/embeddings/word_embeddings/"
        "jit(_take)/gather", "forward",
        "gptforcausallm/gpt/embeddings/word_embeddings",
        "word_embeddings"),
    "a_kernels_own_long_name_is_no_scope": (
        J + "transpose(jvp(gptforcausallm))/gpt/block_0/attn/"
        "jit(flash_attention)/flash_mha_bwd_dq_block_q_major=512_block_k"
        "=512/pallas_call", "backward", "gptforcausallm/gpt/block/attn",
        "attn"),
    "sequential_children_lose_their_index": (
        "jit(f)/jvp(sequential)/0/dot_general", "forward", "sequential",
        "sequential"),
    "another_models_names_read_alike": (
        "jit(full_step)/transpose(jvp(llamaforcausallm))/llama/layers_3/"
        "self_attn/q_proj/dot_general", "backward",
        "llamaforcausallm/llama/layers/self_attn/q_proj", "q_proj"),
    "a_loss_layer_is_the_trainers_scope": (
        J + "jvp(head_loss)/crossentropyloss/reduce_sum", "forward",
        "head_loss/crossentropyloss", "head_loss"),
    "expert_layer_dispatch_inside_a_branch": (
        J + "jvp(afmoeforcausallm)/model/block_1/mlp/experts/cond/"
        "branch_0_fun/jit(routed_sorted)/dispatch/gather", "forward",
        "afmoeforcausallm/model/block/mlp/experts/dispatch", "dispatch"),
    "expert_layer_gathers_transpose_is_the_backward_scatter_add": (
        J + "transpose(jvp(afmoeforcausallm))/model/jvp(afmoeforcausallm)/"
        "model/checkpoint/block_2/mlp/experts/cond/branch_1_fun/"
        "transpose(jvp(jit(routed_sorted)))/dispatch/scatter-add",
        "backward", "afmoeforcausallm/model/afmoeforcausallm/model/block/"
        "mlp/experts/dispatch", "dispatch"),
    "expert_layer_sort_recomputed": (
        J + "transpose(jvp(afmoeforcausallm))/model/checkpoint/"
        "rematted_computation/block_2/mlp/experts/sort/jit(argsort)/sort",
        "recompute", "afmoeforcausallm/model/block/mlp/experts/sort",
        "sort"),
    "nothing_of_the_program": (
        J + "jit(_where)/select_n", "forward", "", "unscoped"),
    "an_argument": ("params['gpt.ln_f.weight']", "forward", "",
                    "unscoped"),
}


def test_a_named_kernels_scope_is_no_layer():
    """The splash kernel is entered through scopes of its own name, which
    its instruction carries too: with the instruction's name the rule
    reads the layer; without it, or for another instruction, the path."""
    path = (J + "transpose(jvp(gptforcausallm))/gpt/blocks/while/body/"
            "closed_call/checkpoint/block/attn/vmap(jit(_splash_attention))/"
            "splash_mha_dkv_no_residuals/splash_mha_dkv_no_residuals/"
            "pallas_call")
    want = {"pass": "backward", "region": "attn",
            "scope": "gptforcausallm/gpt/blocks/block/attn"}
    assert rp.read_scope(path, "splash_mha_dkv_no_residuals.9") == want
    assert rp.read_scope(path, "%splash_mha_dkv_no_residuals") == want
    assert rp.read_scope(path)["region"] == "splash_mha_dkv_no_residuals"
    assert rp.read_scope(path, "fusion.9")["region"] \
        == "splash_mha_dkv_no_residuals"
    got = rp.by_scope({"%splash_mha_dkv_no_residuals.9": 2.0},
                      {"splash_mha_dkv_no_residuals.9": path})
    assert got["rows"][0]["region"] == "attn"


@pytest.mark.parametrize("case", sorted(RULE_CASES))
def test_the_rule_reads_a_path(case):
    path, pass_, scope, region = RULE_CASES[case]
    assert rp.read_scope(path) == {"pass": pass_, "scope": scope,
                                   "region": region}


HLO_FIXTURE = """HloModule jit_step

%fused_computation (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %c = f32[] constant(2)
  %b = f32[8]{0} broadcast(%c), dimensions={}
  ROOT %m = f32[8]{0} multiply(%p0, %b), metadata={op_name="jit(step)/optimizer/mul"}
}

%late (p0.1: f32[8]) -> f32[8] {
  %p0.1 = f32[8]{0} parameter(0)
  %n = f32[8]{0} negate(%p0.1), metadata={op_name="jit(step)/jvp(net)/mlp/neg"}
  ROOT %cp = f32[8]{0} copy(%n)
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0), metadata={op_name="a"}
  %fusion.1 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/transpose(jvp(net))/attn/mul"}
  %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation
  %fusion.3 = f32[8]{0} fusion(%fusion.2), kind=kLoop, calls=%late
  ROOT %copy.4 = f32[8]{0} copy(%fusion.3)
}
"""


def test_the_table_from_text_and_its_fallbacks():
    table = rp.hlo_op_scopes(HLO_FIXTURE)
    assert set(table) == {"p0", "c", "b", "m", "p0.1", "n", "cp", "a",
                          "fusion.1", "fusion.2", "fusion.3", "copy.4"}
    assert table["fusion.1"].endswith("attn/mul")        # its own
    assert table["fusion.2"] == "jit(step)/optimizer/mul"   # callee's root
    assert table["fusion.3"].endswith("mlp/neg")    # callee's first named
    assert table["copy.4"] == table["fusion.3"]     # its operand's
    assert table["a"] == "a" and table["p0"] == ""


def test_an_instruction_broken_over_lines_keeps_its_path():
    """The splash kernel's custom call prints its ``kernel_metadata`` JSON
    on lines of its own, with ``metadata={op_name=...}`` after them: the
    kernel takes its own path, not its first operand's (a mask table)."""
    text = """HloModule m
ENTRY %main (p0: s8[2]) -> f32[8] {
  %p0 = s8[2]{0} parameter(0)
  %tab = s8[2]{0} copy(%p0), metadata={op_name="jit(step)/blocks/while/body"}
  %kern.1 = f32[8]{0} custom-call(%tab), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={
"xprof_metadata":"{\\"block_q\\": 1024}"
}}, metadata={op_name="jit(step)/jvp(net)/blocks/block/attn/pallas_call"}
  ROOT %after = f32[8]{0} negate(%kern.1), metadata={op_name="jit(step)/jvp(net)/blocks/block/mlp/neg"}
}
"""
    table = rp.hlo_op_scopes(text)
    assert list(table) == ["p0", "tab", "kern.1", "after"]
    assert table["kern.1"].endswith("block/attn/pallas_call")
    assert table["after"].endswith("mlp/neg")


def test_by_scope_on_a_made_up_trace():
    table = rp.hlo_op_scopes(HLO_FIXTURE)
    got = rp.by_scope({"%fusion.1": 3.0, "fusion.2": 1.0, "fusion.3": 4.0,
                       "copy.4": 1.5, "not.in.the.text": 0.5}, table)
    assert got["busy_s"] == pytest.approx(10.0)
    rows = {(r["region"], r["pass"]): (r["seconds"], r["share"])
            for r in got["rows"]}
    assert rows == {("mlp", "forward"): (5.5, 0.55),
                    ("attn", "backward"): (3.0, 0.3),
                    ("optimizer", "update"): (1.0, 0.1),
                    ("unscoped", "forward"): (0.5, 0.05)}
    assert [r["seconds"] for r in got["rows"]] == [5.5, 3.0, 1.0, 0.5]
    assert got["by_pass"]["forward"]["seconds"] == pytest.approx(6.0)
    assert got["by_region"]["attn"]["share"] == pytest.approx(0.3)
    assert got["unscoped_share"] == pytest.approx(0.05)
    assert got["not_in_table_share"] == pytest.approx(0.05)
    assert rp.by_scope({}, table)["rows"] == []


def test_runtime_report_gains_by_scope_from_self_times():
    pid, tid = 7, 3
    events = [
        {"ph": "M", "name": "process_name", "pid": pid,
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "name": "thread_name", "pid": pid, "tid": tid,
         "args": {"name": "XLA Ops"}},
        # a loop holds its body's operations: its self time is 40
        {"ph": "X", "pid": pid, "tid": tid, "name": "fusion.3", "ts": 0,
         "dur": 100},
        {"ph": "X", "pid": pid, "tid": tid, "name": "fusion.1", "ts": 10,
         "dur": 60},
        {"ph": "X", "pid": pid, "tid": tid, "name": "fusion.2", "ts": 200,
         "dur": 100},
    ]
    prof = rp.device_op_times(events)
    assert prof.per_op["fusion.3"] == 100
    assert prof.per_op_self == {"fusion.3": 40, "fusion.1": 60,
                                "fusion.2": 100}
    rec = rp.runtime_report("p", hlo_text=HLO_FIXTURE, events=events,
                            dispatches_profiled=2,
                            op_scopes=rp.hlo_op_scopes(HLO_FIXTURE))
    by = rec["by_scope"]
    assert by["busy_s"] == pytest.approx(100e-6)        # a dispatch
    assert by["by_region"]["optimizer"]["share"] == pytest.approx(0.5)
    assert by["by_pass"]["backward"]["seconds"] == pytest.approx(30e-6)
    assert "by_scope" not in rp.runtime_report(
        "p", hlo_text=HLO_FIXTURE, events=events)


# ---------------------------------------------------------------- spans
def _train_events(mark):
    return [e for e in _since(mark) if e["name"].startswith("train.")]


def test_a_call_records_one_step_span_with_its_children(ran):
    _, step, ids, _ = ran
    mark = obs.recorder.appended
    n0 = step.step_count
    for _ in range(3):
        step(ids, ids)
    evs = _train_events(mark)
    steps = [e for e in evs if e["name"] == "train.step"]
    assert [e["args"]["step"] for e in steps] == [n0 + 1, n0 + 2, n0 + 3]
    assert all(e["args"]["program"] == "step" and e["cat"] == "train"
               for e in steps)
    for parent in steps:
        kids = [e for e in evs if e["name"] != "train.step"
                and e["args"]["step"] == parent["args"]["step"]]
        assert [k["name"] for k in kids] == [
            "train.step.prep", "train.step.enqueue", "train.step.post"]
        # the parent is the enclosing span of that thread
        for k in kids:
            assert k["tid"] == parent["tid"]
            assert parent["ts"] <= k["ts"]
            assert k["ts"] + k["dur"] <= parent["ts"] + parent["dur"] + 1e-3
        assert sum(k["dur"] for k in kids) <= parent["dur"] + 1e-3
    assert len(evs) == 12


def test_a_window_records_itself():
    step, ids = _tiny_step(False)
    stacked = paddle.to_tensor(np.stack([np.asarray(ids.value)] * 4))
    mark = obs.recorder.appended
    step.scan_steps(4, stacked, stacked)
    evs = _train_events(mark)
    assert [e["name"] for e in evs] == [
        "train.step.prep", "train.step.enqueue", "train.step.post",
        "train.window"]
    assert evs[-1]["args"] == {"step": 1, "k": 4}
    assert step.step_count == 4


def test_obs_off_records_nothing_and_allocates_no_span():
    step, ids = _tiny_step(False)
    step(ids, ids)
    obs.set_enabled(False)
    try:
        from paddle_tpu.obs import trace
        assert trace.span("train.step", cat="train", step=1) is trace._NOOP
        before = obs.recorder.appended
        step(ids, ids)
        jax.jit(lambda x: x * 3 + 1)(np.ones(7, np.float32))  # a compile
        assert obs.recorder.appended == before
        # an explicit annotation is its own opt-in
        from paddle_tpu.profiler import RecordEvent
        with RecordEvent("asked_for"):
            pass
        assert obs.recorder.appended == before + 1
        assert obs.recorder.events()[-1]["name"] == "asked_for"
        assert obs.recorder.events()[-1]["cat"] == "profiler"
    finally:
        obs.set_enabled(None)


def test_one_span_primitive():
    """`jax.profiler.TraceAnnotation` is made in obs/trace.py and
    nowhere else under paddle_tpu/, and every span enters one."""
    made = []
    for d, _, files in os.walk(os.path.join(ROOT, "paddle_tpu")):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(d, f)) as fh:
                    if re.search(r"TraceAnnotation\(", fh.read()):
                        made.append(os.path.relpath(os.path.join(d, f),
                                                    ROOT))
    assert made == [os.path.join("paddle_tpu", "obs", "trace.py")]
    from paddle_tpu.obs import trace
    from paddle_tpu.profiler import RecordEvent
    assert issubclass(RecordEvent, trace.Span)
    seen = []

    class Ann:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen.append(("in", self.name))

        def __exit__(self, *exc):
            seen.append(("out", self.name))

    real = jax.profiler.TraceAnnotation
    jax.profiler.TraceAnnotation = Ann
    try:
        with obs.span("serve.generate", cat="serve"):
            with RecordEvent("inner"):
                pass
    finally:
        jax.profiler.TraceAnnotation = real
    assert seen == [("in", "serve.generate"), ("in", "inner"),
                    ("out", "inner"), ("out", "serve.generate")]


def test_spans_land_in_a_profiler_session(tmp_path):
    """The same `train.step*` names in the ring's Chrome export and on
    the host plane of the profiler's own trace."""
    from jax.profiler import ProfileData
    import glob
    step, ids = _tiny_step(False)
    step(ids, ids)
    mark = obs.recorder.appended
    jax.profiler.start_trace(str(tmp_path))
    try:
        float(step(ids, ids))
    finally:
        jax.profiler.stop_trace()
    path = obs.trace.export_chrome(str(tmp_path / "ring.json"))
    import json
    with open(path) as f:
        ring = {e["name"] for e in json.load(f)["traceEvents"]}
    want = {"train.step", "train.step.prep", "train.step.enqueue",
            "train.step.post"}
    assert want <= ring and want <= {e["name"] for e in _train_events(mark)}
    found = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert found
    pd = ProfileData.from_file(found[-1])
    host = {e.name for p in pd.planes if p.name == "/host:CPU"
            for line in p.lines for e in line.events}
    assert want <= host


# ------------------------------------------------------------- compiles
def test_compile_events_land_in_the_ring_with_a_time():
    import time

    def heavy(x):                        # a trace well over the floor
        for i in range(300):
            x = x * 1.0001 + i
        return x

    mark = obs.recorder.appended
    t0 = time.perf_counter()
    with counters.CompileTracker() as t:
        jax.jit(heavy)(np.ones(5, np.float32))
    t1 = time.perf_counter()
    evs = [e for e in _since(mark) if e["cat"] == "compile"]
    by = {n: [e for e in evs if e["name"] == "compile." + n]
          for n in ("trace", "lower", "backend")}

    def secs(n):
        return sum(e["dur"] for e in by[n]) / 1e6
    assert t.backend_compiles >= 1 and t.traces >= 1
    # every event over the floor is in the ring; the rest only counted
    floor = counters.RING_FLOOR_S
    missing = t.backend_compiles - len(by["backend"])
    assert missing >= 0
    assert t.compile_secs - missing * floor <= secs("backend") + 1e-9 \
        <= t.compile_secs + 2e-9
    assert 1 <= len(by["trace"]) <= t.traces
    assert by["lower"]
    assert all(e["dur"] >= floor * 1e6 * 0.999 for e in evs)
    named = [e for e in evs if e["args"]["fun_name"] in ("heavy",
                                                         "jit(heavy)")]
    assert {e["name"] for e in named} == {"compile.trace", "compile.lower",
                                          "compile.backend"}
    for e in named:                      # when: inside this test's call
        assert t0 * 1e6 <= e["ts"] and e["ts"] + e["dur"] <= t1 * 1e6 + 1


# ------------------------------------------------- the distributed step
def test_parallel_step_names_and_times_itself_alike():
    import paddle_tpu.distributed as dist
    import paddle_tpu.nn as nn
    dist.set_mesh(None)
    dist.init_mesh({"dp": 2, "sharding": 4})
    try:
        paddle.seed(5)
        net = nn.Sequential(nn.Linear(16, 32), nn.GELU(), nn.Linear(32, 16))
        opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                     parameters=net.parameters())
        step = dist.ParallelTrainStep(
            net, lambda out, y: ((out - y) ** 2).mean(), opt, zero_stage=2)
        x = np.random.RandomState(0).randn(16, 16).astype("float32")
        mark = obs.recorder.appended
        step(x, x)
        step(x, x)
        evs = _train_events(mark)
        assert [e["name"] for e in evs] == [
            "train.step.prep", "train.step.enqueue", "train.step.post",
            "train.step"] * 2
        assert [e["args"]["step"] for e in evs if e["name"] == "train.step"] \
            == [1, 2]
        count = step._trace_count
        rec = last_step_program()
        assert rec is step._step_program
        assert (rec.program, rec.trainer, rec.traces) == (
            "step", "ParallelTrainStep", count)
        with counters.CompileTracker() as t:
            read = [rp.read_scope(p) for p in step.op_scopes().values()
                    if p]
        assert t.backend_compiles == 0 and t.traces == 0
        assert step._trace_count == count and step.step_count == 2
        assert {"optimizer", "head_loss", "sequential"} <= {
            r["region"] for r in read}
        assert {"forward", "backward", "update"} <= {r["pass"] for r in read}
        # its fused window publishes as the single-chip trainer's does
        step.scan_steps(2, np.stack([x, x]), np.stack([x, x]))
        window = last_step_program()
        assert window is step._step_program and window is not rec
        assert (window.program, window.trainer, window.traces) == (
            "scan", "ParallelTrainStep", count + 1)
        step.scan_steps(2, np.stack([x, x]), np.stack([x, x]))
        assert last_step_program() is window
        assert "optimizer" in {rp.read_scope(p)["region"]
                               for p in window.op_scopes().values() if p}
    finally:
        dist.set_mesh(None)
