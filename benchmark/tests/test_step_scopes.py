"""The readers that name a pass or a layer by the step program's own
scopes (``benchmark/step_scopes.py`` and the eight ``*_time_pct`` readers
it serves): on made-up intervals whose answers can be worked out by hand,
and on the small trace recorded on the chip
(``data/step_2layers.xplane.pb.gz``) with a made-up table from instruction
to path."""
import os

import pytest

from benchmark import run as run_mod
from benchmark import step_scopes, trace

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "step_2layers.xplane.pb.gz")
PASS_READERS = {"backward_time_pct": "backward",
                "recompute_time_pct": "recompute",
                "optimizer_time_pct": "update"}
MOE_READERS = ("moe_dispatch_time_pct", "moe_products_time_pct",
               "moe_combine_time_pct")
ALL_READERS = (*PASS_READERS, "head_loss_time_pct", "unscoped_time_pct",
               *MOE_READERS)

_FWD = "jit(full_step)/jvp(net)/model/block_1/"
_BWD = "jit(full_step)/transpose(jvp(net))/model/block_1/"
_SORTED = "mlp/experts/cond/branch_0_fun/jit(routed_sorted)/"


@pytest.fixture(autouse=True)
def no_record_of_another_test(monkeypatch):
    """The record is the process's last compiled step: some other test's
    here. These tests hand their table over as a driver does."""
    from paddle_tpu.jit import training
    monkeypatch.setattr(training, "_last_program", None)


def read(metric, tr, obs):
    said = []
    value = run_mod.load_module("layer_metrics", metric).read(
        tr, obs, {}, None, said.append)
    return value, said


def made_up():
    """2400 ns busy of a 2600 ns window; one operation of each kind."""
    ops = [("fusion.1", 1000, 1100),        # attention, forward
           ("sort.3", 1100, 1150),          # the expert layer's sort
           ("fusion.2", 1150, 1300),        # its gather
           ("gmm.4", 1300, 1500),           # its products
           ("fusion.5", 1500, 1600),        # its combine
           ("fusion.6", 1600, 1900),        # head and loss, forward
           ("fusion.7", 1900, 2100),        # head's gradient products
           ("fusion.8", 2100, 2250),        # the gather's transpose
           ("tgmm.9", 2250, 2450),          # products, backward
           ("fusion.10", 2450, 2700),       # attention, recomputed
           ("fusion.11", 2700, 3000),       # attention, backward
           ("fusion.12", 3000, 3300),       # optimizer
           ("copy.13", 3300, 3380),         # a path with no scope
           ("copy.14", 3380, 3400)]         # not in the table
    table = {
        "fusion.1": _FWD + "attn/q_proj/dot_general",
        "sort.3": _FWD + "mlp/experts/sort/jit(argsort)/sort",
        "fusion.2": _FWD + _SORTED + "dispatch/gather",
        "gmm.4": _FWD + _SORTED + "products/jit(gmm)/pallas_call",
        "fusion.5": _FWD + _SORTED + "combine/scatter-add",
        "fusion.6": "jit(full_step)/jvp(head_loss)/head_loss/dot_general",
        "fusion.7": "jit(full_step)/jvp(head_loss)/head_loss/while/body/"
        "closed_call/transpose(jvp())/dot_general",
        "fusion.8": _BWD + "mlp/experts/cond/branch_0_fun/"
        "transpose(jvp(jit(routed_sorted)))/dispatch/scatter-add",
        "tgmm.9": _BWD + "mlp/experts/cond/branch_0_fun/"
        "transpose(jvp(jit(routed_sorted)))/products/jit(tgmm)/pallas_call",
        "fusion.10": "jit(full_step)/transpose(jvp(net))/model/checkpoint/"
        "rematted_computation/block_1/attn/q_proj/dot_general",
        "fusion.11": _BWD + "attn/q_proj/dot_general",
        "fusion.12": "jit(full_step)/optimizer/mul",
        "copy.13": "jit(full_step)/jit(_where)/select_n"}
    chip = trace.Chip(ops=ops, modules=[("jit_full_step", 1000, 3400)])
    return (trace.Trace([chip], [(trace.WINDOW_SPAN, 900, 3500)]),
            {"steps": 1, "op_scopes": table})


def test_readers_by_hand():
    tr, obs = made_up()
    busy = 2400.0
    assert tr.busy_s() == pytest.approx(busy * 1e-9)
    want = {"backward_time_pct": 200 + 150 + 200 + 300,
            "recompute_time_pct": 250,
            "optimizer_time_pct": 300,
            "head_loss_time_pct": 300 + 200,
            "unscoped_time_pct": 80 + 20,
            "moe_dispatch_time_pct": 50 + 150 + 150,
            "moe_products_time_pct": 200 + 200,
            "moe_combine_time_pct": 100}
    for metric, ns in want.items():
        value, said = read(metric, tr, obs)
        assert value == pytest.approx(100 * ns / busy), metric
        # every reader says the window's four passes, which sum to busy
        assert said and "the window's passes, s: forward" in said[-1]
        assert said[-1].endswith("= 0.0000 of 0.0000 busy")
    got = obs["step_scopes"]            # joined once, kept for the next
    assert step_scopes.read(tr, obs, None) is got
    passes = step_scopes.by_pass(got)
    assert passes == pytest.approx({
        "forward": (100 + 50 + 150 + 200 + 100 + 300 + 80 + 20) * 1e-9,
        "recompute": 250e-9, "backward": 850e-9, "update": 300e-9})
    assert sum(passes.values()) == pytest.approx(got["busy_s"])
    # what the table cannot name counts as forward, and is said apart
    assert got["by_scope"]["not_in_table_share"] == pytest.approx(
        20 / busy, abs=1e-6)
    _, said = read("unscoped_time_pct", tr, obs)
    assert "the driver's hand-over" in said[0]
    _, said = read("moe_dispatch_time_pct", tr, obs)
    assert "forward 0.0000, recompute 0.0000, backward 0.0000" in said[0]
    assert step_scopes.under(got, "experts") == pytest.approx({
        "forward": 500e-9, "recompute": 0.0, "backward": 350e-9,
        "update": 0.0})
    # the join is `benchmark/scopes.py`'s, not a second one
    from benchmark import scopes
    assert got == scopes.table(tr, obs["op_scopes"])
    # the three parts are all of `experts` here
    assert sum(want[m] for m in MOE_READERS) == 850


def test_readers_on_the_recorded_trace_with_a_made_up_table():
    """Every operation of the recorded window gets a path by its family:
    the shares by pass and the unscoped share sum to 100."""
    tr = trace.load(RECORDED)
    lo, hi = tr.window()
    names = {n for n, s, e in tr.chips[0].ops if min(e, hi) > max(s, lo)}
    paths = {"fusion": _FWD + "attn/q_proj/dot_general",
             "convolution_add_fusion": _BWD + "mlp/fc_in/dot_general",
             "bitcast_dynamic-update-slice_fusion":
             "jit(full_step)/transpose(jvp(net))/model/blocks/while/body/"
             "dynamic_update_slice",
             "subtract_convert_fusion": "jit(full_step)/optimizer/sub",
             "add_add_fusion": "jit(full_step)/transpose(jvp(net))/model/"
             "checkpoint/rematted_computation/block_1/mlp/add",
             "multiply_reduce_fusion": "jit(full_step)/jvp(head_loss)/"
             "head_loss/reduce_sum",
             "copy": "jit(full_step)/jit(_where)/select_n"}
    table = {n: paths.get(trace.op_family(n), paths["copy"]) for n in names}
    obs = {"steps": 3, "op_scopes": table}
    values = {m: read(m, tr, obs)[0] for m in ALL_READERS}
    got = obs["step_scopes"]
    assert got["busy_s"] == pytest.approx(tr.busy_s())
    passes = step_scopes.by_pass(got)
    assert sum(passes.values()) == pytest.approx(tr.busy_s(), rel=1e-6)
    for metric, pass_ in PASS_READERS.items():
        assert values[metric] == pytest.approx(
            100 * passes[pass_] / tr.busy_s())
    assert values["optimizer_time_pct"] == pytest.approx(
        100 * 0.02008273 / tr.busy_s(), rel=1e-4)
    assert values["head_loss_time_pct"] == pytest.approx(
        100 * 0.00738188 / tr.busy_s(), rel=1e-4)
    # forward less what no scope names, the three other passes and the
    # unscoped share are the whole of the busy time
    named_forward = 100 * passes["forward"] / tr.busy_s() \
        - values["unscoped_time_pct"]
    assert named_forward > 0
    assert named_forward + sum(values[m] for m in PASS_READERS) \
        + values["unscoped_time_pct"] == pytest.approx(100.0)
    assert 10 < values["unscoped_time_pct"] < 30    # the families not named
    assert got["by_scope"]["not_in_table_share"] == 0
    # no expert layer in this program: nothing to read, nothing raised
    assert all(values[m] is None for m in MOE_READERS)


@pytest.mark.parametrize("metric", ALL_READERS)
def test_readers_return_nothing_where_there_is_no_table(metric):
    tr, obs = made_up()
    assert read(metric, tr, {"steps": 1})[0] is None    # no hand-over
    assert read(metric, tr, {"steps": 1, "op_scopes": {}})[0] is None
    assert read(metric, None, dict(obs))[0] is None     # no trace


@pytest.mark.parametrize("metric", ALL_READERS)
def test_readers_read_nothing_from_a_table_of_another_program(metric):
    """A table that lacks the instructions of more than 1% of the busy
    time is of a program that compiled after the traced one: no number,
    and a line that says why."""
    tr, obs = made_up()
    # fusion.11's 300 ns and copy.14's 20, of 2400 busy
    stale = {n: p for n, p in obs["op_scopes"].items() if n != "fusion.11"}
    value, said = read(metric, tr, {"steps": 1, "op_scopes": stale})
    assert value is None
    assert "13.33% of busy time" in said[0] and "another program" in said[0]


def test_the_programs_own_record_comes_first(monkeypatch):
    """Where the program published a record of its step, the readers take
    its table, whatever the driver handed over; and say so."""
    from paddle_tpu.jit import training
    tr, obs = made_up()
    record = training.StepProgram("step", "TrainStep", 1, None)
    record._table, record._text = dict(obs["op_scopes"]), "HloModule m"
    monkeypatch.setattr(training, "_last_program", record)
    assert step_scopes.table_of({}) == obs["op_scopes"]
    value, said = read("unscoped_time_pct", tr, {"steps": 1})
    assert value == pytest.approx(100 * 100 / 2400)
    assert "the program's own record (step of TrainStep" in said[0]
    value, _ = read("backward_time_pct", tr,
                    {"steps": 1, "op_scopes": {"fusion.1": "jit(f)/x/add"}})
    assert value == pytest.approx(100 * 850 / 2400)


def test_benchmark_json_lists_the_readers():
    bench = run_mod.load_json("BENCHMARK.json")
    mine = {m["name"]: m for m in bench["per_layer"]
            if m["name"] in ALL_READERS}
    assert set(mine) == set(ALL_READERS)
    sparse = ["train-trinity-mini-8k", "train-kanana-2-8k",
              "train-smallthinker-16k"]
    for name, m in mine.items():
        assert (m["unit"], m["source"], m["moves"], m["better"]) == (
            "%", "device_trace", "train_tokens_per_s", "lower")
        assert m["layer"] == ("expert layer" if name in MOE_READERS
                              else "model step")
        want = sparse if name in MOE_READERS else (
            ["train-gpt-1.3b"] + sparse if name == "recompute_time_pct"
            else None)
        assert m.get("workloads") == want
