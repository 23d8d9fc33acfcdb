"""The four readers of the program's own ring (``step_prep_ms.train``,
``step_prep_mean_ms.train``, ``setup_trace_s``,
``setup_compile_or_load_s``) on a made-up ring whose
answers can be worked out by hand, and on a program that records none
of their spans."""
import importlib.util
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
METRICS = os.path.join(os.path.dirname(HERE), "layer_metrics")


def reader(name):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"), os.path.join(METRICS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.fixture
def made_up(monkeypatch):
    """A ring of 16 in the program's place; returns (record, obs).
    Window: 100 s to 103 s, three steps, each inside the driver's
    ``dispatch`` span."""
    import paddle_tpu.obs as program_obs
    from paddle_tpu.obs.trace import FlightRecorder
    rec = FlightRecorder(16)
    monkeypatch.setattr(program_obs, "recorder", rec)
    obs = {"steps": 3, "spans": [
        (n, 100.0 + i + a, 100.0 + i + b) for i in range(3)
        for n, a, b in (("make_batch", 0.0, 0.1), ("dispatch", 0.1, 0.9),
                        ("fetch_loss", 0.9, 1.0))]}

    def record(name, t0, t1, **args):
        rec.record(name, t0, t1, cat="x", args=args)
    return record, obs


def set_up(record):
    # a trace with another inside it, a lowering beside them: 2 + 1 s
    record("compile.trace", 10.0, 12.0, fun_name="full_step")
    record("compile.trace", 10.5, 11.0, fun_name="_var")
    record("compile.lower", 12.0, 13.0, fun_name="jit(full_step)")
    record("compile.backend", 13.0, 17.0, fun_name="jit(full_step)")
    record("compile.backend", 20.0, 20.5, fun_name="jit(make)")
    # the reference's, after the window: not set-up's
    record("compile.trace", 104.0, 105.0, fun_name="reference")
    record("compile.backend", 105.0, 109.0, fun_name="jit(reference)")


def steps(record, n=3, first=0):
    for i in range(first, first + n):
        t = 100.1 + i
        # the third waits for the queue: 2, 4 and 60 ms
        record("train.step.prep", t, t + (0.002, 0.004, 0.060)[i % 3], step=i)
        record("train.step.enqueue", t + 0.01, t + 0.7, step=i)
        record("train.step.post", t + 0.7, t + 0.701, step=i)
        record("train.step", t, t + 0.71, step=i)


def test_the_three_numbers_by_hand(made_up):
    record, obs = made_up
    # a warm-up step before the window is not the window's
    record("train.step.prep", 50.0, 50.5, step=0)
    steps(record)
    said = []
    assert reader("step_prep_ms.train")(None, obs, {}, None, said.append) \
        == pytest.approx(4.0)               # the median, not the mean 22
    assert "prep 22.000, enqueue 690.000" in said[-1]
    # (22 + 690 + 1) ms of the 800 ms the driver's span holds
    assert "89.1% of the driver's span" in said[-1]
    assert reader("step_prep_mean_ms.train")(
        None, obs, {}, None, said.append) == pytest.approx(22.0)
    assert "1 of 3 preps waited" in said[-1]
    assert "longest 60.000 ms" in said[-1]


def test_set_up_by_hand(made_up):
    record, obs = made_up
    set_up(record)
    said = []
    assert reader("setup_trace_s")(None, obs, {}, None, said.append) \
        == pytest.approx(3.0)
    assert "3 times" in said[-1] and said[-1].index("full_step") \
        < said[-1].index("_var")
    assert reader("setup_compile_or_load_s")(
        None, obs, {}, None, said.append) == pytest.approx(4.5)
    assert "2 programs" in said[-1] and "jit(full_step) 4.00 s" in said[-1]


@pytest.mark.parametrize("name", ["step_prep_ms.train",
                                  "step_prep_mean_ms.train",
                                  "setup_trace_s",
                                  "setup_compile_or_load_s"])
def test_a_program_without_the_spans_reads_nothing(made_up, name):
    record, obs = made_up
    record("engine.tick", 1.0, 2.0)
    said = []
    assert reader(name)(None, obs, {}, None, said.append) is None
    assert reader(name)(None, {}, {}, None, said.append) is None
    assert said == []


def test_a_wrapped_ring_has_lost_the_set_up(made_up):
    record, obs = made_up
    set_up(record)                      # 7 events
    steps(record)                       # 12 more: the oldest fall out
    said = []
    for name in ("setup_trace_s", "setup_compile_or_load_s"):
        assert reader(name)(None, obs, {}, None, said.append) is None
        assert "wrapped" in said[-1]
    # the window's spans are the newest, and all there
    assert reader("step_prep_ms.train")(None, obs, {}, None, said.append) \
        == pytest.approx(4.0)


def test_a_count_that_disagrees_is_not_read(made_up):
    record, obs = made_up
    steps(record, n=2)
    said = []
    for name in ("step_prep_ms.train", "step_prep_mean_ms.train"):
        assert reader(name)(None, obs, {}, None, said.append) is None
        assert "2 spans in the window for 3 steps" in said[-1]
