"""The reduction from a trace to numbers: on made-up intervals whose
answers can be worked out by hand, and on a small trace recorded on the
chip (``data/step_2layers.xplane.pb.gz``: three steps of a 2-layer
1.3B-width model, 4 x 2048 tokens, scanned, recomputed; TPU v5 lite,
PR 27)."""
import os

import pytest

from benchmark import flash, trace

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "step_2layers.xplane.pb.gz")


def test_union_clip_total():
    iv = trace.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert iv == [[0, 3], [5, 8]]
    assert trace.total(trace.clip(iv, 2, 6)) == 2
    assert trace.total([]) == 0


def test_self_times_take_children_out():
    evs = [("while.1", 0, 100), ("fusion.1", 10, 30), ("fusion.2", 40, 50),
           ("flash_attention.3", 60, 90), ("copy.1", 100, 110)]
    st = trace.self_times(evs)
    assert st == {"while.1": 40, "fusion.1": 20, "fusion.2": 10,
                  "flash_attention.3": 30, "copy.1": 10}
    assert trace.op_name("%fusion.12 = bf16[8]{0} fusion(...)") == "fusion.12"
    assert trace.op_family("flash_attention.15") == "flash_attention"
    assert trace.op_family("broadcast.45.clone") == "broadcast.45.clone"


def made_up():
    chip = trace.Chip(
        ops=[("while.1", 100, 200), ("fusion.1", 110, 150),
             ("flash_attention.2", 150, 190), ("fusion.1", 300, 340),
             ("flash_mha_bwd_dq_block.9", 340, 360), ("copy.1", 900, 950)],
        modules=[("jit_step", 100, 200), ("jit_step", 300, 380)],
        kernels={n: f"%{n} = bf16[2,4,64,16] custom-call(), {trace.KERNEL_MARK}"
                 for n in ("flash_attention.2", "flash_mha_bwd_dq_block.9")})
    spans = [(trace.WINDOW_SPAN, 50, 400), ("bench.make_batch", 50, 90),
             ("bench.dispatch", 90, 120), ("bench.fetch_loss", 200, 400)]
    return trace.Trace([chip], spans)


CELL = {"config": {"num_heads": 4, "hidden_size": 64},
        "traffic": {"batch": 2, "seq": 64}}
RECORDED_CELL = {"config": {"num_heads": 16, "hidden_size": 2048},
                 "traffic": {"batch": 4, "seq": 2048}}


def test_busy_idle_and_kernel_sum_by_hand():
    tr, cell = made_up(), CELL
    assert tr.window_s() == pytest.approx(350e-9)
    # busy: [100,200) and [300,360) inside the window; 900.. is outside
    assert tr.busy_s() == pytest.approx(160e-9)
    assert tr.op_seconds(flash.matcher(tr, cell)) == pytest.approx(60e-9)
    assert tr.op_count(flash.matcher(tr, cell)) == 2
    # by shape, whatever the name; another kernel's shapes do not count
    is_flash = flash.matcher(trace.Trace([trace.Chip(kernels={
        "tpu_custom_call.7": "%tpu_custom_call.7 = bf16[2,4,64,16] x",
        "fused_ce.1": "%fused_ce.1 = f32[128,1] custom-call(f32[128,256])",
    })], []), CELL)
    assert is_flash("tpu_custom_call.7") and not is_flash("fused_ce.1")
    assert not is_flash("fusion.1")
    idle = tr.idle_by_cause()
    # 50-90 under make_batch, 90-100 under dispatch; 200-300 under
    # fetch_loss; 360-380 while a program runs; 380-400 under fetch_loss
    assert idle == {"make_batch": 40, "dispatch": 10, "fetch_loss": 120,
                    "in_program": 20}
    assert sum(idle.values()) == pytest.approx(
        (tr.window_s() - tr.busy_s()) * 1e9)
    top = dict(tr.top_ops())
    assert top["fusion"] == pytest.approx(80e-9)
    assert top["while"] == pytest.approx(20e-9)


def test_no_device_plane_is_an_error():
    class Line:
        name, events = "python", []

    class Plane:
        name, lines = "/host:CPU", [Line()]

    class PD:
        planes = [Plane()]
    with pytest.raises(ValueError, match="nothing ran on a device"):
        trace.from_profile_data(PD())


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_recorded_trace():
    tr, cell = trace.load(RECORDED), RECORDED_CELL
    assert len(tr.chips) == 1
    w, busy = tr.window_s(), tr.busy_s()
    # three 113.5 ms steps end to end in a 0.34-0.36 s window
    assert 0.33 < w < 0.37
    assert 0.3390 < busy < 0.3420
    assert busy < w
    assert 0 < 1 - busy / w < 0.08
    idle = tr.idle_by_cause()
    assert sum(idle.values()) == pytest.approx((w - busy) * 1e9, rel=1e-6)
    assert set(idle) <= {"make_batch", "dispatch", "fetch_loss",
                         "in_program", "host_other"}
    # flash: 2 layers x 3 steps x (2 forwards, one of them recomputed,
    # + dkv + dq) = 24 kernel events, 0.83 / 0.85 / 1.69 / 1.29 ms each
    assert tr.op_count(flash.matcher(tr, cell)) == 24
    assert tr.op_seconds(flash.matcher(tr, cell)) == pytest.approx(0.02799, rel=5e-3)
    top = tr.top_ops(10)
    assert len(top) == 10 and top[0][1] >= top[-1][1] > 0
    assert sum(s for _, s in top) < busy
    names = [n for n, _ in top]
    assert "fusion" in names and "while" not in names[:1]
