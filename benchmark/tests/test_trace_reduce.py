"""The reduction from a trace to the per-layer table: its arithmetic on
hand-made intervals, then the whole of it on one small recorded trace
(``tests/data/serve_cut.textproto.gz``: 0.3 s of the serving cell on a
TPU v5e, PR 24, cut by ``trace_reduce.to_text_proto``).  Its ``XLA
Modules`` line, read by hand: three decode dispatches of 46.61, 55.76 and
73.92 ms (the 256, 512 and 1024 rungs), one prefill chunk of 35.09 ms,
and the tail of a fourth decode dispatch that the cut's edge splits."""

import os

import pytest

from benchmark.lib import spec, trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_busy_is_the_union_not_the_sum():
    seconds, merged = tr.union_seconds(
        [(0, 4e9), (1e9, 2e9), (3e9, 6e9), (8e9, 9e9)])
    assert seconds == pytest.approx(7.0)
    assert merged == [(0, 6e9), (8e9, 9e9)]
    assert tr.union_seconds([])[0] == 0.0


def test_self_time_takes_the_enclosed_ops_out():
    # a while of 10 s holding two ops of 3 s and 4 s, then an op alone
    events = [(0, 10e9, "while"), (1e9, 4e9, "a"), (5e9, 9e9, "b"),
              (11e9, 12e9, "a")]
    out = tr.self_times(events)
    assert out["while"] == pytest.approx(3.0)
    assert out["a"] == pytest.approx(4.0)
    assert out["b"] == pytest.approx(4.0)


def test_gaps_go_to_the_innermost_host_span():
    lines = [[(0, 100, "outer"), (10, 40, "inner"), (60, 70, "late")],
             [(0, 1000, tr.WINDOW_SPAN)]]
    out = tr._attribute([(20, 30), (45, 55), (200, 300)], lines)
    assert out == {"inner": pytest.approx(10e-9),
                   "outer": pytest.approx(10e-9),
                   "unattributed": pytest.approx(100e-9)}


def test_labels_and_module_names():
    assert tr.module_name("jit_decode_fn(123456789)") == "jit_decode_fn"
    assert tr.op_label(
        "%fusion.5 = (f32[8,16]{1,0:T(8,128)}, bf16[4]{0}) fusion(f32[8]{0} "
        "%p.1), kind=kOutput, calls=%fused.1") == \
        "fusion.5 kOutput (f32[8,16], bf16[4])"
    assert tr.op_label("%copy.1 = f32[24,8]{1,0} copy(f32[24,8]{0,1} %x)") \
        == "copy.1 copy f32[24,8]"
    assert tr.op_label("dot_general") == "dot_general"


@pytest.fixture(scope="module")
def recorded():
    path = os.path.join(DATA, "serve_cut.textproto.gz")
    return tr.reduce_profile(tr.load(path))


def test_recorded_trace_window_and_busy(recorded):
    assert recorded["chips"] == 1
    assert recorded["window_s"] == pytest.approx(0.3, abs=1e-6)
    # the four whole dispatches, and 68 ms of ops of the split one
    assert recorded["busy_s"] == pytest.approx(0.28546, abs=1e-5)
    whole = 0.04661 + 0.05576 + 0.07392 + 0.03509
    assert whole < recorded["busy_s"] < recorded["window_s"]
    ops = sum(s for _, s in recorded["device_ops"])
    # the ten largest ops are part of the busy time, never more than it
    assert 0 < ops <= recorded["busy_s"]
    assert recorded["device_ops"][0][0] == \
        "copy.1411 copy bf16[36,257,32,20,64]"     # the whole pool, copied
    gaps = dict(recorded["idle_gaps"])
    idle = recorded["window_s"] - recorded["busy_s"]
    assert sum(gaps.values()) == pytest.approx(idle, rel=1e-6)
    # the host's fetch of the sampled tokens is the longest idle gap
    assert recorded["idle_gaps"][0][0] == "np.asarray(jax.Array)"


def test_recorded_trace_programs(recorded):
    cell = spec.load_cell("serve_gpt2l_closed8_decode")
    decode = tr.program_time(recorded, cell.traffic["programs"]["decode"])
    prefill = tr.program_time(recorded, cell.traffic["programs"]["prefill"])
    # the split dispatch is counted in neither
    assert decode == (3.0, pytest.approx(0.17629, abs=1e-5))
    assert prefill == (1.0, pytest.approx(0.03509, abs=1e-5))
    assert tr.program_time(recorded, "^jit_no_such_program") is None
    # and the two metrics that read them
    table = {"programs": {"decode": {"count": decode[0],
                                     "seconds": decode[1]},
                          "prefill": {"count": prefill[0],
                                      "seconds": prefill[1]}}}
    assert spec.metric_reader("decode_step_dev_ms")(table) == \
        pytest.approx(58.76, abs=0.01)
    assert spec.metric_reader("prefill_dev_ms")(table) == \
        pytest.approx(35.09, abs=0.01)
    assert spec.metric_reader("train_step_dev_ms")(table) is None
