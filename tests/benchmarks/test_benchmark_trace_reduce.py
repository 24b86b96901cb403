"""benchmarks/trace_reduce.py on a hand-made trace whose answers are known,
and on two recorded ones cut from TPU v5e traces: two whole steps of
``qwen3-0.6b.train.seq2048`` on one chip (``train_two_steps.xplane.pb``) and
one whole step of ``olmo2-7b-l8.train.fsdp4.seq4096`` on four
(``fsdp4_one_step.xplane.pb``), both under ``benchmarks/testdata/``."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks import trace_reduce as tr  # noqa: E402

RECORDED = ROOT / "benchmarks" / "testdata" / "train_two_steps.xplane.pb"
RECORDED_FSDP4 = ROOT / "benchmarks" / "testdata" / "fsdp4_one_step.xplane.pb"

# one device, ns. A while [0, 100) holds f1, an all-gather and f2; then idle
# [100, 150); then an all-reduce [150, 170) that a fusion overlaps from 160;
# then idle to the window's end at 200.
OPS = [
    ("%while.1 = () while()", 0, 100),
    ("%fusion.1 = f32[] fusion()", 0, 40),
    ("%all-gather.1 = f32[] all-gather()", 40, 60),
    ("%fusion.2 = f32[] fusion()", 60, 100),
    ("%all-reduce.1 = f32[] all-reduce()", 150, 170),
    ("%fusion.3 = f32[] fusion()", 160, 180),
]
SPANS = [("data", 0, 10), ("step", 10, 120), ("data", 120, 155),
         ("step", 155, 200)]


def test_union_measure_subtract():
    assert tr.union([(5, 9), (0, 3), (2, 6)]) == [(0, 9)]
    assert tr.measure(tr.union([(0, 3), (5, 6)])) == 4
    assert tr.subtract([(0, 10)], [(2, 3), (5, 20)]) == [(0, 2), (3, 5)]
    assert tr.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]


def test_self_times_subtract_nested_children():
    by_name = {n.split(" = ")[0]: s for n, s, _, _ in tr.self_times(OPS)}
    assert by_name["%while.1"] == 0          # wholly covered by its body
    assert by_name["%fusion.1"] == 40
    assert by_name["%all-gather.1"] == 20


def test_busy_idle_and_gaps():
    busy, gaps = tr.busy_and_gaps(OPS, 0, 200)
    assert tr.measure(busy) == 130           # [0,100) + [150,180)
    assert gaps == [(100, 150), (180, 200)]


def test_exposed_collective_time():
    # all-gather: 20 ns alone. all-reduce: [150,160) alone, [160,170) hidden.
    assert tr.exposed_collective_ns(OPS, 0, 200) == 30


def test_a_fusion_that_consumes_a_collective_is_not_one():
    assert tr.is_collective("%all-gather-start.3 = (f32[8]) all-gather-start(f32[2] %p)")
    assert tr.is_collective("%reduce-scatter.1 = f32[2] reduce-scatter(f32[8] %g)")
    assert not tr.is_collective(
        "%fusion.7 = f32[8] fusion(f32[8] %all-gather-done.3), kind=kLoop")


def test_gap_attribution_by_covering_span():
    _, gaps = tr.busy_and_gaps(OPS, 0, 200)
    totals = tr.attribute_gaps(gaps, SPANS)
    # [100,150): step covers 20, data 30 -> data; [180,200) -> step
    assert totals == {"data": pytest.approx(50e-9), "step": pytest.approx(20e-9)}


def test_reduce_events_hand_made():
    r = tr.reduce_events({0: OPS}, SPANS, 1)
    assert r["window_s"] == pytest.approx(200e-9)
    assert r["busy_s"] == pytest.approx(130e-9)
    assert r["idle_share_worst"] == pytest.approx(70 / 200)
    assert r["exposed_collective_s_worst"] == pytest.approx(30e-9)
    assert r["top_ops"][0] == ["fusion.1 fusion", pytest.approx(40e-9)]
    assert tr.reduce_events({}, SPANS, 1) is None


def test_worst_device_is_reported():
    idle_dev = [("%fusion.9 = f32[] fusion()", 0, 20)]
    r = tr.reduce_events({0: OPS, 1: idle_dev}, SPANS, 2)
    assert r["idle_share_worst"] == pytest.approx(180 / 200)
    assert r["busy_s"] == pytest.approx((130e-9 + 20e-9) / 2)


def test_short_name():
    long = ('%closed_call.13 = (bf16[8,16]{1,0}, f32[8]{0}) custom-call(bf16[8] '
            '%x), custom_call_target="tpu_custom_call", frontend_attributes={}')
    assert tr.short_name(long) == "closed_call.13 tpu_custom_call"
    assert tr.short_name("%fusion.2 = f32[8]{0:T(8)} fusion(f32[8] %y), kind=kLoop") \
        == "fusion.2 fusion"


@pytest.fixture(scope="module")
def recorded():
    device_ops, host_spans, _ = tr.read_planes(RECORDED)
    return device_ops, host_spans, tr.reduce_events(device_ops, host_spans, 1)


def test_recorded_trace_window_busy_idle(recorded):
    device_ops, host_spans, r = recorded
    assert sorted(n for n, _, _ in host_spans) == ["data", "data", "step", "step"]
    assert r["window_s"] == pytest.approx(2.069045671, rel=1e-9)
    assert r["busy_s"] == pytest.approx(2.063883854, rel=1e-6)
    assert r["idle_share_worst"] == pytest.approx(0.0024948, rel=1e-3)
    assert r["exposed_collective_s_worst"] == 0.0     # one chip
    assert r["top_gaps"][0][0] == "step"


def test_recorded_trace_kernels(recorded):
    device_ops, _, r = recorded
    events = tr.kernel_events(device_ops[0],
                              'custom_call_target="tpu_custom_call"',
                              r["lo_ns"], r["hi_ns"])
    # 2 steps x 28 layers x (forward, rematted forward, dq, dkv)
    assert len(events) == 2 * 28 * 4
    assert sum(b - a for _, a, b in events) / 1e9 == pytest.approx(0.686, rel=0.01)
    assert r["top_ops"][0][0].endswith("tpu_custom_call")


@pytest.fixture(scope="module")
def recorded_fsdp4():
    device_ops, host_spans, _ = tr.read_planes(RECORDED_FSDP4)
    return device_ops, tr.reduce_events(device_ops, host_spans, 4)


def test_recorded_four_chip_trace_busy_idle_and_exposed_collectives(recorded_fsdp4):
    device_ops, r = recorded_fsdp4
    assert r["devices"] == [0, 1, 2, 3] and len(r["per_device"]) == 4
    assert r["window_s"] == pytest.approx(1.752583663, rel=1e-9)
    assert r["busy_s"] == pytest.approx(1.748678755, rel=1e-6)
    assert r["idle_share_worst"] == pytest.approx(0.00222876, rel=1e-3)
    # the step's synchronous fp32 all-gathers, with nothing else running
    exposed = [tr.exposed_collective_ns(device_ops[d], r["lo_ns"], r["hi_ns"])
               for d in r["devices"]]
    assert r["exposed_collective_s_worst"] == max(exposed) / 1e9 \
        == pytest.approx(0.247634133, rel=1e-9)
    assert max(exposed) - min(exposed) < 0.001 * max(exposed)
    assert [n for n, _ in r["top_ops"][1:3]] == ["all-gather.208 all-gather",
                                                 "all-gather.207 all-gather"]


def test_collective_reader_reads_only_across_chips(recorded, recorded_fsdp4):
    from benchmarks.readers import trace_collective

    assert trace_collective.read({"trace": recorded[2]}, {}) is None
    assert trace_collective.read({"trace": recorded_fsdp4[1]}, {}) \
        == pytest.approx(14.13, rel=1e-3)
    assert trace_collective.read({"trace": None}, {}) is None


def test_recorded_four_chip_trace_kernels(recorded_fsdp4):
    device_ops, r = recorded_fsdp4
    for d in r["devices"]:
        events = tr.kernel_events(device_ops[d],
                                  'custom_call_target="tpu_custom_call"',
                                  r["lo_ns"], r["hi_ns"])
        # one step x 8 layers x (forward, rematted forward, dq, dkv)
        assert len(events) == 8 * 4
