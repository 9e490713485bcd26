"""The trace reduction, the peaks table and the kernel work, on recorded
and on hand-made traces. Nothing here is a device number."""
import glob
import os

import pytest

import roofline
import trace_reduce as tr
from trace_reduce import Event

HERE = os.path.dirname(os.path.abspath(__file__))


def ops(*spans):
    return [Event(f"op{i}", s, e - s) for i, (s, e) in enumerate(spans)]


def test_union_busy_and_gaps():
    o = ops((0, 10), (5, 20), (30, 40), (35, 36), (50, 60))
    assert tr.union((x.start_ns, x.end_ns) for x in o) == [
        (0, 20), (30, 40), (50, 60)]
    assert tr.busy_ns(o, 0, 60) == 40
    assert tr.busy_ns(o, 15, 55) == 5 + 10 + 5
    assert tr.gaps(o, 0, 70) == [(20, 30), (40, 50), (60, 70)]
    assert tr.gaps(o, 15, 35) == [(20, 30)]


def test_op_kind():
    assert tr.op_kind("%paged_flash_decode_bkhd.105 = bf16[16,4,8,128] "
                      "custom-call(s32[16,152] %x.1)") == \
        "paged_flash_decode_bkhd"
    assert tr.op_kind("%fusion = bf16[2] fusion(%a.3)") == "fusion"
    assert tr.op_kind("copy-start.2") == "copy-start"


def test_op_time_top_ops_and_gap_labels():
    o = [Event("%kern.1 = f32[2] custom-call()", 0, 4),
         Event("%fusion.3 = f32[2] fusion()", 4, 2),
         Event("%kern.2 = f32[2] custom-call()", 10, 4),
         Event("%fusion = f32[2] fusion()", 20, 1),
         Event("%while.2 = (f32[2]) while()", 0, 21)]
    kern = lambda n: tr.op_kind(n) == "kern"  # noqa: E731
    assert tr.op_time_ns(o, 0, 30, kern) == (8, 2)
    assert tr.op_time_ns(o, 2, 12, kern) == (4, 2)
    top = tr.top_ops(o, 0, 30, k=3)          # the loop holding them is out
    assert [n for n, _ in top] == ["kern", "fusion"]
    o = o[:-1]
    assert top[0][1] == pytest.approx(8e-9) and top[1][1] == pytest.approx(3e-9)
    spans = [(0, 30, "engine.step"), (14, 20, "L16.commit")]
    got = tr.longest_gaps(tr.gaps(o, 0, 30), spans, k=2)
    assert got == [("engine.step", pytest.approx(9e-9)),
                   ("L16.commit", pytest.approx(6e-9))]
    assert tr.label_at(spans, 40) == "untraced host"


def test_recorded_trace_reads_device_and_host():
    """A trace recorded with jax.profiler on a TPU v5e (a few calls of the
    paged decode kernel, kept in data/): planes, ops and host spans."""
    files = glob.glob(os.path.join(HERE, "data", "*.xplane.pb"))
    assert files, "recorded trace missing"
    t = tr.load(files[0])
    assert list(t.device_ops) == ["/device:TPU:0"]
    dev = t.device_ops["/device:TPU:0"]
    assert dev and all(e.dur_ns >= 0 for e in dev)
    kern = [e for e in dev
            if tr.op_kind(e.name) == "paged_flash_decode_bkhd"]
    assert len(kern) == 3          # one call in each of the three steps
    assert len([e for e in t.host if e.name == "bench.step"]) == 3
    # each kernel event lies inside the device time of its step
    ns, n = tr.op_time_ns(dev, dev[0].start_ns, dev[-1].end_ns,
                          lambda x: tr.op_kind(x) == "paged_flash_decode_bkhd")
    assert n == 3 and ns == pytest.approx(sum(e.dur_ns for e in kern))
    assert any(e.name == "bench.step" for e in t.host)
    lo, hi = dev[0].start_ns, dev[-1].end_ns
    busy = tr.busy_ns(dev, lo, hi)
    assert 0 < busy <= hi - lo
    idle = sum(e - s for s, e in tr.gaps(dev, lo, hi))
    assert busy + idle == pytest.approx(hi - lo)


def test_peaks_table_knows_only_v5e():
    pk = roofline.peaks("TPU v5 lite")
    assert pk["bf16_flops"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("cpu")


def test_paged_decode_work():
    # one row of 100 live tokens, 32 q heads / 4 kv heads of 128, bf16
    f, b = roofline.paged_decode_call([100, 0], 32, 4, 128)
    assert f == 4 * 32 * 128 * 100
    assert b == 2 * (2 * 4 * 128 * 100 + 2 * 32 * 128)
    # bytes bound it: 8 FLOPs a byte is far under the v5e ridge
    pk = roofline.peaks("TPU v5 lite")
    assert roofline.roofline_s(f, b, pk) == pytest.approx(b / 819e9)
    assert roofline.token_flops(1000, 2, 4, 8, 9) == 2000 + 4 * 2 * 4 * 8 * 10
