"""Trace reduction and kernel work counts, on traces built by hand in the
shape the TPU profiler writes (event names as recorded on a v5e)."""

import pytest

from bench import devtrace, work

HINDEX = ('%hindex_rows.21 = s32[343040,1]{1,0:T(8,128)} custom-call(s32[343040,8]{1,0:T(8,128)} '
          '%pad.126, s32[343040,1]{1,0:T(8,128)} %copy.19), custom_call_target="tpu_custom_call", '
          'operand_layout_constraints={s32[343040,8]{1,0}, s32[343040,1]{1,0}}')
SEGSUM = ('%closed_call.11 = f32[64,8,128]{2,1,0:T(8,128)S(1)} custom-call(s32[277]{0:T(512)S(1)} '
          '%copy-done.15, bf16[277,16,128]{2,1,0:T(8,128)(2,1)S(1)} %bitcast.52, '
          's32[277,16,128]{2,1,0:T(8,128)S(1)} %copy-done.13), custom_call_target="tpu_custom_call"')
FUSION = ('%fusion.99 = bf16[11812864]{0:T(1024)(128)(2,1)S(1)} fusion(bf16[10171739]{0} %a, '
          's32[11812864]{0} %b), kind=kCustom, calls=%fused_computation.15')
WHILE = '%while.3 = (s32[1048576]{0:T(1024)}, pred[]) while((s32[1048576]{0}, pred[]) %tuple.31)'
MS = 1_000_000


def planes():
    """Window [100, 1100] ms. Device: a while op over [100, 600] holding a
    fusion and two kernels, then an op at [800, 900] and one that starts
    before the window closes. Host: the window, a prep span over the gap
    [600, 800], an update span over the rest, and a frame under prep."""
    device = [
        (WHILE, 100 * MS, 500 * MS),
        (FUSION, 120 * MS, 200 * MS),
        (HINDEX, 330 * MS, 10 * MS),
        (SEGSUM, 350 * MS, 30 * MS),
        (FUSION, 800 * MS, 100 * MS),
        (FUSION, 1050 * MS, 100 * MS),
    ]
    host = [
        ("bench.window", 100 * MS, 1000 * MS),
        ("bench.update", 100 * MS, 500 * MS),
        ("bench.prep", 600 * MS, 200 * MS),
        ("$graphs.py:150 relabel", 610 * MS, 180 * MS),
        ("bench.update", 800 * MS, 300 * MS),
    ]
    return [("/device:TPU:0", [("XLA Ops", device)]), ("/host:CPU", [("python", host)])]


def test_busy_and_idle_are_the_union_of_device_ops_in_the_window():
    r = devtrace.reduce_planes(planes())
    assert r.window_s == pytest.approx(1.0)
    # [100, 600] + [800, 900] + [1050, 1100] (clipped at the close)
    assert r.busy_s == pytest.approx(0.65)
    assert r.chips == 1


def test_idle_gaps_are_named_by_the_host_span_and_frame_at_their_middle():
    r = devtrace.reduce_planes(planes())
    (name, secs), *rest = r.idle_gaps
    assert secs == pytest.approx(0.2)
    assert name == "bench.prep | graphs.py:150 relabel"
    assert [n for n, _ in rest] == ["bench.update"]
    assert sum(s for _, s in r.idle_gaps) == pytest.approx(0.35)


def test_kernel_events_are_found_and_summed():
    r = devtrace.reduce_planes(planes())
    kinds = sorted((k.kernel, round(k.seconds, 6)) for k in r.kernels)
    assert kinds == [("hindex", 0.01), ("segsum", 0.03)]
    assert devtrace.kernel_of(FUSION) is None
    assert devtrace.kernel_of(WHILE) is None


def test_device_ops_rank_ops_by_time_without_the_loop_that_holds_them():
    r = devtrace.reduce_planes(planes())
    names = [n for n, _ in r.device_ops]
    assert names[0] == "fusion.99 bf16[11812864] fusion"
    assert r.device_ops[0][1] == pytest.approx(0.4)
    assert not any(n.endswith(" while") for n in names)


def test_a_trace_without_the_window_span_is_refused():
    p = planes()
    p[1] = ("/host:CPU", [("python", [("bench.update", 0, 10)])])
    with pytest.raises(ValueError):
        devtrace.reduce_planes(p)


def test_busy_is_averaged_over_chips():
    p = planes()
    p.append(("/device:TPU:1", [("XLA Ops", [(FUSION, 100 * MS, 1000 * MS)])]))
    r = devtrace.reduce_planes(p)
    assert r.chips == 2
    assert r.busy_s == pytest.approx((0.65 + 1.0) / 2)


def test_work_counts_follow_the_wrapper_operands():
    assert work.hindex_bytes(10, 8) == 4 * 10 * 8 + 4 * 10 + 4 * 10
    assert work.segsum_bytes(1 << 19, 1, 65536) == (1 << 19) * 5 + 4 * 65536


def test_roofline_reads_each_event_against_its_wrapper_call():
    shapes = work.KernelShapes()
    shapes.hindex[8] = {(342968, 8, 4)}
    shapes.segsum[277] = {(1 << 19, 1, 65536)}
    r = devtrace.reduce_planes(planes())
    bw = 819e9
    h = work.roofline_pct(r.kernels, shapes, "hindex", bw)
    assert h == pytest.approx(100 * work.hindex_bytes(342968, 8) / bw / 0.01)
    s = work.roofline_pct(r.kernels, shapes, "segsum", bw)
    assert s == pytest.approx(100 * work.segsum_bytes(1 << 19, 1, 65536) / bw / 0.03)


def test_roofline_is_silent_where_it_cannot_size_a_call():
    r = devtrace.reduce_planes(planes())
    shapes = work.KernelShapes()
    assert work.roofline_pct(r.kernels, shapes, "hindex", 819e9) is None
    shapes.hindex[8] = {(342968, 8, 4), (1000, 8, 4)}
    assert work.roofline_pct(r.kernels, shapes, "hindex", 819e9) is None
    assert work.roofline_pct([], shapes, "segsum", 819e9) is None
