"""The trace reduction: busy union, idle share, device time per program
and per kernel, and idle gaps named by host spans."""
import pathlib

import pytest

import xplane

DATA = pathlib.Path(__file__).resolve().parent / "data"


def test_busy_is_the_union_of_op_intervals():
    ops = [(0, 10, "a"), (5, 15, "b"), (20, 30, "c"), (22, 25, "d")]
    assert xplane.merge([(s, e) for s, e, _ in ops]) == [(0, 15), (20, 30)]
    assert xplane.busy_ns(ops) == 25


def test_idle_gaps_inside_the_window():
    ops = [(10, 20, "a"), (30, 40, "b")]
    assert xplane.idle_gaps(ops, 0, 50) == [(0, 10), (20, 30), (40, 50)]
    assert xplane.idle_gaps(ops, 15, 35) == [(20, 30)]


def test_gaps_are_named_by_the_host_span_covering_most_of_them():
    gaps = [(0, 10), (20, 30), (40, 50)]
    spans = [(0, 8, "executor.decode"), (18, 31, "wait_for_arrival")]
    named = xplane.name_gaps(gaps, spans)
    assert named == pytest.approx({"executor.decode": 10e-9,
                                   "wait_for_arrival": 10e-9,
                                   "host between ops": 10e-9})


def test_summary_per_program_and_kernel():
    ops = {"/device:TPU:0": [(0, 4, "fusion.1"), (4, 9, "paged_attn"),
                             (12, 20, "paged_attn"), (30, 40, "fusion.2")]}
    progs = {"/device:TPU:0": [(0, 9, "jit__step(3)"),
                               (12, 20, "jit__step(3)"),
                               (30, 40, "jit__lambda(5)")]}
    spans = [(9, 12, "executor.decode"), (0, 44, "traced_window")]
    s = xplane.summarize_window(ops, progs, spans, "traced_window")
    assert s.window_s == pytest.approx(44e-9)
    assert s.busy_s == pytest.approx(27e-9)
    assert s.idle_share == pytest.approx(17 / 44)
    assert s.program(r"^jit__step$") == pytest.approx([2, 17e-9])
    assert s.program(r"^nothing$") is None
    assert s.op_seconds("paged_attn") == pytest.approx(13e-9)
    assert s.idle_by_span == pytest.approx({"executor.decode": 3e-9,
                                            "host between ops": 14e-9})
    b = s.breakdown()
    assert b["device_ops"][0][0] == "paged_attn"
    assert b["idle_gaps"][0] == ["host between ops", pytest.approx(14e-9)]


def test_idle_at_the_ends_of_the_traced_window_counts():
    ops = {"/device:TPU:0": [(10, 20, "a")]}
    s = xplane.summarize(ops, {}, [], lo=0, hi=40)
    assert s.window_s == pytest.approx(40e-9)
    assert s.idle_share == pytest.approx(0.75)
    s = xplane.summarize(ops, {}, [], lo=12, hi=16)
    assert s.busy_s == pytest.approx(4e-9) and s.idle_share == 0.0


def test_a_trace_with_no_device_operation_is_refused():
    with pytest.raises(ValueError):
        xplane.summarize({}, {}, [], 0, 10)
    with pytest.raises(ValueError, match="one 'traced_window'"):
        xplane.summarize_window({"/device:TPU:0": [(0, 1, "a")]}, {}, [],
                                "traced_window")


def test_a_trace_recorded_on_the_chip(tmp_path):
    """3 s of the serving cell on one TPU v5e, traced by the harness: the
    device plane, the decode program and the paged-attention kernel are
    found under the names the readers look for."""
    import gzip

    import serve_cell

    path = tmp_path / "serve_decode.xplane.pb"
    path.write_bytes(gzip.decompress(
        (DATA / "serve_decode.xplane.pb.gz").read_bytes()))
    ops, progs, spans = xplane.read_xplane(
        str(path), serve_cell.SPANS + (serve_cell.WINDOW_SPAN,))
    assert list(ops) == ["/device:TPU:0"]
    s = xplane.summarize_window(ops, progs, spans, serve_cell.WINDOW_SPAN)
    assert s.window_s == pytest.approx(3.074124345)
    assert 0 < s.busy_s <= s.window_s
    assert s.idle_share == pytest.approx(0.0499226, abs=1e-6)
    runs, seconds = s.program(r"^jit__step$")
    assert runs == 16 and seconds == pytest.approx(2.509706017)
    assert s.op_seconds(r"paged_attention") == pytest.approx(0.3184953)
    b = s.breakdown()
    # op names are cut from the HLO text the trace gives them
    assert b["device_ops"][0][0] == "%while.4"
    assert all(len(name) < 64 for name, _ in b["device_ops"])
    assert {n for n, _ in b["idle_gaps"]} <= {
        "executor.decode", "executor.prefill", "wait_for_arrival",
        "host between ops"}
