"""The reduction of a trace by the program's own spans (``program_spans``):
idle by innermost span, split where the span changes, idle under no span
kept apart, the per-call readings by hand, and the benchmark's own naming
of gaps left as it was."""
import gzip
import pathlib

import pytest

import program_spans
import serve_cell
import xplane
from program_spans import OUTSIDE
from repro.serve.spans import NAMES

DATA = pathlib.Path(__file__).resolve().parent / "data"

# one decode call [100, 200) and one prefill call of two requests
# [300, 400), with the engine's spans between them (times in ns)
SPANS = [
    (90, 100, "serve.engine.kv_grow"),
    (100, 200, "serve.decode"),
    (102, 130, "serve.decode.prepare"),
    (130, 140, "serve.decode.dispatch"),
    (140, 195, "serve.decode.sync"),
    (210, 230, "serve.engine.detach"),
    (260, 280, "serve.engine.admit"),
    (300, 400, "serve.prefill"),
    (300, 340, "serve.prefill.request"),
    (340, 380, "serve.prefill.request"),
    (380, 400, "serve.prefill.sync"),
]


def test_each_gap_goes_to_the_innermost_span_covering_it():
    got = program_spans.innermost([(110, 120), (190, 198)], SPANS)
    assert got == pytest.approx({"serve.decode.prepare": 10e-9,
                                 "serve.decode.sync": 5e-9,
                                 "serve.decode": 3e-9})


def test_a_gap_is_split_where_the_span_changes():
    got = program_spans.innermost([(120, 150)], SPANS)
    assert got == pytest.approx({"serve.decode.prepare": 10e-9,
                                 "serve.decode.dispatch": 10e-9,
                                 "serve.decode.sync": 10e-9})


def test_idle_under_no_program_span_is_kept_apart():
    got = program_spans.innermost([(195, 305)], SPANS)
    assert got == pytest.approx({"serve.decode": 5e-9,
                                 "serve.engine.detach": 20e-9,
                                 "serve.engine.admit": 20e-9,
                                 "serve.prefill.request": 5e-9,
                                 OUTSIDE: 60e-9})
    assert program_spans.innermost([(0, 50)], SPANS) == pytest.approx(
        {OUTSIDE: 50e-9})


def test_a_span_sharing_its_start_with_its_parent_is_the_inner_one():
    got = program_spans.innermost([(300, 310)], SPANS)
    assert got == pytest.approx({"serve.prefill.request": 10e-9})


def test_the_readings_by_hand():
    # busy [0, 120), [150, 190), [320, 330), [350, 395) in [0, 420)
    ops = {"/device:TPU:0": [(0, 120, "a"), (150, 190, "b"),
                             (320, 330, "c"), (350, 395, "d")]}
    ps = program_spans.reduce(ops, SPANS, 0, 420)
    # idle: [120, 150) in the decode call: 10 prepare + 10 dispatch +
    # 10 sync; [190, 320): 5 sync, 5 decode, 20 detach, 20 admit,
    # 20 request, 60 outside; [330, 350): 10 + 10 request;
    # [395, 420): 5 prefill.sync, 20 outside
    assert ps.idle_under("serve.decode") == pytest.approx(40e-9)
    assert ps.idle_under("serve.prefill") == pytest.approx(45e-9)
    assert ps.idle[OUTSIDE] == pytest.approx(80e-9)
    assert sum(ps.idle.values()) == pytest.approx((420 - 215) * 1e-9)
    assert ps.counts["serve.decode"] == 1
    assert ps.counts["serve.prefill.request"] == 2
    assert program_spans.decode_host_idle_ms(ps) == pytest.approx(40e-6)
    assert program_spans.prefill_host_idle_ms(ps) == pytest.approx(22.5e-6)
    # a window that holds no decode call reads nothing for it
    assert program_spans.decode_host_idle_ms(
        program_spans.reduce(ops, SPANS, 250, 420)) is None

    progs = {"/device:TPU:0": [(0, 120, "jit__step(1)"),
                               (320, 330, "jit_prefill(2)"),
                               (330, 335, "jit_scatter_prefill_pages(3)"),
                               (350, 380, "jit_prefill(2)"),
                               (380, 395, "jit_scatter_prefill_pages(4)")]}
    s = xplane.summarize(ops, progs, [], 0, 420)
    assert program_spans.prefill_device_ms(s) == pytest.approx(
        1e3 * 60e-9 / 2)
    s = xplane.summarize(ops, {"/device:TPU:0": progs["/device:TPU:0"][:1]},
                         [], 0, 420)
    assert program_spans.prefill_device_ms(s) is None


def test_program_runs_inside_the_spans_that_issued_them():
    calls = [(100, 200, "serve.decode"), (300, 400, "serve.decode")]
    runs = [(140, 190, "jit__step(1)"), (350, 401, "jit__step(1)"),
            (250, 260, "jit__step(1)")]
    assert program_spans.inside(runs, calls) == (1, 3)
    assert program_spans.inside([], calls) == (0, 0)


def test_the_clock_offset_is_bounded_by_issue_and_wait():
    """Device runs traced 30 ns before the spans that issued them could
    start them: the offset is at least 30 ns, and at most the least time
    from a run's end to the end of the span that waited for it."""
    issued = [(100, 110, "serve.decode.dispatch"),
              (300, 310, "serve.decode.dispatch")]
    waited = [(110, 200, "serve.decode.sync"),
              (310, 400, "serve.decode.sync")]
    runs = [(75, 150, "jit__step(1)"), (270, 340, "jit__step(1)")]
    assert program_spans.clock_offset(runs, issued, waited) == (30, 50)
    assert program_spans.inside(runs, waited) == (0, 2)
    moved = program_spans.shifted({"/device:TPU:0": runs}, 30)
    assert moved == {"/device:TPU:0": [(105, 180, "jit__step(1)"),
                                       (300, 370, "jit__step(1)")]}
    decode = [(100, 200, "serve.decode"), (300, 400, "serve.decode")]
    assert program_spans.inside(moved["/device:TPU:0"], decode) == (2, 2)


def test_the_benchmarks_naming_of_gaps_is_left_as_it_was():
    """A program span inside ``executor.decode`` that covers a gap more
    than the harness span does takes the gap in the reduction by program
    span, while the harness's own summary, read with its own span names,
    still puts the gap under ``executor.decode``."""
    ops = {"/device:TPU:0": [(0, 100, "a"), (190, 300, "b")]}
    harness = [(95, 205, "executor.decode"), (0, 300, "traced_window")]
    program = [(96, 204, "serve.decode"), (100, 190, "serve.decode.sync")]
    wanted = set(serve_cell.SPANS + (serve_cell.WINDOW_SPAN,))
    s = xplane.summarize_window(
        ops, {}, [sp for sp in harness + program if sp[2] in wanted],
        serve_cell.WINDOW_SPAN)
    assert s.idle_by_span == pytest.approx({"executor.decode": 90e-9})
    assert s.breakdown()["idle_gaps"] == [
        ["executor.decode", pytest.approx(90e-9)]]
    ps = program_spans.reduce(ops, program, 0, 300)
    assert ps.idle == pytest.approx({"serve.decode.sync": 90e-9})


def test_the_recorded_trace_has_all_its_idle_under_no_program_span(tmp_path):
    """The recording predates the program's spans: the reduction by them
    puts the window's whole idle time under no span, the same idle the
    benchmark's summary reads."""
    path = tmp_path / "serve_decode.xplane.pb"
    path.write_bytes(gzip.decompress(
        (DATA / "serve_decode.xplane.pb.gz").read_bytes()))
    names = serve_cell.SPANS + (serve_cell.WINDOW_SPAN,)
    ops, progs, spans = xplane.read_xplane(str(path), names)
    s = xplane.summarize_window(ops, progs, spans, serve_cell.WINDOW_SPAN)
    (lo, hi), = [(a, b) for a, b, n in spans if n == serve_cell.WINDOW_SPAN]
    assert xplane.read_xplane(str(path), NAMES)[2] == []
    ps = program_spans.reduce(ops, [], lo, hi)
    assert list(ps.idle) == [OUTSIDE]
    assert ps.idle[OUTSIDE] == pytest.approx(s.window_s - s.busy_s)
    assert ps.idle[OUTSIDE] == pytest.approx(sum(s.idle_by_span.values()))
