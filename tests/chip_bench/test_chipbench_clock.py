"""The wall-clock adapter, driving the program's engine over a fake
executor on a fake clock."""
import pytest

import clock
from repro.serve import ContinuousServeEngine, ServeRequest


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def now(self):
        return self.t

    def sleep(self, d):
        self.t += d


class FakeExecutor:
    """Ops take fixed time on the fake clock; costs are its own."""

    def __init__(self, clk):
        self.clk = clk

    def prefill(self, reqs):
        t0 = self.clk.t
        self.clk.t += 0.010 * len(reqs)
        return [1] * len(reqs), self.clk.t - t0

    def decode(self, reqs):
        t0 = self.clk.t
        self.clk.t += 0.004
        return [2] * len(reqs), self.clk.t - t0

    def release(self, req):
        pass


def drive(host_gap):
    clk = FakeClock()
    reqs = [ServeRequest(rid=i, prompt_len=8, max_new=5 + i % 3,
                         t_submit=0.05 * i + (0.5 if i > 6 else 0.0))
            for i in range(12)]
    eng = ContinuousServeEngine(4, None)

    def on_op(_el):                 # engine host work before every op
        clk.t += host_gap

    ad = clock.WallClockExecutor(FakeExecutor(clk), lambda: eng.t,
                                 seq_len=lambda r: 8, on_op=on_op,
                                 now=clk.now, sleep=clk.sleep)
    eng.executor = ad
    ad.open()
    eng.run(reqs)
    return reqs, ad


@pytest.mark.parametrize("host_gap", [0.0, 0.0015])
def test_engine_time_equals_wall_time_at_every_op_end(host_gap):
    reqs, ad = drive(host_gap)
    ends = sorted(c.end for c in ad.calls)
    for r in reqs:
        assert r.t_first == pytest.approx(r.token_times[0], abs=1e-12)
        for t in r.token_times:
            assert min(abs(t - e) for e in ends) < 1e-9


@pytest.mark.parametrize("host_gap", [0.0, 0.0015])
def test_no_op_starts_before_it_is_due(host_gap):
    reqs, ad = drive(host_gap)
    for c in ad.calls:
        assert c.start >= c.due - 1e-12
    # idle jumps became real waits: the first op waited for the first
    # arrival (0.0), the gap before the late arrivals was slept through
    assert ad.wake_late and all(x >= 0 for x in ad.wake_late)
    firsts = {r.rid: r.t_first - r.t_submit for r in reqs}
    assert all(v > 0 for v in firsts.values())


def test_host_work_between_ops_is_charged_to_the_next_op():
    _, quiet = drive(0.0)
    _, busy = drive(0.0015)
    dq = [c for c in quiet.calls if c.kind == "decode"]
    db = [c for c in busy.calls if c.kind == "decode"]
    # the executor's own cost is the same; the engine's cost (due to end)
    # carries the host gap
    assert all(c.cost == pytest.approx(0.004) for c in dq + db)
    assert all(c.end - c.due == pytest.approx(0.004, abs=1e-9) for c in dq)
    assert any(c.end - c.due == pytest.approx(0.0055, abs=1e-9) for c in db)


def test_stop_run_ends_the_engine_run():
    clk = FakeClock()
    eng = ContinuousServeEngine(2, None)
    ad = clock.WallClockExecutor(FakeExecutor(clk), lambda: eng.t,
                                 seq_len=lambda r: 8,
                                 should_stop=lambda el: el >= 0.05,
                                 now=clk.now, sleep=clk.sleep)
    eng.executor = ad
    ad.open()
    with pytest.raises(clock.StopRun):
        eng.run([ServeRequest(rid=i, prompt_len=8, max_new=100)
                 for i in range(3)])
    assert ad.calls and ad.calls[-1].start < 0.05
