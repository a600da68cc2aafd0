"""Order statistics, the peak table, and the FLOP and byte counters."""
import math

import numpy as np
import pytest

import flops
import peaks
import stats

SMOLLM = {"num_layers": 30, "d_model": 576, "num_heads": 9,
          "num_kv_heads": 3, "head_dim": 64, "d_ff": 1536,
          "vocab_size": 49152}


@pytest.mark.parametrize("q", [0, 50, 90, 95, 99, 100])
def test_percentile_is_numpy_linear_on_pooled_samples(q):
    xs = np.random.default_rng(3).lognormal(size=257).tolist()
    assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q),
                                                    rel=1e-12)


def test_percentile_counts_failed_requests_as_infinite():
    xs = [0.1] * 94 + [math.inf] * 6          # 6% failed
    assert stats.percentile(xs, 95) == math.inf
    assert stats.percentile([0.1] * 96 + [math.inf] * 4, 95) == 0.1
    assert math.isnan(stats.percentile([], 95))


def test_spread_is_interquartile_over_median():
    vals = [1.0, 2.0, 3.0, 4.0, 5.0, 6.0]
    # statistics.quantiles (exclusive): 1.75, 3.5, 5.25
    assert stats.spread(vals) == pytest.approx((5.25 - 1.75) / 3.5)


def test_peak_table_names_v5e_and_refuses_unknown_chips():
    p = peaks.peak("TPU v5 lite")
    assert (p.bf16_flops, p.hbm_bytes) == (197e12, 819e9)
    assert "TPU v5e" in p.source
    with pytest.raises(KeyError, match="TPU v9"):
        peaks.peak("TPU v9")


def test_matmul_params_of_smollm_by_hand():
    per_layer = 576 * 576 + 2 * 576 * 192 + 576 * 576 + 3 * 576 * 1536
    assert per_layer == 3_538_944
    assert flops.matmul_params(SMOLLM) == 30 * 3_538_944 + 576 * 49152
    assert flops.matmul_params(SMOLLM) == 134_479_872


def test_decode_counts_live_rows_at_their_lengths():
    # two live rows of 100 and 900 tokens
    want = 2 * 2 * 134_479_872 + 4 * 30 * 9 * 64 * 1000
    assert flops.decode_flops(SMOLLM, 2, 1000) == want
    assert flops.paged_attn_flops(SMOLLM, 1000) == 4 * 30 * 9 * 64 * 1000
    # K and V, 3 kv heads of 64 in bf16, per position and layer; q and out
    kv = 30 * 2 * 3 * 64 * 2 * 1000
    qo = 30 * 2 * 2 * 9 * 64 * 2
    assert flops.paged_attn_bytes(SMOLLM, 2, 1000) == kv + qo + 30 * 4 * 2


def test_unfinished_requests_wait_until_the_run_ends():
    import types

    import spec

    def req(t_submit, t_first, t_done, t_admit):
        return types.SimpleNamespace(t_submit=t_submit, t_first=t_first,
                                     t_done=t_done, t_admit=t_admit)

    done = [req(0.1 * i, 0.1 * i + 0.05, 1.0, 0.1 * i) for i in range(19)]
    stuck = req(1.0, 0.0, 0.0, 0.0)
    run = types.SimpleNamespace(kind="serve", elapsed=31.0,
                                requests=done + [stuck])
    for name in ("ttft_p95_s", "queue_wait_p95_s.serve"):
        got = spec.reader(name)(run)
        assert math.isfinite(got)
        # the stuck request waited 30 s; it sits above the p95's rank
        assert got == pytest.approx(stats.percentile(
            [r.t_first - r.t_submit if name == "ttft_p95_s" else 0.0
             for r in done] + [30.0], 95))
