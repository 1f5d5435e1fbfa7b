"""Percentile, TTFT, TPOT and rate arithmetic on hand-made samples."""
import pytest

from benchmarks.harness import stats


def test_percentile_interpolates_between_order_statistics():
    xs = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert stats.percentile(xs, 0) == 10.0 and stats.percentile(xs, 100) == 50.0
    assert stats.percentile(xs, 50) == 30.0 == stats.median(xs)
    assert stats.percentile(xs, 95) == pytest.approx(48.0)  # 40 + 0.8 * 10
    assert stats.percentile([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def rec(due, sent, packets, n=None):
    toks = sum(k for _, k in packets)
    return {"due": due, "sent": sent, "first": packets[0][0] if packets else None,
            "last": packets[-1][0] if packets else None, "n_tokens": toks if n is None else n,
            "packets": packets}


def test_ttft_counts_from_due_in_an_open_loop_and_from_send_in_a_closed_one():
    r = rec(due=100.0, sent=100.4, packets=[(101.0, 1)])
    assert stats.ttft_s(r, "open") == pytest.approx(1.0)
    assert stats.ttft_s(r, "closed") == pytest.approx(0.6)
    assert stats.ttft_s(rec(0.0, 0.0, []), "open") is None


def test_tpot_is_robust_to_multi_token_packets():
    # 1 token at t=1.0, then a speculative burst of 3 at t=1.3, then 1 at t=1.4
    r = rec(0.0, 0.0, [(1.0, 1), (1.3, 3), (1.4, 1)])
    assert stats.tpot_s(r) == pytest.approx(0.4 / 4)
    assert stats.tpot_s(rec(0.0, 0.0, [(1.0, 1)])) is None


def test_rate_counts_only_tokens_inside_the_window():
    rs = [rec(0.0, 0.0, [(9.9, 1), (10.0, 2), (19.99, 4), (20.0, 8)])]
    assert stats.tokens_in_window(rs, 10.0, 20.0) == 6
    out = stats.end_to_end(rs, loop="open", t0=10.0, window_s=10.0)
    assert out["tokens_per_s"] == pytest.approx(0.6)
    assert out["ttft_p50_ms"] == pytest.approx(9900.0) and out["ttft_samples"] == 1


def test_a_ramp_request_gives_tokens_to_the_rate_and_no_latency_sample():
    ramp = {**rec(-5.0, -5.0, [(-1.0, 1), (11.0, 3)]), "ramp": True}
    mine = rec(10.5, 10.5, [(12.0, 1), (13.0, 1)])
    out = stats.end_to_end([ramp, mine], loop="closed", t0=10.0, window_s=10.0)
    assert out["tokens_per_s"] == pytest.approx(0.5)      # 3 of the ramp's + 2 of the window's
    assert out["ttft_samples"] == 1 and out["tpot_samples"] == 1
    assert out["ttft_p50_ms"] == pytest.approx(1500.0)
