"""The serving cells' end-to-end numbers from a window's host times."""
import numpy as np
import pytest

import serve_loop


def _batch(start, first, gap, n, rows=2, length=8):
    times = first + gap * np.arange(n)
    return {"length": length, "prompt": np.zeros((rows, length), np.int32), "t_start": start,
            "tokens": np.zeros((rows, n), np.int32), "times": times}


def test_rates_and_tails():
    # two finished batches and one cut by the window's end; gaps 50/100/200 ms
    win = {"t0": 0.0, "window_s": 10.0, "gen": 8, "batches": [
        _batch(0.0, 0.5, 0.05, 8), _batch(1.0, 1.2, 0.1, 8), _batch(3.0, 3.1, 0.2, 3)]}
    out = serve_loop.e2e(win)
    assert out["gen_tok_s"] == pytest.approx((16 + 16 + 6) / 10.0)
    # every request counts: the cut batch's two tokens span 0.4 s >= 0.25 s
    assert out["tpot_p95_ms"] == pytest.approx(np.percentile([50, 50, 100, 100, 200, 200], 95))


def test_span_under_a_quarter_second_is_not_read():
    win = {"t0": 0.0, "window_s": 1.0, "gen": 8, "batches": [_batch(0.0, 0.1, 0.01, 8)]}
    assert "tpot_p95_ms" not in serve_loop.e2e(win)


def test_sample_holds_every_batch_and_the_longest():
    # batches of prompt 8 and 32 finished (4 tokens), one of 16 still running (2)
    win = {"gen": 4, "batches": [_batch(0.0, 0.1, 0.1, 4, rows=3, length=n)
                                 for n in (8, 32)] + [_batch(1.0, 1.1, 0.1, 2, rows=3, length=16)]}
    reqs = serve_loop.requests(win)
    assert len(reqs) == 9
    a = serve_loop.sample(reqs, 5, seed=7)
    assert len(a) == 5
    assert sorted({len(p) for p, _ in a}) == [8, 16, 32]
    assert max(len(p) + len(t) for p, t in a) == 36
    assert [len(t) for p, t in a if len(p) == 16] == [2] * sum(len(p) == 16 for p, _ in a)
    b = serve_loop.sample(reqs, 5, seed=7)
    assert [len(p) for p, _ in a] == [len(p) for p, _ in b]
    assert len(serve_loop.sample(reqs, 2, seed=7)) == 3  # one of each batch at the least
