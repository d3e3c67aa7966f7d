"""The serving cells' window arithmetic on hand-made request records."""

import pytest

from bench.drivers import serve


def rec(i, first, last, n, status="done", due=None, sent=None, gen=None):
    step = (last - first) / max(n - 1, 1)
    return {"id": i, "gen": gen or n, "plen": 4, "due": due,
            "sent": sent if sent is not None else first - 0.1,
            "first": first, "last": last, "tokens": list(range(n)),
            "deltas": [[first + k * step, 1] for k in range(n)],
            "status": status, "done_tokens": list(range(n))}


def test_closed_loop_rate_counts_tokens_inside_the_window():
    recs = [rec(0, 1.0, 2.0, 11), rec(1, 9.0, 11.0, 21)]
    m = serve.window_metrics(recs, 0.0, 10.0, {"loop": "closed"})
    # 11 tokens of request 0 and the 11 of request 1 up to t = 10
    assert m["out_tok_per_s"] == pytest.approx(22 / 10)
    # only request 0 finished in the window: 1.0 s over 10 gaps
    assert m["tpot_p95_ms"] == pytest.approx(100.0)


def test_open_loop_ttft_is_timed_from_when_each_request_was_due():
    recs = [rec(i, 1.0 + i + 0.05 * (i + 1), 1.5 + i, 5, due=1.0 + i,
                sent=1.0 + i + 0.001) for i in range(20)]
    missing = rec(20, 5.0, 5.0, 1, status="abandoned", due=5.0, sent=5.0)
    missing.update(first=None, last=None, tokens=[], deltas=[])
    recs.append(missing)
    mix = {"loop": "open", "grace": 2.0}
    m = serve.window_metrics(recs, 0.0, 10.0, mix)
    ttft = sorted([50.0 * (i + 1) for i in range(20)] +
                  [(12.0 - 5.0) * 1e3])       # the missing one waited
    assert m["ttft_p95_ms"] == pytest.approx(
        serve.percentile(ttft, 95))
    assert m["_late_ms"][0] == pytest.approx(1.0)


def test_sample_holds_the_longest_answer_and_depends_on_the_seed():
    done = [rec(i, 0.0, 1.0, 5 + i % 7) for i in range(40)]
    a = serve.sample_requests(done, 3, 6)
    b = serve.sample_requests(done, 3, 6)
    c = serve.sample_requests(done, 4, 6)
    assert [r["id"] for r in a] == [r["id"] for r in b]
    assert [r["id"] for r in a] != [r["id"] for r in c]
    assert a[0]["gen"] == max(r["gen"] for r in done)
    assert len({r["id"] for r in a}) == 6
