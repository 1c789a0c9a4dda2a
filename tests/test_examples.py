"""Smoke tests: every example's main() runs end-to-end with tiny sizes.

Parity with the reference shipping runnable ``examples/`` alongside the
framework; keeping them executed in CI prevents doc rot.
"""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

EXAMPLES = Path(__file__).resolve().parent.parent / "examples"
sys.path.insert(0, str(EXAMPLES))


def test_asgd_async_example():
    import asgd_async

    res = asgd_async.main(n=2048, d=16, iters=150)
    assert res.accepted == 150
    assert np.isfinite(res.final_objective)


def test_asaga_history_example():
    import asaga_history

    res = asaga_history.main(n=2048, d=16, iters=120)
    assert res.accepted == 120


def test_streaming_example():
    import streaming_pipeline

    out = streaming_pipeline.main(n_batches=4, batch=32, d=8)
    assert len(out) == 4


def test_graph_example():
    import graph_pagerank

    r, cc = graph_pagerank.main(n=200, e=800)
    assert r.sum() == pytest.approx(1.0, abs=1e-3)
    assert cc.shape == (200,)


def test_ring_attention_example():
    import ring_attention_demo

    out = ring_attention_demo.main(t=64, h=4, d=8)
    assert np.isfinite(np.asarray(out)).all()


def test_log_topic_example():
    import log_topic_pipeline

    revenue, replayed = log_topic_pipeline.main(n_events=600, per_batch=200)
    assert len(revenue) == 3          # 600 events / 200 per batch
    assert all(r > 0 for r in revenue)
    assert replayed == []             # committed offsets: nothing replays


def test_network_topic_example(capsys):
    import network_topic_stream

    network_topic_stream.main(n_events=400, per_batch=100)
    out = capsys.readouterr().out
    assert "consumed exactly once" in out


def test_sql_explain_example(capsys):
    import sql_explain_optimizer

    sql_explain_optimizer.main()
    out = capsys.readouterr().out
    assert "Scan(dim)" in out                 # reorder visible
    assert "SetOp(union_all)" in out
    assert out.count("Shared(s)") == 2        # execute-once CTE


def test_sql_example():
    import sql_pipeline

    report = sql_pipeline.main(n=500)
    assert set(report.columns) >= {"region", "revenue", "manager"}
    assert len(report) == 3


def test_sql_ml_pipeline_example():
    import sql_ml_pipeline

    acc = sql_ml_pipeline.main(n=600, quiet=True)
    assert acc > 0.7


def test_sparse_asgd_example():
    import sparse_asgd

    res = sparse_asgd.main(n=512, d=4096, iters=60, quiet=True)
    assert res.accepted == 60


@pytest.mark.slow
def test_staleness_experiment_example():
    import staleness_experiment

    out = staleness_experiment.main(n=1024, d=16, iters=80, coeff=1.0,
                                    quiet=True)
    assert set(out) == {"sync + straggler", "async tau=inf", "async tau=8",
                        "async stale-read-2"}
    for res in out.values():
        assert res.trajectory[-1][1] < res.trajectory[0][1]


def test_streaming_kmeans_example():
    import streaming_kmeans_demo

    model, labels = streaming_kmeans_demo.main(n_batches=6, per_cluster=20)
    # centers tracked the drifting clusters: still well separated
    c = np.sort(model.centers[:, 0])
    assert c[1] - c[0] > 5.0
    assert len(labels) == 6


def test_sql_analytics_example():
    import sql_analytics

    heavy = sql_analytics.main(n=1000, n_users=20)
    totals = np.asarray(heavy["total"])
    assert np.all(totals > 500)
    assert np.all(np.diff(totals) <= 0)  # ORDER BY total DESC


@pytest.mark.parametrize("chips", [4, 1])
def test_engine_sketch_reproduces_the_ledgers_cells(chips, capsys):
    """The sketch fed the medians of the ledger's PR 40 lines (no chip here:
    the numbers it is held to are the record's, and the bounds are wide,
    a sketch's).  Four chips (`mnist8m-f32-asgd.steady`: 708.25 updates/s,
    `device_idle` 28.1%): `compute` 8.31 ms = `task.dispatch` 1.40 + the
    inbox and self time 1.9 + `task.device_wait` 4.72 + `result.queue`,
    a step of 4.2414 ms, 0.3 ms from its end to the worker's return.  One
    chip (`mnist8m-asgd.steady`: a step of 2.26 ms, `task.dispatch` 1.10):
    eight workers on one queue never leave the chip empty."""
    import engine_sketch

    if chips == 4:
        for seed in range(3):
            got = engine_sketch.simulate(
                workers=8, chips=4, bucket_ratio=0.7, step_ms=4.2414,
                inbox_ms=1.9, dispatch_ms=1.40, notice_ms=0.3, seed=seed)
            assert got["updates_per_s"] == pytest.approx(708.25, rel=0.10)
            assert abs(got["device_idle"] - 28.1) <= 5.0
            assert 5.0 <= got["inflight_mean"] <= 8.0
    else:
        got = engine_sketch.simulate(
            workers=8, chips=1, step_ms=2.26, inbox_ms=1.5, dispatch_ms=1.10)
        assert got["chip_starved"] < 0.1
        assert got["device_idle"] < 1.0
        assert got["updates_per_s"] == pytest.approx(1e3 / 2.26, rel=0.01)
    assert engine_sketch.main(["--chips", str(chips), "--seeds", "2",
                               "--seconds", "2"]) == 0
    line = json.loads(capsys.readouterr().out)
    assert set(line) == set(got) and all(lo <= hi for lo, hi in line.values())
