"""Continuous telemetry plane (ISSUE 7): time-series metrics, Prometheus
exposition, convergence history, and SLO health.

Acceptance: ``/metrics`` on a real PS process, a real serving replica
process, and a real frontend process passes the strict Prometheus
text-format parser; a real two-process DCN run (PS child + this process's
workers with ``async.convergence.sample`` on) shows a non-empty
loss-vs-wallclock curve under ``/api/status`` ``convergence``; and a
freshness-lag SLO transitions firing -> ok when a killed replica
recovers.

Satellites covered here: the counter-registration audit (every
module-level ``*_totals`` provider either registered in
``metrics/registry.py`` or explicitly exempted, live-UI baselines driven
by the registry), k8s scrape-annotation rendering, and telemetry-plane
chaos (both endpoints stay available, valid, and monotonic while a
worker is SIGKILLed and a seeded fault schedule fires).
"""

import importlib
import json
import math
import os
import pkgutil
import signal
import socket as socket_mod
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path

import numpy as np
import pytest

from asyncframework_tpu.conf import AsyncConf, global_conf, set_global_conf
from asyncframework_tpu.data.sharded import ShardedDataset
from asyncframework_tpu.metrics import registry, reset_totals, slo
from asyncframework_tpu.metrics import prom
from asyncframework_tpu.metrics import timeseries as ts
from asyncframework_tpu.metrics import top
from asyncframework_tpu.metrics.live import (
    LiveStateListener,
    LiveUIServer,
    start_telemetry_from_conf,
)
from asyncframework_tpu.net import faults
from asyncframework_tpu.net.faults import (
    CONNECT_OP,
    CONNECT_REFUSED,
    CUT_MID_FRAME,
    DROP_REPLY,
    FaultSchedule,
    STALL_READ,
)
from asyncframework_tpu.net.retry import reset_breakers
from asyncframework_tpu.parallel import ps_dcn
from asyncframework_tpu.serving import ServingFrontend
from asyncframework_tpu.serving import metrics as smetrics
from asyncframework_tpu.solvers import SolverConfig
from asyncframework_tpu.utils.clock import ManualClock

pytestmark = pytest.mark.telemetry

REPO = Path(__file__).parent.parent
CHILD = Path(__file__).parent / "ps_dcn_child.py"
CHAOS_SEED = int(os.environ.get("ASYNC_CHAOS_SEED", "7"))


def make_cfg(**kw):
    defaults = dict(
        num_workers=8, num_iterations=300, gamma=1.2, taw=2**31 - 1,
        batch_rate=0.3, bucket_ratio=0.5, printer_freq=50, seed=42,
        calibration_iters=20, run_timeout_s=120.0,
    )
    defaults.update(kw)
    return SolverConfig(**defaults)


@pytest.fixture(autouse=True)
def _clean_state():
    """Telemetry state is process-global (store, convergence history,
    SLO engine, sampler thread, counter families) -- no test may inherit
    or leak any of it.  A fresh conf is INSTALLED (global_conf() hands
    out throwaways otherwise, so a test's .set() would vanish)."""
    set_global_conf(AsyncConf())
    ts.stop_sampler()
    reset_totals()
    reset_breakers()
    faults.clear()
    yield
    ts.stop_sampler()
    set_global_conf(None)
    reset_totals()
    reset_breakers()
    faults.clear()


def _get(url: str, timeout: float = 3.0):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, r.read().decode()


def _get_json(url: str, timeout: float = 3.0):
    status, body = _get(url, timeout=timeout)
    return status, json.loads(body)


# ----------------------------------------------------------- TimeSeriesStore
class TestTimeSeriesStore:
    def test_record_window_agg_and_percentiles(self):
        clk = ManualClock()
        st = ts.TimeSeriesStore(capacity=64, clock=clk)
        for v in (1.0, 2.0, 3.0, 4.0):
            clk.advance(1000)
            st.record("x", v)
        agg = st.window_agg("x", window_s=10.0)
        assert agg["count"] == 4
        assert agg["min"] == 1.0 and agg["max"] == 4.0
        assert agg["mean"] == 2.5 and agg["last"] == 4.0
        # trailing window restricts (cutoff inclusive: t >= now - w)
        agg2 = st.window_agg("x", window_s=1.5)
        assert agg2["count"] == 2 and agg2["min"] == 3.0

    def test_ring_bounded_and_evictions_counted(self):
        st = ts.TimeSeriesStore(capacity=8)
        for i in range(20):
            st.record("s", float(i))
        assert len(st.series("s")) == 8
        assert st.series("s")[0][1] == 12.0  # oldest evicted first
        assert st.evicted == 12
        assert st.samples_recorded == 20

    def test_rate_derivation_and_reset_clamp(self):
        clk = ManualClock()
        st = ts.TimeSeriesStore(capacity=64, clock=clk)
        for v in (0, 50, 100):
            st.record("ctr", float(v))
            clk.advance(1000)
        assert st.rate("ctr", window_s=60.0) == pytest.approx(50.0)
        # counter reset mid-window reads as a stall, never negative
        st.record("ctr", 0.0)
        assert st.rate("ctr", window_s=60.0) == 0.0

    def test_rate_needs_two_spanning_samples(self):
        st = ts.TimeSeriesStore(capacity=8)
        assert st.rate("nope", 10.0) is None
        st.record("one", 1.0)
        assert st.rate("one", 10.0) is None

    def test_record_flat_skips_non_numerics(self):
        st = ts.TimeSeriesStore(capacity=8)
        st.record_flat("f", {"a": 1, "b": True, "c": "x", "d": 2.5,
                             "e": None})
        assert sorted(st.names()) == ["f.a", "f.d"]

    def test_dump_summary_clear(self):
        st = ts.TimeSeriesStore(capacity=8)
        st.record("a", 1.0)
        st.record("b", 2.0)
        dump = st.dump()
        assert set(dump) == {"a", "b"}
        assert dump["a"][0][1] == 1.0
        s = st.summary()
        assert s["series"] == 2 and s["last"]["b"] == 2.0
        st.clear()
        assert st.names() == [] and st.samples_recorded == 0


# ------------------------------------------------------- ConvergenceHistory
class TestConvergenceHistory:
    def test_stride_compaction_keeps_full_span(self):
        h = ts.ConvergenceHistory(capacity=32)
        for k in range(500):
            h.add(float(k), k, loss=1.0 / (k + 1))
        assert h.offered == 500
        assert h.compactions >= 1
        assert h._stride > 1
        curves = h.curves()
        lw = curves["loss_vs_wallclock"]
        assert lw, "curve empty after compaction"
        # both the start and the end of the run survive compaction
        assert lw[0][0] == 0.0
        assert lw[-1][0] >= 400.0
        assert len(h._pts) <= h.capacity

    def test_non_finite_losses_do_not_poison_the_curve(self):
        h = ts.ConvergenceHistory()
        h.add(0.0, 0, loss=float("nan"))
        h.add(1.0, 1, loss=float("inf"))
        h.add(2.0, 2, loss=0.5)
        lw = h.curves()["loss_vs_wallclock"]
        assert lw == [[2.0, 0.5]]
        assert h.summary()["best_loss"] == 0.5

    def test_curves_thinned_to_max_points(self):
        h = ts.ConvergenceHistory(capacity=4096)
        for k in range(1000):
            h.add(float(k), k, loss=float(k))
        for curve in h.curves(max_points=50).values():
            assert len(curve) <= 50

    def test_summary_slope_and_loss_at(self):
        h = ts.ConvergenceHistory()
        for k in range(100):
            h.add(k * 100.0, k, loss=10.0 - k * 0.05)
        s = h.summary()
        assert s["first_loss"] == 10.0
        assert s["last_loss"] == pytest.approx(10.0 - 99 * 0.05)
        assert s["slope_per_s"] < 0  # converging
        la = s["loss_at"]
        assert la["100pct"] == s["last_loss"]
        assert la["25pct"] > la["50pct"] > la["100pct"]

    def test_loss_at_fractions_empty_and_slope_degenerate(self):
        assert ts.loss_at_fractions([]) == {
            "25pct": None, "50pct": None, "100pct": None}
        assert ts.loss_slope([]) is None
        assert ts.loss_slope([(0.0, 1.0)]) is None

    def test_loss_slope_two_point_fallback(self):
        # the trailing-half slice of a 2-point curve leaves one point;
        # the fallback must reach back to the FULL curve's last two, not
        # return None for a perfectly computable slope
        s = ts.loss_slope([(0.0, 2.0), (1000.0, 1.0)])
        assert s == pytest.approx(-1.0)  # -1 loss unit per second
        # 3 points: trailing half is the last 2, slope from those alone
        s = ts.loss_slope([(0.0, 9.0), (1000.0, 2.0), (2000.0, 1.0)])
        assert s == pytest.approx(-1.0)

    def test_buffer_wire_bound_order_and_merge_back(self):
        buf = ts.ConvergenceBuffer(capacity=64)
        for k in range(40):
            buf.add(k, 0.1 * k, 1.0)
        wire = buf.take_wire()
        assert len(wire) == ts.ConvergenceBuffer.MAX_WIRE
        assert wire[0][0] == 0  # FIFO
        # a terminally failed push merges its samples back, order kept
        buf.merge_back(wire)
        again = buf.take_wire()
        assert again == wire

    def test_buffer_bounded_drops_counted(self):
        buf = ts.ConvergenceBuffer(capacity=8)
        for k in range(20):
            buf.add(k, None, None)
        assert buf.dropped == 12
        assert len(buf.take_wire()) == 8

    def test_fold_trajectory(self):
        ts.fold_trajectory([(0.0, 2.0), (500.0, 1.0)])
        s = ts.convergence().summary()
        assert s["samples"] == 2 and s["last_loss"] == 1.0


# ------------------------------------------------------------- SLO engine
class TestSLORules:
    def test_grammar_full_and_defaults(self):
        rules = slo.parse_rules(
            "a: p95(serving.freshness_lag_ms) < 2000 over 15s for 2s; "
            "b: rate(ps.accepted) > 0.5"
        )
        assert rules[0].window_s == 15.0 and rules[0].for_s == 2.0
        assert rules[1].window_s == 30.0 and rules[1].for_s == 0.0
        assert rules[1].agg == "rate" and rules[1].op == ">"

    def test_grammar_rejects_garbage(self):
        with pytest.raises(ValueError, match="unparseable"):
            slo.parse_rules("what even is this")
        with pytest.raises(ValueError, match="unknown aggregate"):
            slo.parse_rules("a: p42(x) < 1")
        with pytest.raises(ValueError, match="duplicate"):
            slo.parse_rules("a: last(x) < 1; a: last(y) < 2")

    def test_unless_gate_clause_parses_and_round_trips(self):
        rules = slo.parse_rules(
            "floor: rate(ps.accepted) > 0.5 over 30s for 10s "
            "unless ps.done"
        )
        assert rules[0].unless_series == "ps.done"
        assert slo.parse_rules(rules[0].spec())[0] == rules[0]
        assert slo.parse_rules("a: last(x) < 1")[0].unless_series is None

    def test_default_conf_rule_set_parses(self):
        from asyncframework_tpu.conf import SLO_RULES

        rules = slo.parse_rules(str(global_conf().get(SLO_RULES)))
        by_name = {r.name: r for r in rules}
        assert {"serve_freshness", "predict_p99", "staleness_ms",
                "updates_floor"} <= set(by_name)
        # the updates/s floor stands down once the run is DONE
        assert by_name["updates_floor"].unless_series == "ps.done"


def _engine_on_manual_clock(rule_text):
    clk = ManualClock()
    st = ts.TimeSeriesStore(capacity=256, clock=clk)
    eng = slo.SLOEngine(slo.parse_rules(rule_text), store=st,
                        now_fn=lambda: clk.now_ms() / 1e3)
    return clk, st, eng


class TestSLOStateMachine:
    RULE = "lag: p95(x) < 100 over 10s for 3s"

    def test_burn_ok_pending_firing_recovery(self):
        clk, st, eng = _engine_on_manual_clock(self.RULE)

        def tick(v):
            clk.advance(1000)
            st.record("x", v)
            return eng.evaluate()["lag"]

        for _ in range(10):
            view = tick(50.0)
        assert view["state"] == slo.OK
        # violation shorter than the burn duration stays pending
        view = tick(500.0)
        assert view["state"] == slo.PENDING
        view = tick(500.0)
        assert view["state"] == slo.PENDING
        # ... and past it, fires, with the burn duration reported
        view = tick(500.0)
        view = tick(500.0)
        assert view["state"] == slo.FIRING
        assert view["burn_s"] >= 3.0
        assert view["fired"] == 1
        # recovery: the window must actually drain below the threshold
        for _ in range(12):
            view = tick(10.0)
        assert view["state"] == slo.OK
        assert view["recovered"] == 1
        assert view["burn_s"] == 0.0

    def test_transient_spike_never_fires(self):
        # `last` aggregate: one bad sample violates for ONE tick only --
        # shorter than the burn duration, so the rule peaks at pending
        # (a p95 window would legitimately hold a spike violated longer)
        clk, st, eng = _engine_on_manual_clock(
            "lag: last(x) < 100 over 10s for 3s"
        )

        def tick(v):
            clk.advance(1000)
            st.record("x", v)
            return eng.evaluate()["lag"]

        for _ in range(5):
            tick(50.0)
        assert tick(500.0)["state"] == slo.PENDING  # the spike
        states = [tick(50.0)["state"] for _ in range(12)]
        assert slo.FIRING not in states
        assert states[-1] == slo.OK

    def test_no_data_never_fires_but_firing_survives_silence(self):
        clk, st, eng = _engine_on_manual_clock(self.RULE)
        assert eng.evaluate()["lag"]["state"] == slo.NO_DATA
        # burn into firing
        for _ in range(6):
            clk.advance(1000)
            st.record("x", 900.0)
            eng.evaluate()
        assert eng.evaluate()["lag"]["state"] == slo.FIRING
        # the series goes silent (window drains empty): the alarm HOLDS
        clk.advance(60_000)
        assert eng.evaluate()["lag"]["state"] == slo.FIRING

    def test_unless_gate_stands_down_even_a_firing_rule(self):
        """A finished run (ps.done=1) must not leave the updates/s floor
        wedged firing: the gate clears the state, unlike silence."""
        clk, st, eng = _engine_on_manual_clock(
            "floor: rate(c) > 0.5 over 10s for 2s unless done"
        )
        for _ in range(6):  # a stalled counter: rate 0 -> burns to firing
            clk.advance(1000)
            st.record("c", 10.0)
            eng.evaluate()
        assert eng.evaluate()["floor"]["state"] == slo.FIRING
        clk.advance(1000)
        st.record("done", 1.0)
        view = eng.evaluate()["floor"]
        assert view["state"] == slo.NO_DATA
        assert view["unless"] == "done"
        assert view["burn_s"] == 0.0

    def test_health_rollup_and_reset(self):
        clk, st, eng = _engine_on_manual_clock(
            "a: last(x) < 100 over 10s; b: last(y) < 100 over 10s"
        )
        h = eng.health()
        assert h["state"] == slo.OK  # pure no_data = healthy idle
        clk.advance(1000)
        st.record("x", 500.0)
        h = eng.health()
        assert h["state"] == slo.FIRING  # for_s=0: violated = firing
        assert h["firing"] == ["a"]
        assert h["rules"]["b"]["state"] == slo.NO_DATA
        eng.reset()
        assert eng._states["a"].fired_count == 0


# -------------------------------------------------- freshness-lag SLO signal
class TestFreshnessLagSignal:
    def test_idle_lull_holds_failing_demand_grows(self):
        """The SLO input must distinguish "nobody is asking" (healthy
        replicas, a traffic lull -- lag holds at the last served value)
        from "demand is failing" (dead or all-UNHEALTHY replicas -- lag
        grows with the failing attempts), or the default serve_freshness
        rule false-fires on every low-QPS service."""
        assert smetrics.freshness_lag_ms() is None  # idle-from-birth
        smetrics.observe_predict("r:1", 2.0, 1, 40.0, 7)
        time.sleep(0.05)
        # no attempts since the success: held, not grown by wall time
        assert smetrics.freshness_lag_ms() == pytest.approx(40.0)
        # a failing RPC attempt advances the demand clock
        smetrics.observe_predict("r:1", 0.0, 0, 0.0, 0, ok=False)
        lag = smetrics.freshness_lag_ms()
        assert lag >= 40.0 + 50.0 * 0.9
        # ... as does an UNHEALTHY reject (alive-but-stale outage)
        time.sleep(0.05)
        smetrics.note_attempt()
        assert smetrics.freshness_lag_ms() >= lag + 50.0 * 0.9
        # recovery: next success re-anchors to the served lag
        smetrics.observe_predict("r:1", 2.0, 1, 41.0, 8)
        assert smetrics.freshness_lag_ms() == pytest.approx(41.0)


# ------------------------------------------------------ Prometheus exposition
class TestPromExposition:
    def test_render_passes_strict_parser_with_labels(self):
        smetrics.observe_predict("r:1", 2.5, 1, 40.0, 7)
        ts.convergence().add(100.0, 3, loss=0.25, grad_norm=1.5)
        body = prom.render({"role": "test", "run_id": "rid1"})
        parsed = prom.parse_exposition(body)
        assert parsed, "empty exposition"
        key = ("async_process_info", (("role", "test"), ("run_id", "rid1")))
        assert parsed[key] == 1.0
        # registered counter families appear with the _total suffix
        assert any(name.startswith("async_serving_") and
                   name.endswith("_total") for (name, _l) in parsed)
        # convergence gauges
        assert any(name == "async_convergence_loss"
                   for (name, _l) in parsed)
        # SLO states for every conf rule, coded
        slo_states = {dict(l)["rule"]: v for (n, l), v in parsed.items()
                      if n == "async_slo_state"}
        assert "updates_floor" in slo_states
        assert set(slo_states.values()) <= {-1.0, 0.0, 1.0, 2.0}

    def test_metric_name_sanitization(self):
        assert prom._metric_name("async", "net_bytes", "sent.PULL",
                                 "total") == "async_net_bytes_sent_PULL_total"
        assert prom._metric_name("9bad").startswith("_")

    def test_high_water_keys_are_gauges_not_counters(self):
        ps_dcn._pl_fold({"inflight_max": 3, "prefetch_hits": 5})
        body = prom.render({"role": "t"})
        assert "async_pipeline_inflight_max " in body.replace("{", " {") \
            or "async_pipeline_inflight_max{" in body
        assert "async_pipeline_inflight_max_total" not in body
        assert "async_pipeline_prefetch_hits_total" in body

    def test_render_groups_metrics_contiguously(self):
        """The exposition format requires all lines of one metric to be
        a single uninterrupted group; the SLO loop emits state/value/
        fired per RULE, so the writer must regroup per metric."""
        global_conf().set(
            "async.slo.rules",
            "a: p95(serving.freshness_lag_ms) < 2000; "
            "b: p99(serving.predict_p99_ms) < 500; "
            "c: max(ps.staleness_ms) < 1500",
        )
        slo.reset_engine()
        ts.store().record("serving.freshness_lag_ms", 10.0)
        ts.store().record("serving.predict_p99_ms", 10.0)
        body = prom.render({"role": "t"})
        seen, closed = [], set()
        for line in body.splitlines():
            name = line.split(None, 3)[2] if line.startswith("#") \
                else line.split("{")[0].split()[0]
            if seen and seen[-1] == name:
                continue
            assert name not in closed, f"{name} group interrupted"
            if seen:
                closed.add(seen[-1])
            seen.append(name)
        # and the multi-rule SLO gauges really did exercise regrouping
        states = [n for n in seen if n == "async_slo_state"]
        assert states == ["async_slo_state"]

    def test_parser_rejects_interleaved_groups(self):
        with pytest.raises(ValueError, match="interleaved"):
            prom.parse_exposition(
                "# TYPE x gauge\nx 1\n# TYPE y gauge\ny 1\nx 2\n")

    def test_parser_rejects_malformed(self):
        with pytest.raises(ValueError, match="undeclared"):
            prom.parse_exposition("orphan_sample 1.0\n")
        with pytest.raises(ValueError, match="bad TYPE"):
            prom.parse_exposition("# TYPE x flavor\nx 1\n")
        with pytest.raises(ValueError, match="bad value"):
            prom.parse_exposition("# TYPE x gauge\nx lots\n")
        with pytest.raises(ValueError, match="bad label"):
            prom.parse_exposition('# TYPE x gauge\nx{a=unquoted} 1\n')
        with pytest.raises(ValueError, match="bad comment"):
            prom.parse_exposition("# WAT x\n")

    def test_large_counters_render_full_precision(self):
        """'%g' would quantize a 10 MB byte counter to 6 significant
        digits, corrupting scrape-side rate() deltas."""
        big = 10_485_763
        ps_dcn._pl_fold({"prefetch_hits": big})
        body = prom.render({"role": "t"})
        parsed = prom.parse_exposition(body)
        vals = [v for (n, _l), v in parsed.items()
                if n == "async_pipeline_prefetch_hits_total"]
        assert vals == [float(big)]
        assert str(big) in body  # printed exact, not 1.04858e+07

    def test_label_escaping_round_trips(self):
        body = prom.render({"role": 'we"ird\\label', "run_id": "r"})
        parsed = prom.parse_exposition(body)
        assert parsed  # strict parse survived the escaped labels


# --------------------------------------------- registry + audit (satellite)
#: providers that legitimately live OUTSIDE the registry, with the reason
AUDIT_EXEMPT = {
    # the registry's own aggregate view (the consumer, not a producer)
    ("asyncframework_tpu.metrics.registry", "all_totals"),
    # aggregated INTO the registered `net` family by net_totals()
    ("asyncframework_tpu.net.retry", "retry_totals"),
}


def _walk_totals_providers():
    """Every public module-level ``*_totals`` callable in the package
    (the audit surface).  Import failures are skipped -- a module the
    suite cannot import cannot leak counters into this process either."""
    import asyncframework_tpu

    providers = {}
    for info in pkgutil.walk_packages(asyncframework_tpu.__path__,
                                      prefix="asyncframework_tpu."):
        if ".native" in info.name:
            continue
        try:
            mod = importlib.import_module(info.name)
        except Exception:
            continue
        for attr in dir(mod):
            if (attr.startswith("_") or attr.startswith("reset")
                    or not attr.endswith("_totals")):
                continue
            fn = getattr(mod, attr)
            if callable(fn):
                providers[(info.name, attr)] = fn
    return providers


class TestRegistryAudit:
    def test_every_totals_provider_is_registered_or_exempt(self):
        """THE audit (satellite 1): a counter family added anywhere in the
        package without a registry entry -- the bug class where a second
        run inherits counts because reset/baseline enumerations forgot it
        -- fails this test by name."""
        registered = set()
        for fam in registry.families().values():
            registered.add(id(fam._resolve(fam.totals_attr)))
        exempt_ids = set()
        for (mod_name, attr) in AUDIT_EXEMPT:
            exempt_ids.add(id(getattr(importlib.import_module(mod_name),
                                      attr)))
        strays = [
            site for site, fn in _walk_totals_providers().items()
            if id(fn) not in registered and id(fn) not in exempt_ids
        ]
        assert not strays, (
            f"unregistered *_totals providers {strays}: add a "
            f"CounterFamily to metrics/registry.py (wires reset_totals, "
            f"live-UI baselines, the sampler, and /metrics at once) or "
            f"an explicit AUDIT_EXEMPT entry with a reason"
        )

    def test_families_are_flat_numeric_and_reset_zeroes(self):
        ps_dcn._pl_fold({"prefetch_hits": 5, "inflight_max": 2})
        smetrics.bump("predicts", 3)
        for name, fam in registry.families().items():
            tot = fam.totals()
            assert isinstance(tot, dict), name
            for k, v in tot.items():
                assert isinstance(k, str), (name, k)
                assert isinstance(v, (int, float)), (name, k, v)
        registry.reset_all()
        for name, fam in registry.families().items():
            assert all(v == 0 for v in fam.totals().values()), (
                f"family {name!r} not zeroed by reset_all"
            )

    def test_live_ui_baselines_cover_every_baseline_family(self):
        """Satellite 1b: the dashboard's per-run delta baselines are
        registry-driven, so a new family cannot be forgotten there."""
        listener = LiveStateListener(2)
        want = {n for n, f in registry.families().items() if f.baseline}
        assert set(listener._bases) == want

    def test_reset_totals_resets_whole_telemetry_plane(self):
        ts.store().record("x", 1.0)
        ts.convergence().add(0.0, 0, loss=1.0)
        eng_before = slo.engine()
        reset_totals()
        assert ts.store().names() == []
        assert ts.convergence().summary()["samples"] == 0
        assert slo.engine() is not eng_before  # rebuilt from conf

    def test_high_water_keys_declared_exist(self):
        fam = registry.families()["pipeline"]
        assert "inflight_max" in fam.high_water


# ------------------------------------------------------- sampler + sources
class TestSampler:
    def test_sample_once_records_families_and_sources(self):
        ps_dcn._pl_fold({"prefetch_hits": 2})
        st = ts.TimeSeriesStore(capacity=32)
        ts.sample_once(st)
        names = set(st.names())
        assert "pipeline.prefetch_hits" in names
        assert "timeseries.ticks" in names

    def test_dynamic_source_register_unregister_identity(self):
        src_a = lambda: {"v": 1}  # noqa: E731
        src_b = lambda: {"v": 2}  # noqa: E731
        ts.register_source("dyn", src_a)
        ts.register_source("dyn", src_b)  # last registration wins
        ts.unregister_source("dyn", src_a)  # stale unhook: must not land
        st = ts.TimeSeriesStore(capacity=8)
        ts.sample_once(st)
        assert st.last("dyn.v") == 2.0
        ts.unregister_source("dyn", src_b)
        st2 = ts.TimeSeriesStore(capacity=8)
        ts.sample_once(st2)
        assert st2.last("dyn.v") is None

    def test_failing_family_does_not_kill_the_tick(self):
        """A counter family whose provider raises (e.g. a lazy import
        failing in a lean process) must not kill the sampler thread."""
        from asyncframework_tpu.metrics.registry import (
            _FAMILIES,
            CounterFamily,
            _register,
        )

        _register(CounterFamily("badfam", "no.such.module",
                                "x_totals", "reset_x"))
        try:
            st = ts.TimeSeriesStore(capacity=8)
            ts.sample_once(st)  # must not raise
            assert "timeseries.ticks" in st.names()
        finally:
            _FAMILIES.pop("badfam", None)

    def test_failing_source_does_not_kill_the_tick(self):
        def boom():
            raise RuntimeError("telemetry must not crash the plane")

        ts.register_source("boom", boom)
        try:
            st = ts.TimeSeriesStore(capacity=8)
            ts.sample_once(st)  # must not raise
            assert "timeseries.ticks" in st.names()
        finally:
            ts.unregister_source("boom")

    def test_interval_nonpositive_disables_sampler(self):
        global_conf().set("async.metrics.interval.s", 0)
        ts.ensure_started()
        assert not ts.sampler_running()

    def test_sampler_thread_ticks_and_stops(self):
        global_conf().set("async.metrics.interval.s", 0.02)
        ts.ensure_started()
        assert ts.sampler_running()
        deadline = time.monotonic() + 5.0
        while ts.store().last("timeseries.ticks") is None:
            assert time.monotonic() < deadline, "sampler never ticked"
            time.sleep(0.02)
        ts.stop_sampler()
        assert not ts.sampler_running()

    def test_ps_registers_ps_source_and_unhooks_on_stop(self, devices8):
        cfg = make_cfg(num_workers=2, num_iterations=10)
        ps = ps_dcn.ParameterServer(cfg, 8, 64, device=devices8[0],
                                    port=0).start()
        try:
            st = ts.TimeSeriesStore(capacity=8)
            ts.sample_once(st)
            assert st.last("ps.accepted") == 0.0
            assert st.last("ps.clock") == 0.0
        finally:
            ps.stop()
        st2 = ts.TimeSeriesStore(capacity=8)
        ts.sample_once(st2)
        assert st2.last("ps.accepted") is None  # unhooked by stop()


# -------------------------------------------------------- HTTP endpoints
class TestTelemetryEndpoints:
    def test_bare_server_status_metrics_timeseries(self):
        global_conf().set("async.metrics.interval.s", 0)  # no thread
        srv = LiveUIServer(None, port=0, role="worker",
                           labels={"wid": "3"}).start()
        try:
            base = f"http://127.0.0.1:{srv.port}"
            status, snap = _get_json(f"{base}/api/status")
            assert status == 200
            assert snap["role"] == "worker"
            assert "counters" in snap and "net" in snap["counters"]
            assert "health" in snap and "convergence" in snap
            status, body = _get(f"{base}/metrics")
            assert status == 200
            parsed = prom.parse_exposition(body)
            info = [(n, dict(l)) for (n, l) in parsed
                    if n == "async_process_info"]
            assert info and info[0][1]["role"] == "worker"
            assert info[0][1]["wid"] == "3"
            status, rings = _get_json(f"{base}/api/timeseries")
            assert status == 200 and isinstance(rings, dict)
            with pytest.raises(urllib.error.HTTPError) as err:
                _get(f"{base}/definitely-not-a-page")
            assert err.value.code == 404
        finally:
            srv.stop()

    def test_start_telemetry_from_conf_gating_and_port_conflict(self):
        # default -1: off
        assert start_telemetry_from_conf("worker") is None
        global_conf().set("async.metrics.port", 0)
        global_conf().set("async.metrics.interval.s", 0)
        srv = start_telemetry_from_conf("worker")
        assert srv is not None
        try:
            # a second process-alike asking for the SAME fixed port must
            # not crash the boot path (k8s env inheritance)
            global_conf().set("async.metrics.port", srv.port)
            assert start_telemetry_from_conf("worker") is None
        finally:
            srv.stop()

    def test_bad_slo_rules_degrade_health_not_500(self):
        """A typo'd async.slo.rules must surface AS the health section,
        not take down every dashboard page while training runs fine."""
        global_conf().set("async.slo.rules", "this is not a rule")
        global_conf().set("async.metrics.interval.s", 0)
        slo.reset_engine()
        srv = LiveUIServer(None, port=0, role="worker").start()
        try:
            status, snap = _get_json(
                f"http://127.0.0.1:{srv.port}/api/status")
            assert status == 200
            assert snap["health"]["state"] == "error"
            assert "unparseable" in snap["health"]["error"]
        finally:
            srv.stop()

    def test_driver_dashboard_serves_metrics_too(self):
        global_conf().set("async.metrics.interval.s", 0)
        state = LiveStateListener(2)
        srv = LiveUIServer(state, port=0).start()
        try:
            base = f"http://127.0.0.1:{srv.port}"
            _status, snap = _get_json(f"{base}/api/status")
            assert "convergence" in snap and "health" in snap
            assert "timeseries" in snap
            _status, body = _get(f"{base}/metrics")
            assert prom.parse_exposition(body)
        finally:
            srv.stop()


# ------------------------------------------------------------- async-top
class TestAsyncTop:
    def test_sparkline(self):
        assert top.sparkline([]) == ""
        assert top.sparkline([1.0, 1.0, 1.0]) == "▁▁▁"
        line = top.sparkline(list(range(100)), width=10)
        assert len(line) == 10
        assert line[0] == "▁" and line[-1] == "█"

    def test_render_status_sections(self):
        status = {
            "role": "driver", "run_id": "r1", "elapsed_s": 12.5,
            "updates_per_sec": 300.25, "accepted": 100, "dropped": 2,
            "model_version": 99,
            "health": {"state": "firing", "firing": ["lag"], "rules": {
                "lag": {"state": "firing", "value": 5000.0,
                        "threshold": 2000.0, "op": "<", "agg": "p95",
                        "series": "serving.freshness_lag_ms",
                        "window_s": 15.0, "for_s": 2.0, "burn_s": 4.2,
                        "fired": 1, "recovered": 0},
            }},
            "convergence": {
                "samples": 10, "last_loss": 0.25, "best_loss": 0.2,
                "slope_per_s": -0.01,
                "curves": {"loss_vs_wallclock": [[0, 1.0], [1, 0.5],
                                                 [2, 0.25]]},
            },
            "trace": {"stages_ms": {
                "compute": {"count": 5, "p50": 1.0, "p95": 2.0,
                            "p99": 3.0},
            }, "staleness_ms": {"count": 5, "p95": 10.0, "max": 20.0}},
            "serving": {"detail": {"qps": 1000.0, "predicts": 50,
                                   "freshness_lag_ms": 55.0,
                                   "failovers": 1,
                                   "predict_ms": {"p50": 0.5,
                                                  "p99": 2.0}}},
            "timeseries": {"series": 12, "samples": 300, "evicted": 0},
        }
        out = top.render_status(status, plain=True)
        assert "FIRING" in out
        assert "lag" in out and "burn=4.2s" in out
        assert "converging" in out
        assert "compute" in out and "2.00" in out
        assert "qps=1000.0" in out
        assert "12 series" in out
        assert any(ch in out for ch in top._SPARK)

    def test_main_once_against_live_server(self, capsys):
        global_conf().set("async.metrics.interval.s", 0)
        srv = LiveUIServer(None, port=0, role="ps").start()
        try:
            rc = top.main([f"127.0.0.1:{srv.port}", "--once", "--plain"])
        finally:
            srv.stop()
        assert rc == 0
        out = capsys.readouterr().out
        assert "role=ps" in out

    def test_main_unreachable_is_graceful(self, capsys):
        with socket_mod.socket() as s:
            s.bind(("127.0.0.1", 0))
            dead_port = s.getsockname()[1]
        rc = top.main([f"127.0.0.1:{dead_port}", "--once", "--plain"])
        assert rc == 0
        assert "unreachable" in capsys.readouterr().out


# -------------------------------------------------- k8s scrape (satellite)
class TestK8sScrapeWiring:
    def _pods(self, objs):
        return [(o["metadata"]["name"], o["spec"]["template"])
                for o in objs if o.get("kind") == "Deployment"]

    def test_all_daemon_pods_annotated_and_wired(self):
        from asyncframework_tpu.deploy import k8s

        objs = (k8s.render_master() + k8s.render_workers(2)
                + k8s.render_serving(2, ps="ps:7000"))
        pods = self._pods(objs)
        assert len(pods) == 4  # master, workers, frontend, replicas
        for name, tpl in pods:
            ann = tpl["metadata"].get("annotations") or {}
            assert ann.get("prometheus.io/scrape") == "true", name
            assert ann.get("prometheus.io/port") == str(k8s.METRICS_PORT)
            assert ann.get("prometheus.io/path") == "/metrics"
            c = tpl["spec"]["containers"][0]
            env = {e["name"]: e["value"] for e in c.get("env", [])}
            assert env.get("ASYNCTPU_ASYNC_METRICS_PORT") == str(
                k8s.METRICS_PORT), name
            ports = [p["containerPort"] for p in c.get("ports", [])]
            assert k8s.METRICS_PORT in ports, name

    def test_rendered_yaml_round_trips(self):
        import yaml

        from asyncframework_tpu.deploy import k8s

        text = k8s.to_yaml(k8s.render_serving(1, ps="ps:7000"))
        docs = list(yaml.safe_load_all(text))
        assert any(
            d["metadata"]["name"] == "async-serve-replicas" for d in docs
        )


# ---------------------------------------------- telemetry plane under chaos
@pytest.mark.chaos
class TestTelemetryUnderChaos:
    def test_endpoints_survive_faults_and_sigkill(self, devices8,
                                                  monkeypatch):
        """Satellite 3: poll /api/status AND /metrics continuously while
        a seeded fault schedule fires and a worker process is SIGKILLed:
        no 500s, every status is JSON-valid, every exposition passes the
        strict parser, and counter series stay monotonic."""
        monkeypatch.setenv("ASYNCTPU_ASYNC_CONVERGENCE_SAMPLE", "4")
        monkeypatch.setenv("ASYNCTPU_ASYNC_METRICS_INTERVAL_S", "0.1")
        cfg = make_cfg(num_iterations=600, printer_freq=100,
                       run_timeout_s=240.0)
        n, d = 4096, 24
        ds = ShardedDataset.generate_on_device(n, d, 8, devices=devices8,
                                               seed=11, noise=0.01)
        ui = LiveUIServer(None, port=0, role="ps").start()
        ps = ps_dcn.ParameterServer(cfg, d, n, device=devices8[0],
                                    port=0).start()
        ep = f"127.0.0.1:{ps.port}"
        sched = FaultSchedule(seed=CHAOS_SEED)
        sched.add(ep, CONNECT_OP, 3, CONNECT_REFUSED)
        sched.add(ep, "PULL", 7, STALL_READ)
        sched.add(ep, "PUSH", 5, DROP_REPLY)
        sched.add(ep, "PUSH", 11, CUT_MID_FRAME)

        env = dict(os.environ)
        env.pop("JAX_PLATFORMS", None)
        env.pop("XLA_FLAGS", None)
        env.update(
            PS_ROLE="worker", PS_PORT=str(ps.port), PS_WORKER_ID="1",
            PS_NUM_WORKER_PROCS="2", PS_WIDS="4,5,6,7", PS_EVAL="0",
            PS_NUM_ITER="600",
        )
        doomed = subprocess.Popen(
            [sys.executable, str(CHILD)], env=env,
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        )
        statuses, expositions, bad = [], [], []
        stop_poll = threading.Event()

        def poll():
            base = f"http://127.0.0.1:{ui.port}"
            while not stop_poll.is_set():
                try:
                    code, snap = _get_json(f"{base}/api/status")
                    if code != 200:
                        bad.append(code)
                    else:
                        statuses.append(snap)
                    code, body = _get(f"{base}/metrics")
                    if code != 200:
                        bad.append(code)
                    else:
                        expositions.append(prom.parse_exposition(body))
                except urllib.error.HTTPError as e:
                    bad.append(e.code)
                except (OSError, ValueError):
                    pass  # transient connects are not the endpoint's fault
                time.sleep(0.05)

        poller = threading.Thread(target=poll, daemon=True)
        poller.start()
        try:
            with faults.injected(sched):
                t_surv = threading.Thread(
                    target=lambda: ps_dcn.run_worker_process(
                        "127.0.0.1", ps.port, [0, 1, 2, 3],
                        {w: ds.shard(w) for w in range(4)}, cfg, d, n,
                        eval_wid=0, deadline_s=240.0,
                        proc_token="survivor"),
                    daemon=True,
                )
                t_surv.start()
                deadline = time.monotonic() + 120
                while time.monotonic() < deadline:
                    with ps._lock:
                        if all(ps.pushes_by_wid.get(w, 0) >= 2
                               for w in (4, 5, 6, 7)):
                            break
                    time.sleep(0.05)
                doomed.send_signal(signal.SIGKILL)
                doomed.wait(timeout=10)
                t_surv.join(timeout=240)
                assert not t_surv.is_alive(), "survivor never finished"
                res = ps.wait_done(timeout_s=30.0)
                assert res, str(res)
        finally:
            stop_poll.set()
            poller.join(timeout=5)
            if doomed.poll() is None:
                doomed.kill()
            ps.stop()
            ui.stop()

        assert not bad, bad
        assert len(statuses) > 10
        assert len(expositions) > 10  # every one already parsed strictly
        # monotonic counter series across snapshots (process-global view)
        acc_seq = [s["counters"]["net"].get("retries_attempted", 0)
                   for s in statuses]
        assert all(a <= b for a, b in zip(acc_seq, acc_seq[1:]))
        conv_seq = [s["convergence"]["samples"] for s in statuses]
        assert all(a <= b for a, b in zip(conv_seq, conv_seq[1:]))
        # chaos fired, the piggyback delivered convergence samples, and
        # the exposition ended populated
        assert statuses[-1]["counters"]["net"]["faults_fired"] >= 1
        assert statuses[-1]["convergence"]["samples"] > 0
        fault_vals = [e[k] for e in expositions for k in e
                      if k[0] == "async_net_faults_fired_total"]
        assert fault_vals and max(fault_vals) >= 1


# --------------------------------------------- two-process acceptance
class TestAcceptance:
    def test_convergence_curve_and_prom_on_ps_replica_frontend(
            self, devices8, monkeypatch, tmp_path):
        """Acceptance: a REAL two-process DCN run (PS child process + this
        process's workers, convergence sampling on) yields a non-empty
        loss-vs-wallclock curve in the PS's /api/status ``convergence``
        section, and /metrics on the PS process, a real replica process,
        and a real frontend process all pass the strict Prometheus
        parser."""
        env = dict(os.environ)
        env.pop("JAX_PLATFORMS", None)
        env.pop("XLA_FLAGS", None)
        env.update(PS_ROLE="ps", PS_NUM_WORKER_PROCS="1",
                   PS_NUM_ITER="300", PS_UI="1",
                   ASYNCTPU_ASYNC_METRICS_INTERVAL_S="0.2")
        ps_proc = subprocess.Popen(
            [sys.executable, str(CHILD)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        )
        serve_procs = []
        statuses, expositions = [], []
        stop_poll = threading.Event()
        try:
            hello = json.loads(ps_proc.stdout.readline())
            port, ui_port = hello["port"], hello["ui_port"]

            # the PS child's UI dies with the child at run end: collect
            # its /api/status + /metrics DURING the run
            def poll():
                base = f"http://127.0.0.1:{ui_port}"
                while not stop_poll.is_set():
                    try:
                        code, snap = _get_json(f"{base}/api/status")
                        if code == 200:
                            statuses.append(snap)
                        code, body = _get(f"{base}/metrics")
                        if code == 200:
                            expositions.append(
                                prom.parse_exposition(body))
                    except (OSError, ValueError):
                        pass  # child not up yet / already gone
                    time.sleep(0.1)

            poller = threading.Thread(target=poll, daemon=True)
            poller.start()

            # real serving processes wired to the live PS, each with its
            # own telemetry endpoint on an ephemeral-free port
            def free_port():
                with socket_mod.socket() as s:
                    s.bind(("127.0.0.1", 0))
                    return s.getsockname()[1]

            fe_mport, rep_mport = free_port(), free_port()
            senv = dict(os.environ)
            senv["JAX_PLATFORMS"] = "cpu"
            senv["PYTHONPATH"] = str(REPO)
            senv["ASYNCTPU_ASYNC_METRICS_INTERVAL_S"] = "0.2"
            serve_procs.append(subprocess.Popen(
                [sys.executable, "-m", "asyncframework_tpu.serving.cli",
                 "frontend", "--host", "127.0.0.1",
                 "--conf", f"async.metrics.port={fe_mport}"],
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
                env=senv, cwd=str(REPO),
            ))
            serve_procs.append(subprocess.Popen(
                [sys.executable, "-m", "asyncframework_tpu.serving.cli",
                 "replica", "--ps", f"127.0.0.1:{port}",
                 "--host", "127.0.0.1",
                 "--conf", f"async.metrics.port={rep_mport}"],
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
                env=senv, cwd=str(REPO),
            ))

            # this process IS the worker process: convergence sampling on
            monkeypatch.setenv("ASYNCTPU_ASYNC_CONVERGENCE_SAMPLE", "4")
            cfg = make_cfg()
            n, d = 4096, 24
            ds = ShardedDataset.generate_on_device(
                n, d, 8, devices=devices8, seed=11, noise=0.01)
            shards = {w: ds.shard(w) for w in range(8)}
            ps_dcn.run_worker_process(
                "127.0.0.1", port, list(range(8)), shards, cfg, d, n,
                eval_wid=0, deadline_s=120.0, proc_token="telem-test",
            )
            ps_proc.communicate(timeout=60)
            stop_poll.set()
            poller.join(timeout=5)

            # --- PS process: the piggybacked samples became a real
            # loss-vs-wallclock curve in /api/status `convergence`
            assert statuses, "PS /api/status never polled"
            conv_snaps = [s["convergence"] for s in statuses
                          if (s.get("convergence") or {})
                          .get("samples", 0) > 0]
            assert conv_snaps, "convergence section never saw samples"
            conv = conv_snaps[-1]
            curve = conv["curves"]["loss_vs_wallclock"]
            assert len(curve) >= 2, conv
            # losses are finite and the curve spans real wallclock
            assert all(math.isfinite(l) for (_t, l) in curve)
            assert curve[-1][0] > curve[0][0]
            # loss-vs-version too (the adaptive controller's other axis)
            assert conv["curves"]["loss_vs_version"], conv
            # /metrics on the PS parsed strictly every poll; the last
            # ones carry the folded convergence gauges
            assert expositions, "PS /metrics never polled"
            assert any(nm == "async_convergence_loss"
                       for e in expositions for (nm, _l) in e)

            # --- replica + frontend processes: /metrics parses, labeled
            for which, mport in (("frontend", fe_mport),
                                 ("replica", rep_mport)):
                deadline = time.monotonic() + 30.0
                parsed = None
                while time.monotonic() < deadline:
                    try:
                        _code, body = _get(
                            f"http://127.0.0.1:{mport}/metrics")
                        parsed = prom.parse_exposition(body)
                        break
                    except (OSError, ValueError):
                        time.sleep(0.2)
                assert parsed, f"{which} /metrics never came up"
                roles = {dict(l).get("role") for (nm, l) in parsed
                         if nm == "async_process_info"}
                assert roles == {which}, (which, roles)
        finally:
            stop_poll.set()
            for p in serve_procs:
                try:
                    p.kill()
                except OSError:
                    pass
            if ps_proc.poll() is None:
                ps_proc.kill()

    def test_freshness_slo_fires_on_kill_and_recovers(self, devices8):
        """Acceptance: the serve-freshness SLO transitions firing -> ok
        across a replica kill/recover cycle.  The frontend (this process)
        observes predicts; the replica is a REAL OS process SIGKILLed
        mid-stream and then relaunched.  Windows are shortened via conf
        so the burn/drain cycle fits a test."""
        global_conf().set(
            "async.slo.rules",
            "serve_freshness: p95(serving.freshness_lag_ms) < 500 "
            "over 3s for 0.5s",
        )
        slo.reset_engine()
        cfg = make_cfg(num_workers=2, num_iterations=10_000,
                       bucket_ratio=0.0, calibration_iters=4)
        d, n = 16, 256
        ps = ps_dcn.ParameterServer(cfg, d, n, device=devices8[0],
                                    port=0).start()
        fe = None
        rep_proc = None
        X = np.ones((4, d), np.float32)

        def spawn_replica():
            env = dict(os.environ)
            env["JAX_PLATFORMS"] = "cpu"
            env["PYTHONPATH"] = str(REPO)
            env["ASYNCTPU_ASYNC_SERVE_REFRESH_INTERVAL_S"] = "0.02"
            return subprocess.Popen(
                [sys.executable, "-m",
                 "asyncframework_tpu.serving.cli", "replica",
                 "--ps", f"127.0.0.1:{ps.port}",
                 "--host", "127.0.0.1",
                 "--frontend", f"127.0.0.1:{fe.port}"],
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
                env=env, cwd=str(REPO),
            )

        def pump(seconds, deadline_state=None):
            """Predict (failures tolerated) + sampler tick until either
            the duration elapses or the health state is reached; returns
            the last state seen."""
            state = None
            t_end = time.monotonic() + seconds
            while time.monotonic() < t_end:
                try:
                    fe.predict(X)
                except Exception:
                    pass  # dead replica: the lag signal must grow anyway
                ts.sample_once()
                state = slo.engine().health()["rules"][
                    "serve_freshness"]["state"]
                if deadline_state is not None and state == deadline_state:
                    return state
                time.sleep(0.1)
            return state

        try:
            fe = ServingFrontend(deadline_s=0.5).serve(port=0,
                                                       host="127.0.0.1")
            rep_proc = spawn_replica()
            deadline = time.monotonic() + 60.0
            while fe.replica_count() < 1:
                assert time.monotonic() < deadline, "replica never joined"
                time.sleep(0.1)
            # healthy traffic: the rule must settle OK (not just no_data)
            state = pump(10.0, deadline_state=slo.OK)
            assert state == slo.OK, state

            # SIGKILL the only replica: freshness lag now grows with wall
            # time (the last successful predict recedes) -> rule FIRES
            os.kill(rep_proc.pid, signal.SIGKILL)
            rep_proc.wait(timeout=10)
            state = pump(30.0, deadline_state=slo.FIRING)
            assert state == slo.FIRING, state
            view = slo.engine().health()["rules"]["serve_freshness"]
            assert view["fired"] >= 1

            # recovery: a fresh replica process joins, predicts succeed,
            # the window drains -> rule returns to OK (not wedged firing)
            rep_proc = spawn_replica()
            state = pump(40.0, deadline_state=slo.OK)
            assert state == slo.OK, state
            view = slo.engine().health()["rules"]["serve_freshness"]
            assert view["recovered"] >= 1
            assert slo.engine().health()["state"] == slo.OK
        finally:
            if fe is not None:
                fe.stop()
            if rep_proc is not None and rep_proc.poll() is None:
                rep_proc.kill()
            ps.stop()
