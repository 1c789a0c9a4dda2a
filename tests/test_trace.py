"""Distributed tracing for the async update loop (metrics/trace.py).

Acceptance (ISSUE 3): a two-process DCN ASGD run over real sockets
produces >= 1 complete cross-process trace -- pull.rtt / compute /
push.rtt spans sharing one trace_id -- with staleness reported in both
versions and milliseconds, visible in the live UI's /api/status,
reconstructed by bin/async-trace from the event log, and exported as
valid Chrome tracing JSON.  Sampling off => zero wire header and
byte-identical frames.

Satellites covered here: process-global counter reset / per-run delta
capture, truncated-event-log tolerance (kill -9 mid-write), live UI under
chaos (faults + SIGKILL, no 500s, monotonic sections), and the
Histogram nearest-rank percentile fix.
"""

import json
import os
import signal
import socket
import struct
import subprocess
import sys
import threading
import time
import urllib.request
from pathlib import Path

import numpy as np
import pytest

from asyncframework_tpu.data.sharded import ShardedDataset
from asyncframework_tpu.metrics import reset_totals
from asyncframework_tpu.metrics import trace
from asyncframework_tpu.metrics.bus import GradientMerged, ListenerBus, TraceSpan
from asyncframework_tpu.metrics.eventlog import EventLogReader, EventLogWriter
from asyncframework_tpu.metrics.live import LiveStateListener, LiveUIServer
from asyncframework_tpu.metrics.system import Histogram
from asyncframework_tpu.net import frame, net_totals
from asyncframework_tpu.net.faults import (
    CONNECT_OP,
    CONNECT_REFUSED,
    CUT_MID_FRAME,
    DROP_REPLY,
    STALL_READ,
    FaultSchedule,
)
from asyncframework_tpu.net import faults, retry
from asyncframework_tpu.net.session import DedupWindow
from asyncframework_tpu.parallel import ps_dcn
from asyncframework_tpu.parallel import supervisor as supervisor_mod
from asyncframework_tpu.parallel.supervisor import (
    ElasticSupervisor,
    recovery_totals,
)
from asyncframework_tpu.solvers import SolverConfig

CHILD = Path(__file__).parent / "ps_dcn_child.py"


def make_cfg(**kw):
    defaults = dict(
        num_workers=8, num_iterations=300, gamma=1.2, taw=2**31 - 1,
        batch_rate=0.3, bucket_ratio=0.5, printer_freq=50, seed=42,
        calibration_iters=20, run_timeout_s=120.0,
    )
    defaults.update(kw)
    return SolverConfig(**defaults)


@pytest.fixture(autouse=True)
def _clean_trace_state():
    """Tracing state is ambient (TLS context, process-global aggregator)
    and breakers/schedules are process-global -- no test may inherit or
    leak any of it."""
    trace.set_current(None)
    retry.reset_breakers()
    faults.clear()
    yield
    trace.set_current(None)
    retry.reset_breakers()
    faults.clear()


def _get_json(url: str, timeout: float = 2.0):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, json.loads(r.read().decode())


# --------------------------------------------------------------- wire format
class TestWireFormat:
    def test_frames_byte_identical_when_tracing_off(self):
        """Sampling off => no ambient context => the frame bytes are
        EXACTLY the pre-trace encoding (zero wire-header overhead)."""
        assert trace.wire_header() is None
        a, b = socket.socketpair()
        try:
            header = {"op": "PULL", "wid": 3}
            frame.send_msg(a, header)
            head = json.dumps(header).encode()
            expected = (struct.pack("!I", len(head)) + head
                        + struct.pack("!I", 0))
            got = b.recv(65536)
            assert got == expected
            assert b"tc" not in got
        finally:
            a.close()
            b.close()

    def test_tc_header_stamped_from_ambient_context(self):
        ctx = trace.TraceContext("deadbeefdeadbeef", 5, 17)
        trace.set_current(ctx)
        try:
            a, b = socket.socketpair()
            try:
                frame.send_msg(a, {"op": "PULL", "wid": 5})
                hdr, _ = frame.recv_msg(b)
            finally:
                a.close()
                b.close()
        finally:
            trace.set_current(None)
        assert hdr["tc"] == ["deadbeefdeadbeef", ctx.span_id, 5, 17]
        rt = trace.TraceContext.from_wire(hdr["tc"])
        assert (rt.trace_id, rt.worker_id, rt.model_version) == (
            "deadbeefdeadbeef", 5, 17)

    def test_caller_header_never_mutated(self):
        """Stamping copies: retries re-send the caller's header verbatim
        (the dedup (sid, seq) contract must survive tracing)."""
        ctx = trace.TraceContext("t" * 16, 0, 0)
        trace.set_current(ctx)
        try:
            a, b = socket.socketpair()
            try:
                header = {"op": "PUSH", "wid": 0, "sid": "s", "seq": 9}
                frame.send_msg(a, header)
                assert "tc" not in header
            finally:
                a.close()
                b.close()
        finally:
            trace.set_current(None)

    def test_span_wire_round_trip(self):
        sp = trace.Span(
            stage=trace.PUSH_RTT, trace_id="t" * 16, span_id="abcd1234",
            parent_id=None, worker_id=2, model_version=40,
            start_ms=123.5, dur_ms=4.25, staleness=3, staleness_ms=9.5,
            accepted=True,
        )
        rt = trace.Span.from_wire(sp.to_wire())
        assert rt == sp

    def test_span_wire_round_trip_preserves_zeros(self):
        """model_version 0 is the PS's FIRST served clock -- exactly the
        update counter-based sampling always traces -- and worker 0 /
        start 0.0 are equally legitimate; none may collapse to sentinels."""
        sp = trace.Span(
            stage=trace.PULL_RTT, trace_id="t" * 16, span_id="00000001",
            parent_id=None, worker_id=0, model_version=0,
            start_ms=0.0, dur_ms=1.0,
        )
        rt = trace.Span.from_wire(sp.to_wire())
        assert rt.model_version == 0
        assert rt.worker_id == 0
        assert rt.start_ms == 0.0

    def test_junk_tc_header_yields_none_not_crash(self):
        """Wire junk (a dict, a short list, None) must never escape
        from_wire -- a KeyError would kill the PS connection handler."""
        for junk in ({}, {"a": 1}, [], ["only-one"], None, 7):
            assert trace.TraceContext.from_wire(junk) is None


# ----------------------------------------------------------------- sampling
class TestSampling:
    def test_rate_zero_is_fully_off(self):
        rec = trace.TraceRecorder(sample_rate=0.0, capacity=16)
        assert not rec.enabled
        assert rec.start_update(0) is None
        assert rec.drain_wire() == []

    def test_counter_sampling_first_update_always_traced(self):
        rec = trace.TraceRecorder(sample_rate=0.25, capacity=64)
        hits = [rec.start_update(0) is not None for _ in range(8)]
        assert hits == [True, False, False, False, True, False, False,
                        False]
        # independent counters per worker: a late-joining worker's first
        # update is still traced
        assert rec.start_update(7) is not None

    def test_ring_is_bounded_and_counts_drops(self):
        rec = trace.TraceRecorder(sample_rate=1.0, capacity=4)
        for i in range(10):
            ut = rec.start_update(0)
            ut.add(trace.COMPUTE, 0.0, 1.0)
        assert rec.dropped_spans == 6
        assert len(rec.drain_wire()) == 4
        assert rec.drain_wire() == []

    def test_requeue_restores_undelivered_spans_in_order(self):
        """A push that spends its whole retry budget re-queues its drained
        piggyback: the spans ride the next push instead of vanishing."""
        rec = trace.TraceRecorder(sample_rate=1.0, capacity=8)
        ut = rec.start_update(3)
        ut.add(trace.PULL_RTT, 0.0, 1.0)
        ut.add(trace.COMPUTE, 1.0, 2.0)
        drained = rec.drain_wire()
        assert len(drained) == 2 and rec.drain_wire() == []
        rec.requeue(drained)           # the send terminally failed
        again = rec.drain_wire()
        assert again == drained        # same spans, same order


    def test_held_spans_reach_the_sink_at_release_in_their_order(self):
        """``UpdateTrace.hold``: what is recorded (by any thread) between a
        hold and its release is handed over at the release, once, in the
        order it was recorded; a release with nothing held hands nothing."""
        seen = []
        rec = trace.TraceRecorder(sample_rate=1.0, sink=seen.append)
        ut = rec.start_update(0)
        ut.add(trace.SUBMIT, 0.0, 1.0)
        assert [sp.stage for sp in seen] == [trace.SUBMIT]
        ut.hold()
        ut.hold()                       # a second copy of the task: no-op
        ut.add(trace.TASK_WAKE, 1.0, 2.0)
        with trace.span(trace.TASK_ENQUEUE, ut):
            pass
        assert [sp.stage for sp in seen] == [trace.SUBMIT]
        assert len(ut.spans) == 3       # the handle has them all the while
        ut.release()
        assert [sp.stage for sp in seen] == [
            trace.SUBMIT, trace.TASK_WAKE, trace.TASK_ENQUEUE]
        ut.release()
        ut.add(trace.RESULT_QUEUE, 2.0, 3.0)
        assert [sp.stage for sp in seen][3:] == [trace.RESULT_QUEUE]


# --------------------------------------------- Histogram nearest-rank (sat 6)
class TestHistogramPercentiles:
    def test_small_n_p95_is_not_max(self):
        h = Histogram()
        for v in range(1, 21):   # 1..20; old int(0.95*20)=19 -> max
            h.update(float(v))
        snap = h.snapshot()
        assert snap["p95"] == 19.0
        assert snap["p99"] == 20.0
        assert snap["p50"] == 10.0
        assert snap["max"] == 20.0

    def test_single_value(self):
        h = Histogram()
        h.update(7.0)
        snap = h.snapshot()
        assert snap["p50"] == snap["p95"] == snap["p99"] == 7.0

    def test_nearest_rank_definition(self):
        # nearest-rank: smallest value with cdf >= q
        assert Histogram._pct([1.0, 2.0, 3.0, 4.0], 0.5) == 2.0
        assert Histogram._pct([1.0, 2.0, 3.0, 4.0], 0.75) == 3.0
        assert Histogram._pct([1.0, 2.0, 3.0, 4.0], 0.76) == 4.0


# ----------------------------------------- totals reset + per-run delta (sat 1)
class TestTotalsResetAndDelta:
    def test_reset_totals_zeroes_every_subsystem(self):
        supervisor_mod.bump_total("rejoins")
        w = DedupWindow()
        hdr = {"sid": "s1", "seq": 1}
        w.record(hdr, {"op": "ACK"})
        assert w.check(hdr) is not None
        trace.aggregator().add(trace.Span(
            stage=trace.COMPUTE, trace_id="t" * 16, span_id="s",
            parent_id=None, worker_id=0, model_version=0, start_ms=0.0,
            dur_ms=1.0,
        ))
        assert recovery_totals()["rejoins"] >= 1
        assert net_totals()["dedup_hits"] >= 1
        assert trace.aggregator().spans_total >= 1
        reset_totals()
        assert recovery_totals()["rejoins"] == 0
        assert net_totals()["dedup_hits"] == 0
        assert trace.aggregator().spans_total == 0
        from asyncframework_tpu.data.spill import shuffle_totals

        assert all(v == 0 for v in shuffle_totals().values())

    def test_live_ui_captures_per_run_delta(self):
        """Regression: a second run's live UI must not inherit the first
        run's process-global counts."""
        supervisor_mod.bump_total("rejoins", 5)
        listener = LiveStateListener(num_workers=2)  # "second run" starts
        snap = listener.snapshot()
        assert snap["recovery"]["rejoins"] == 0
        supervisor_mod.bump_total("rejoins", 2)      # progress IN this run
        snap = listener.snapshot()
        assert snap["recovery"]["rejoins"] == 2
        assert snap["net"]["retries"] >= 0  # delta view, never negative


# ------------------------------------------------ truncated event log (sat 2)
class TestTruncatedEventLog:
    def _write_log(self, path, n=5):
        wr = EventLogWriter(path)
        for i in range(n):
            wr.on_event(GradientMerged(
                time_ms=float(i), worker_id=i % 2, staleness=i,
                accepted=True, iteration=i,
            ))
        wr.close()

    def test_torn_final_record_skip_and_count(self, tmp_path):
        log = tmp_path / "run.jsonl"
        self._write_log(log, n=5)
        # crash mid-write: cut the file in the middle of the last record
        data = log.read_bytes()
        log.write_bytes(data[: len(data) - 20])
        reader = EventLogReader(log)
        events = list(reader.replay(strict=False))
        assert len(events) == 4
        assert reader.truncated_records == 1
        # strict mode still surfaces the corruption
        with pytest.raises(json.JSONDecodeError):
            list(EventLogReader(log).replay(strict=True))
        # the summary (report path) surfaces the count
        summary = EventLogReader(log).summary()
        assert summary["truncated_records"] == 1
        assert summary["merges"] == 4

    def test_writer_killed_9_mid_record_replay_survives(self, tmp_path):
        """THE kill -9 world: a writer process SIGKILLed while streaming
        events leaves an arbitrary tail; the tolerant replay must never
        raise and must count at most the one torn record."""
        log = tmp_path / "killed.jsonl"
        code = (
            "import sys\n"
            "sys.path.insert(0, %r)\n"
            "from asyncframework_tpu.metrics.eventlog import EventLogWriter\n"
            "from asyncframework_tpu.metrics.bus import GradientMerged\n"
            "wr = EventLogWriter(%r)\n"
            "i = 0\n"
            "while True:\n"
            "    wr.on_event(GradientMerged(time_ms=float(i), worker_id=0,\n"
            "                staleness=i, accepted=True, iteration=i,\n"
            "                batch_size=10**6))\n"
            "    i += 1\n"
        ) % (str(Path(__file__).parent.parent), str(log))
        proc = subprocess.Popen([sys.executable, "-c", code],
                                stdout=subprocess.DEVNULL,
                                stderr=subprocess.DEVNULL)
        try:
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                if log.exists() and log.stat().st_size > 20_000:
                    break
                time.sleep(0.02)
            proc.send_signal(signal.SIGKILL)
            proc.wait(timeout=10)
        finally:
            if proc.poll() is None:
                proc.kill()
        reader = EventLogReader(log)
        events = list(reader.replay(strict=False))  # must not raise
        assert len(events) > 0
        assert reader.truncated_records in (0, 1)

    def test_history_index_reports_truncation(self, tmp_path):
        from asyncframework_tpu.metrics.history import build_history

        log = tmp_path / "torn.jsonl"
        self._write_log(log, n=6)
        data = log.read_bytes()
        log.write_bytes(data[: len(data) - 15])
        index = build_history(tmp_path)
        html = index.read_text()
        assert "truncated record" in html


# ---------------------------------------------- single-process solver tracing
class TestSingleProcessTracing:
    def test_run_instruments_emits_lifecycle_spans(self, tmp_path):
        """The hooks of one sampled update, driven by hand in the order the
        engine's threads call them: the sampling decision falls at submit,
        every span is recorded where its stage happens (no start time is
        reconstructed), what ``merge.apply`` carries for the update goes
        through the span call, and ``on_gradient_merged`` is no part of
        tracing."""
        from asyncframework_tpu.context import PartialResult
        from asyncframework_tpu.solvers.instrumentation import (
            RunInstruments,
            worker_task,
        )

        log = tmp_path / "sp.jsonl"
        cfg = SolverConfig(num_workers=2, trace_sample=1.0,
                           event_log=str(log))
        inst = RunInstruments(cfg, 2)
        uts = inst.start_updates([0])           # submitter: at submit
        (ut,) = uts.values()
        with trace.span(trace.SUBMIT, uts.values(), batch=1):
            inst.begin_compute(uts, 7)

        class _Ready:
            def block_until_ready(self):
                time.sleep(0.004)

        task = worker_task(lambda mine: (_Ready(),), 0.0, ut)
        task.on_launch()                        # submitter: the inbox's put
        task()                                  # executor: the closure
        task()                                  # a retry records nothing
        ut.begin(trace.RESULT_QUEUE)            # executor: the handler
        res = PartialResult(None, 2, 10, 0, ut)
        drained = inst.on_drained((res,))       # updater
        assert drained == (ut,)
        with trace.span(trace.MERGE_QUEUE, drained):
            pass
        with trace.span(trace.MERGE_APPLY,
                        inst.apply_attrs([(res, True)]), batch=3):
            pass
        inst.on_gradient_merged(res, accepted=True, iteration=9)
        inst.close()
        spans, _ = trace.load_trace_events(log)
        by_stage = {s.stage: s for s in spans}
        # the closure alone (no copy, no step of its own, no count of the
        # chip's steps) adds the wake-up and an empty turn to the four
        assert sorted(s.stage for s in spans) == sorted(
            (trace.SUBMIT, trace.COMPUTE, *trace.COMPUTE_CHILDREN,
             trace.TASK_WAKE, trace.TASK_TURN,
             trace.MERGE_QUEUE, trace.MERGE_APPLY))
        apply_span = by_stage[trace.MERGE_APPLY]
        assert apply_span.staleness == 2
        assert apply_span.accepted is True
        assert apply_span.batch == 3
        # staleness in time: since the submit, so at least the sleep
        assert apply_span.staleness_ms >= 4.0
        assert by_stage[trace.COMPUTE].dur_ms >= 4.0
        # the version the worker read, stamped at submit
        assert {s.model_version for s in spans} == {7}
        # one trace, parents set: submit <- compute <- the rest
        assert len({s.trace_id for s in spans}) == 1
        assert by_stage[trace.SUBMIT].parent_id is None
        assert (by_stage[trace.COMPUTE].parent_id
                == by_stage[trace.SUBMIT].span_id)
        for st in (*trace.COMPUTE_CHILDREN, trace.MERGE_QUEUE,
                   trace.MERGE_APPLY):
            assert by_stage[st].parent_id == by_stage[trace.COMPUTE].span_id
        # the first child starts in the instant its parent does
        assert (by_stage[trace.TASK_INBOX].start_ms
                - by_stage[trace.COMPUTE].start_ms) < 0.5
        assert by_stage[trace.TASK_DEVICE_WAIT].dur_ms >= 4.0
        assert inst.staleness_hist == {2: 1}

    def test_asgd_run_traced_end_to_end(self, tiny_problem, tmp_path):
        from asyncframework_tpu.solvers import ASGD

        X, y, _w = tiny_problem
        log = tmp_path / "asgd.jsonl"
        cfg = SolverConfig(
            num_workers=4, num_iterations=40, gamma=0.4, taw=2**31 - 1,
            batch_rate=0.3, bucket_ratio=0.5, printer_freq=20, seed=42,
            calibration_iters=8, run_timeout_s=60.0, event_log=str(log),
            trace_sample=1.0, heartbeat=False,
        )
        res = ASGD(X, y, cfg).run()
        assert res.accepted == 40
        spans, _ = trace.load_trace_events(log)
        stages = {s.stage for s in spans}
        assert trace.COMPUTE in stages and trace.MERGE_APPLY in stages
        applies = [s for s in spans if s.stage == trace.MERGE_APPLY]
        assert applies and all(s.staleness is not None
                               and s.staleness_ms is not None
                               for s in applies)


class TestPSFoldDedup:
    def test_piggyback_refold_is_deduped_by_span_id(self, devices8):
        """A push delivered but never ACKed re-queues its piggyback under
        a fresh (sid, seq); the PS must not fold the same spans twice."""
        cfg = make_cfg(num_workers=1, num_iterations=10)
        ps = ps_dcn.ParameterServer(cfg, 8, 64, device=devices8[0], port=0)
        try:
            wire = trace.Span(
                stage=trace.COMPUTE, trace_id="t" * 16,
                span_id="aabbccdd", parent_id=None, worker_id=0,
                model_version=1, start_ms=1.0, dur_ms=2.0,
            ).to_wire()
            ps._fold_wire_spans([wire])
            ps._fold_wire_spans([wire])  # the re-queued re-delivery
            assert ps.trace_spans == 1
        finally:
            ps.stop()


class TestCliExitCodes:
    def test_json_mode_flags_traceless_log(self, tmp_path, capsys):
        """--json must agree with table mode: a trace-less log (sampling
        off / no event log attached) exits 1 so CI can gate on it."""
        log = tmp_path / "empty.jsonl"
        EventLogWriter(log).close()
        rc = trace.main([str(log), "--json"])
        out = capsys.readouterr().out.strip()
        assert rc == 1
        assert json.loads(out)["spans"] == 0


# ------------------------------------------------- THE acceptance scenario
class TestCrossProcessAcceptance:
    def test_two_process_dcn_trace_end_to_end(self, devices8, tmp_path,
                                              monkeypatch, capsys):
        """Two OS processes (PS child + this process's workers) over real
        loopback sockets: >= 1 complete span chain (pull.rtt / compute /
        push.rtt under one trace_id), staleness in versions AND ms,
        visible in /api/status, reconstructed by bin/async-trace, exported
        as valid Chrome tracing JSON."""
        log = tmp_path / "dcn.jsonl"
        env = dict(os.environ)
        env.pop("JAX_PLATFORMS", None)
        env.pop("XLA_FLAGS", None)
        env.update(
            PS_ROLE="ps", PS_NUM_WORKER_PROCS="1", PS_NUM_ITER="300",
            PS_UI="1", PS_EVENT_LOG=str(log),
            ASYNCTPU_ASYNC_TRACE_SAMPLE="1.0",
        )
        ps_proc = subprocess.Popen(
            [sys.executable, str(CHILD)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        )
        snapshots = []
        poll_errors = []
        stop_poll = threading.Event()
        try:
            hello = json.loads(ps_proc.stdout.readline())
            port, ui_port = hello["port"], hello["ui_port"]

            def poll():
                url = f"http://127.0.0.1:{ui_port}/api/status"
                while not stop_poll.is_set():
                    try:
                        status, snap = _get_json(url)
                        if status != 200:
                            poll_errors.append(status)
                        else:
                            snapshots.append(snap)
                    except Exception:
                        pass  # server not up yet / already down
                    time.sleep(0.05)

            poller = threading.Thread(target=poll, daemon=True)
            poller.start()

            # this process IS the second process: real sockets to the PS
            monkeypatch.setenv("ASYNCTPU_ASYNC_TRACE_SAMPLE", "1.0")
            cfg = make_cfg()
            n, d = 4096, 24
            ds = ShardedDataset.generate_on_device(
                n, d, 8, devices=devices8, seed=11, noise=0.01)
            shards = {w: ds.shard(w) for w in range(8)}
            ps_dcn.run_worker_process(
                "127.0.0.1", port, list(range(8)), shards, cfg, d, n,
                eval_wid=0, deadline_s=120.0, proc_token="trace-test",
            )
            out, _ = ps_proc.communicate(timeout=120)
        finally:
            stop_poll.set()
            if ps_proc.poll() is None:
                ps_proc.kill()
        final = json.loads(out.strip().splitlines()[-1])
        assert final["done"], final
        assert final["accepted"] == 300
        assert final["trace_spans"] > 0, final

        # --- live UI: the trace section carried spans and staleness-in-ms
        assert not poll_errors, poll_errors
        traced = [s for s in snapshots if s["trace"]["spans"] > 0]
        assert traced, "no /api/status snapshot ever showed trace spans"
        last = traced[-1]["trace"]
        assert last["staleness_ms"]["count"] > 0
        assert last["staleness_versions"]["count"] > 0
        assert "p95" in last["stages_ms"][trace.MERGE_APPLY]

        # --- event log: >= 1 complete cross-process chain
        spans, truncated = trace.load_trace_events(log)
        assert truncated == 0
        traces = trace.build_traces(spans)
        complete = trace.complete_traces(traces)
        assert len(complete) >= 1
        tid, chain = next(iter(complete.items()))
        chain_stages = {s.stage for s in chain}
        assert {trace.PULL_RTT, trace.COMPUTE,
                trace.PUSH_RTT} <= chain_stages
        assert all(s.trace_id == tid for s in chain)
        # the server saw the same trace ids the workers minted (wire
        # propagation, not correlation): PS-side stages joined the chains
        server_stages = {s.stage for s in spans}
        assert trace.MERGE_APPLY in server_stages
        assert trace.PULL_WAIT in server_stages
        joined = [t for t, ss in complete.items()
                  if any(s.stage == trace.MERGE_APPLY for s in ss)]
        assert joined, "no chain carried both client and server spans"
        # staleness in BOTH units on the merge spans
        merge = [s for s in spans if s.stage == trace.MERGE_APPLY]
        assert any(s.staleness is not None and s.staleness_ms is not None
                   for s in merge)

        # --- bin/async-trace reconstruction + chrome export
        chrome_path = tmp_path / "chrome.json"
        rc = trace.main([str(log), "--chrome", str(chrome_path), "--json"])
        assert rc == 0
        summary = json.loads(capsys.readouterr().out.strip())
        assert summary["complete_traces"] >= 1
        assert summary["decomposition"]["stages_ms"][trace.PUSH_RTT][
            "count"] > 0
        assert summary["stragglers"]
        chrome = json.loads(chrome_path.read_text())
        assert chrome["traceEvents"]
        for ev in chrome["traceEvents"][:50]:
            assert ev["ph"] == "X"
            assert {"name", "ts", "dur", "pid", "tid"} <= set(ev)

    def test_sampling_off_no_trace_work(self, devices8, monkeypatch):
        """async.trace.sample = 0: no recorder, no wire context, no spans
        -- the hot path does zero tracing work."""
        monkeypatch.setenv("ASYNCTPU_ASYNC_TRACE_SAMPLE", "0.0")
        cfg = make_cfg(num_iterations=60, num_workers=4)
        n, d = 1024, 16
        ds = ShardedDataset.generate_on_device(
            n, d, 4, devices=devices8[:4], seed=3, noise=0.01)
        ps = ps_dcn.ParameterServer(cfg, d, n, device=devices8[0],
                                    port=0).start()
        shards = {w: ds.shard(w) for w in range(4)}
        before = trace.aggregator().spans_total
        ps_dcn.run_worker_process(
            "127.0.0.1", ps.port, list(range(4)), shards, cfg, d, n,
            deadline_s=60.0,
        )
        done = ps.wait_done(timeout_s=5.0)
        ps.stop()
        assert done
        assert ps.trace_spans == 0
        assert trace.aggregator().spans_total == before


# --------------------------------------------- live UI under chaos (sat 3)
class TestLiveUIUnderChaos:
    def test_api_status_survives_faults_and_sigkill(self, devices8,
                                                    monkeypatch):
        """Poll /api/status continuously while a seeded fault schedule
        fires and a worker process is SIGKILLed: the server never 500s,
        every snapshot is JSON-valid, and the trace/recovery sections stay
        monotonic."""
        monkeypatch.setenv("ASYNCTPU_ASYNC_TRACE_SAMPLE", "1.0")
        sup = ElasticSupervisor(8, dead_after_s=1.0, check_interval_s=0.2,
                                boot_grace_s=60.0)
        cfg = make_cfg(num_iterations=1200, printer_freq=300,
                       run_timeout_s=240.0)
        n, d = 4096, 24
        ds = ShardedDataset.generate_on_device(n, d, 8, devices=devices8,
                                               seed=11, noise=0.01)
        bus = ListenerBus()
        state = LiveStateListener(8)
        bus.add_listener(state)
        bus.start()
        ui = LiveUIServer(state, port=0).start()
        ps = ps_dcn.ParameterServer(cfg, d, n, device=devices8[0], port=0,
                                    supervisor=sup, bus=bus).start()
        ep = f"127.0.0.1:{ps.port}"
        sched = FaultSchedule(seed=11)
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
            PS_NUM_ITER="1200",
        )
        doomed = subprocess.Popen(
            [sys.executable, str(CHILD)], env=env,
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        )
        polled = []
        bad_status = []
        stop_poll = threading.Event()

        def poll():
            url = f"http://127.0.0.1:{ui.port}/api/status"
            while not stop_poll.is_set():
                try:
                    status, snap = _get_json(url)
                    if status != 200:
                        bad_status.append(status)
                    else:
                        polled.append(snap)
                except (urllib.error.HTTPError,) as e:  # a 500 lands here
                    bad_status.append(e.code)
                except Exception:
                    pass  # transient connect issues are not the UI's fault
                time.sleep(0.03)

        poller = threading.Thread(target=poll, daemon=True)
        poller.start()
        counts = {}
        try:
            with faults.injected(sched):
                t_surv = threading.Thread(
                    target=lambda: counts.update(ps_dcn.run_worker_process(
                        "127.0.0.1", ps.port, [0, 1, 2, 3],
                        {w: ds.shard(w) for w in range(4)}, cfg, d, n,
                        eval_wid=0, deadline_s=240.0,
                        shard_factory=ds.shard, proc_token="survivor")),
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
            bus.stop()

        # the UI never errored and every snapshot parsed (parsing happened
        # in the poller; reaching here with entries proves it)
        assert not bad_status, bad_status
        assert len(polled) > 10
        # monotonic sections: trace span counts and recovery counters only
        # ever grow within one run
        spans_seq = [s["trace"]["spans"] for s in polled]
        assert all(a <= b for a, b in zip(spans_seq, spans_seq[1:]))
        lost_seq = [s["recovery"]["workers_lost"] for s in polled]
        assert all(a <= b for a, b in zip(lost_seq, lost_seq[1:]))
        assert lost_seq[-1] >= 4  # the SIGKILLed process's four wids
        adopted_seq = [s["recovery"]["shards_adopted"] for s in polled]
        assert all(a <= b for a, b in zip(adopted_seq, adopted_seq[1:]))
        # chaos fired and the dashboard saw it (per-run delta view)
        assert polled[-1]["net"]["faults_fired"] >= 1
        # and the trace section ended populated despite the chaos
        assert polled[-1]["trace"]["spans"] > 0
