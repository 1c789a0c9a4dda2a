"""Continuous profiling plane (ISSUE 18).

The correctness spine:

- ONE declared zone table: the sampling classifier, the exact
  accumulators at the wire/merge/dispatch choke points, and the lint
  rule all reference ``profiler.ZONES`` -- grammar, uniqueness and the
  classifier's claims are asserted here;
- OFF is really off: ``zone()`` hands back the one shared no-op,
  ``wrap_dispatch()`` returns its argument UNCHANGED (identity
  asserted), and the wire is byte-identical per-op with profiling on
  vs off -- observation must not perturb the thing observed;
- the exact collectors attribute real nanoseconds at the real choke
  points (frame pump, XOR delta, CRC, quantize, compress), and the
  ``profile`` counter family rides the registry (``reset_totals()``
  clears it like every other family);
- THE acceptance: a delta-pull + int8-push DCN run decomposes into the
  five wire zones separately and non-zero, ``/api/status`` serves the
  ``profile`` section, ``bin/async-prof --collapsed`` emits valid
  flamegraph collapsed-stack input, and ``--diff`` between codec-on
  and codec-off arms shows ``wire.quantize`` only in the codec arm;
- the chaos rider (every ``bin/chaos_sweep.py`` seed): a SIGKILLed
  worker child's harvested flight dump carries a non-empty profile
  snapshot -- the post-mortem answers "where were the cycles going"
  even when the process cannot.
"""

import json
import os
import re
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.request
from pathlib import Path

import numpy as np
import pytest

from asyncframework_tpu.conf import AsyncConf, set_global_conf
from asyncframework_tpu.metrics import flightrec, profiler, reset_totals
from asyncframework_tpu.net import frame, wirecodec, wiredelta
from asyncframework_tpu.net import reset_net_totals

pytestmark = pytest.mark.prof

CHILD = Path(__file__).parent / "ps_dcn_child.py"
CHAOS_SEED = int(os.environ.get("ASYNC_CHAOS_SEED", "7"))

#: zone-name grammar: a family, optionally one dotted sub-zone
_ZONE_RE = re.compile(r"^[a-z]+(\.[a-z_]+)?$")
#: flamegraph collapsed line: semicolon-joined file:func frames, a
#: space, a positive count (what flamegraph.pl / inferno consume)
_COLLAPSED_RE = re.compile(r"^[^ ;]+(;[^ ;]+)* [0-9]+$")

_FIVE_WIRE_ZONES = ("wire.encode", "wire.decode", "wire.xor",
                    "wire.crc", "wire.quantize")


@pytest.fixture(autouse=True)
def _clean_state():
    conf = AsyncConf()
    conf.set("async.metrics.interval.s", 0)
    set_global_conf(conf)
    profiler.uninstall()
    profiler._last_final = None
    flightrec.uninstall()
    reset_net_totals()
    yield
    profiler.uninstall()
    profiler._last_final = None
    flightrec.uninstall()
    reset_net_totals()
    set_global_conf(None)


def _pump_frames(n=4, payload=b"\xab" * 4096):
    """Drive n request frames through a real socketpair; returns the
    per-op frame-byte totals the run produced."""
    frame.reset_bytes_totals()
    a, b = socket.socketpair()
    try:
        for i in range(n):
            frame.send_msg(a, {"op": "PING", "i": i}, payload)
            hdr, pl = frame.recv_msg(b)
            assert hdr["op"] == "PING" and pl == payload
    finally:
        a.close()
        b.close()
    return frame.bytes_totals()


# -------------------------------------------------------------- zone table
class TestZoneTable:
    def test_grammar_unique_and_fallback_last(self):
        assert len(set(profiler.ZONES)) == len(profiler.ZONES)
        for z in profiler.ZONES:
            assert _ZONE_RE.match(z), z
        # the declared fallback is the classifier's last row AND a zone
        assert profiler._CLASSIFIER[-1].zone == "gil.other"
        assert profiler._CLASSIFIER[-1].path == ""
        assert profiler._WIRE_ZONES == tuple(
            z for z in profiler.ZONES if z.startswith("wire."))

    def test_every_classifier_zone_is_declared(self):
        for rule in profiler._CLASSIFIER:
            assert rule.zone in profiler.ZONES, rule.zone

    @pytest.mark.parametrize("filename,func,zone", [
        ("/x/asyncframework_tpu/net/wiredelta.py", "crc", "wire.crc"),
        ("/x/asyncframework_tpu/net/wiredelta.py", "encode", "wire.xor"),
        ("/x/asyncframework_tpu/net/wirecodec.py", "encode_grad",
         "wire.quantize"),
        ("/x/asyncframework_tpu/net/wirecodec.py", "compress_model_part",
         "wire.compress"),
        ("/x/asyncframework_tpu/net/frame.py", "recv_exact", "wire.decode"),
        ("/x/asyncframework_tpu/net/frame.py", "_send_frame", "wire.encode"),
        ("/x/asyncframework_tpu/parallel/ps_dcn.py", "_drain_merge_locked",
         "merge.drain"),
        ("/usr/lib/python3.11/json/encoder.py", "iterencode", "serde"),
        ("/site-packages/jax/_src/api.py", "cache_miss", "kernel.dispatch"),
        ("/site-packages/jaxlib/xla_client.py", "execute", "kernel.dispatch"),
    ])
    def test_classify_single_frame_stacks(self, filename, func, zone):
        assert profiler.classify_stack([(filename, func)]) == zone

    def test_unclaimed_stack_falls_back_to_gil_other(self):
        stack = [("/x/myapp/train.py", "loop"), ("/x/myapp/main.py", "main")]
        assert profiler.classify_stack(stack) == "gil.other"
        assert profiler.classify_stack([]) == "gil.other"

    def test_innermost_claimed_frame_wins(self):
        # crc running UNDER decode: innermost claim (crc) wins, matching
        # the "where are the cycles actually burning" reading
        stack = [
            ("/x/asyncframework_tpu/net/wiredelta.py", "crc"),
            ("/x/asyncframework_tpu/net/wiredelta.py", "decode"),
            ("/x/asyncframework_tpu/parallel/ps_dcn.py", "_handle_pull"),
        ]
        assert profiler.classify_stack(stack) == "wire.crc"
        # an unclaimed app frame above a claimed one does not mask it
        stack2 = [("/x/myapp/helper.py", "pack"),
                  ("/x/asyncframework_tpu/net/frame.py", "_send_frame")]
        assert profiler.classify_stack(stack2) == "wire.encode"


# ---------------------------------------------------------------- off path
class TestOffPath:
    def test_zone_is_the_shared_noop(self):
        for z in profiler.ZONES:
            assert profiler.zone(z) is profiler._NOOP_ZONE
        with profiler.zone("wire.encode"):
            pass  # must be usable as a context manager

    def test_wrap_dispatch_is_identity(self):
        def step(x):
            return x + 1
        assert profiler.wrap_dispatch(step, "kernel.dispatch") is step

    def test_zoned_passthrough_and_empty_totals(self):
        # the production zoned codecs run fine with no profiler and
        # leave the registry family empty
        buf = np.arange(64, dtype=np.float32)
        assert wiredelta.crc(buf) == wiredelta.crc(buf)
        assert profiler.profile_totals() == {}
        profiler.reset_profile_totals()  # no-op, must not raise
        assert profiler.last_snapshot() is None
        assert profiler.active() is None

    def test_zoned_rejects_undeclared_zone_at_decoration(self):
        with pytest.raises(ValueError, match="undeclared"):
            profiler.zoned("wire.bogus")

    def test_wire_byte_identical_prof_on_vs_off(self):
        """Observation must not perturb: the exact same frame exchange
        produces the exact same per-op byte totals with profiling on."""
        off = _pump_frames()
        profiler.install("t-onoff", hz=0)
        on = _pump_frames()
        assert on == off
        assert off.get("sent.PING", 0) > 0  # the comparison saw traffic


# -------------------------------------------------------- exact collectors
class TestExactCollectors:
    def test_frame_and_codec_zones_accumulate(self, rng):
        p = profiler.install("t-exact", hz=0)
        _pump_frames()
        d = 256
        basis = rng.normal(size=d).astype(np.float32)
        cur = (basis * 1.0001).astype(np.float32)
        payload = wiredelta.encode_xfull(cur, basis)
        out = wiredelta.decode(wiredelta.XFULL, payload, 0, basis,
                               wiredelta.crc(cur), None)
        assert out is not None
        g = (0.1 * rng.normal(size=d)).astype(np.float32)
        hdr, qpayload, _err = wirecodec.encode_grad(g, wirecodec.INT8, None)
        wirecodec.decode_grad(hdr, qpayload, d)
        chdr, cpayload = wirecodec.compress_model_part(
            wiredelta.XFULL, payload)
        wirecodec.decompress_model_part(chdr, cpayload)
        totals = p.totals()
        for z in ("wire.encode", "wire.decode", "serde", "wire.xor",
                  "wire.crc", "wire.quantize", "wire.compress"):
            assert totals.get(f"zone_ns.{z}", 0) > 0, z
            assert totals.get(f"zone_calls.{z}", 0) > 0, z
        # the snapshot folds the same totals into per-zone rows
        zones = p.snapshot()["zones"]
        assert zones["wire.xor"]["calls"] >= 2  # encode_xfull + decode

    def test_registry_reset_totals_resets_profile_family(self):
        p = profiler.install("t-registry", hz=0)
        with profiler.zone("wire.encode"):
            pass
        assert profiler.profile_totals().get("zone_calls.wire.encode") == 1
        reset_totals()  # the one whole-process reset every suite uses
        assert profiler.profile_totals() == {}
        assert p.totals() == {}

    def test_zone_ns_direct_bump(self):
        profiler.install("t-direct", hz=0)
        profiler.zone_ns("wire.encode", 1_000_000)
        t = profiler.profile_totals()
        assert t["zone_ns.wire.encode"] == 1_000_000
        assert t["zone_calls.wire.encode"] == 1

    def test_wrap_dispatch_compile_then_dispatch_accounting(self):
        p = profiler.install("t-dispatch", hz=0)
        calls = []

        def step(x):
            calls.append(x)
            return x
        w = profiler.wrap_dispatch(step, "kernel.dispatch", "unit_step")
        assert w is not step  # enabled path wraps
        for i in range(4):
            assert w(i) == i
        snap = p.snapshot()
        assert snap["compile"]["count"] == 1  # first call = trace+compile
        assert snap["dispatch"]["count"] == 3
        assert snap["dispatch"]["ns"] >= 0
        assert "unit_step" in snap["dispatch"]["ewma_ms"]
        # only dispatch calls feed the zone (compile is its own bucket)
        assert snap["zones"]["kernel.dispatch"]["calls"] == 3

    def test_memory_gauges_host_rss_always(self):
        mem = profiler.memory_gauges()
        assert mem["host_rss_bytes"] > 0


# ------------------------------------------------------------------ sampler
class TestSampler:
    def test_sample_once_classifies_and_collapses(self):
        p = profiler.Profiler("t-sampler", hz=0)
        n = p.sample_once()
        assert n >= 1  # at least this thread
        snap = p.snapshot()
        assert snap["samples"] == n
        assert sum(z["samples"] for z in snap["zones"].values()) == n
        assert snap["stacks"]
        for line in profiler.collapsed_lines(snap):
            assert _COLLAPSED_RE.match(line), line

    def test_sampler_skips_its_own_thread(self):
        p = profiler.Profiler("t-skip", hz=0)
        before = p.sample_once(skip_tid=threading.get_ident())
        all_threads = p.sample_once()
        assert all_threads == before + 1

    def test_stack_table_bounded_drop_not_evict(self):
        """Beyond stacks_max, NEW stacks are dropped (and counted), the
        resident hot stacks keep counting -- eviction would bias the
        long-running stacks out of the flamegraph."""
        p = profiler.Profiler("t-bound", hz=0, stacks_max=1)
        stop = threading.Event()

        def parked_in_a():
            stop.wait(10.0)

        def parked_in_b():
            stop.wait(10.0)
        threads = [threading.Thread(target=parked_in_a, daemon=True),
                   threading.Thread(target=parked_in_b, daemon=True)]
        for t in threads:
            t.start()
        time.sleep(0.05)  # both parked in distinctly-named frames
        try:
            p.sample_once()
            p.sample_once()
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=5.0)
        totals = p.totals()
        assert len(p.snapshot()["stacks"]) == 1
        assert totals.get("stack_overflow", 0) >= 1
        # the one resident stack kept counting on the second pass
        assert max(p.snapshot()["stacks"].values()) >= 2

    def test_background_sampler_thread_collects(self):
        p = profiler.install("t-thread", hz=251.0)
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            if p.totals().get("samples", 0) >= 5:
                break
            time.sleep(0.01)
        assert p.totals().get("samples", 0) >= 5
        snap = profiler.uninstall()
        # uninstall keeps the final snapshot for late flight dumps
        assert snap is not None and snap["samples"] >= 5
        assert profiler.last_snapshot() is snap


# ------------------------------------------------- status + flight + story
class TestStatusAndFlight:
    def test_api_status_profile_section_and_metrics_family(self):
        from asyncframework_tpu.metrics.live import LiveUIServer

        profiler.install("t-status", hz=0)
        with profiler.zone("wire.encode"):
            pass
        srv = LiveUIServer(None, port=0, role="t-status").start()
        try:
            base = f"http://127.0.0.1:{srv.port}"
            with urllib.request.urlopen(f"{base}/api/status",
                                        timeout=3.0) as r:
                snap = json.loads(r.read())
            assert snap["profile"]["role"] == "t-status"
            assert snap["profile"]["zones"]["wire.encode"]["calls"] == 1
            with urllib.request.urlopen(f"{base}/metrics",
                                        timeout=3.0) as r:
                body = r.read().decode()
            assert "async_profile_" in body  # the registry family rides
        finally:
            srv.stop()
        # after uninstall the section is gone, not erroring
        profiler.uninstall()
        srv2 = LiveUIServer(None, port=0, role="t-status2").start()
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv2.port}/api/status",
                    timeout=3.0) as r:
                snap2 = json.loads(r.read())
            assert "profile" not in snap2
        finally:
            srv2.stop()

    def test_flight_dump_embeds_profile_snapshot(self, tmp_path):
        profiler.install("t-flight", hz=0)
        with profiler.zone("merge.drain"):
            pass
        rec = flightrec.install("t-flight", str(tmp_path))
        dump = rec.snapshot("test")
        assert dump["profile"]["zones"]["merge.drain"]["calls"] == 1
        # a dump AFTER uninstall still carries the final snapshot
        profiler.uninstall()
        dump2 = rec.snapshot("late")
        assert dump2["profile"]["zones"]["merge.drain"]["calls"] == 1
        # and with no profiler ever installed the key is absent
        profiler._last_final = None
        assert "profile" not in rec.snapshot("never")

    def test_observer_harvest_persist_roundtrip(self, tmp_path):
        from asyncframework_tpu.metrics.observer import (
            RunHistoryStore,
            load_run,
        )

        profiler.install("t-hist", hz=0)
        with profiler.zone("wire.xor"):
            pass
        snap = profiler.active().snapshot()
        store = RunHistoryStore(str(tmp_path), "prof-run")
        dump = {"schema": 1, "role": "worker", "pid": 4242,
                "dumped_s": snap["dumped_s"], "events": [],
                "profile": snap}
        assert store.harvest(dump, "flight-worker-4242.json")
        profs = store.profile_snapshots()
        assert len(profs) == 1
        key = next(iter(profs))
        assert profs[key]["zones"]["wire.xor"]["calls"] == 1
        # stale re-harvest is a no-op; fresher dumped_s re-harvests
        assert not store.harvest_profile(dict(snap), "again")
        fresher = dict(snap, dumped_s=snap["dumped_s"] + 5.0)
        assert store.harvest_profile(fresher, "again")
        rd = store.persist()
        assert rd and os.path.isfile(
            os.path.join(rd, "profile", f"{key}.json"))
        loaded = load_run(rd)
        assert loaded["profile"][key]["zones"]["wire.xor"]["calls"] == 1
        assert key in loaded["meta"]["profile_snapshots"]
        assert key in store.summary()["profile_snapshots"]

    def test_top_renders_compact_zone_share_row(self):
        from asyncframework_tpu.metrics.top import render_profile_row

        section = {"samples": 200, "zones": {
            "wire.encode": {"samples": 120, "share": 0.6},
            "gil.other": {"samples": 80, "share": 0.4},
        }, "compile": {"count": 2, "ns": 3_000_000}}
        row = render_profile_row(section)
        assert "samples=200" in row
        assert "wire.encode 60%" in row
        assert "compile=2" in row
        # the observer's compact per-role block carries bare share floats
        row2 = render_profile_row(
            {"samples": 10, "zones": {"serde": 1.0}})
        assert "serde 100%" in row2


# ----------------------------------------------------------------- CLI
def _snapshot_with_traffic(role, rng, quantize):
    """One arm's worth of exact-collector traffic -> its snapshot."""
    profiler.uninstall()
    profiler.install(role, hz=0)
    _pump_frames(n=2)
    if quantize:
        g = (0.1 * rng.normal(size=64)).astype(np.float32)
        hdr, payload, _ = wirecodec.encode_grad(g, wirecodec.INT8, None)
        wirecodec.decode_grad(hdr, payload, 64)
    prof = profiler.active()
    prof.sample_once()
    snap = prof.snapshot()
    profiler.uninstall()
    return snap


class TestCLI:
    def test_collapsed_output_is_valid_flamegraph_input(self, tmp_path,
                                                        capsys, rng):
        snap = _snapshot_with_traffic("arm-a", rng, quantize=False)
        f = tmp_path / "snap.json"
        f.write_text(json.dumps(snap))
        assert profiler.main([str(f), "--collapsed"]) == 0
        out = capsys.readouterr().out.strip().splitlines()
        assert out
        for line in out:
            assert _COLLAPSED_RE.match(line), line
        # counts sum to the snapshot's resident-stack samples
        assert (sum(int(ln.rsplit(" ", 1)[1]) for ln in out)
                == sum(snap["stacks"].values()))

    def test_diff_codec_arms_quantize_only_in_codec_on(self, tmp_path,
                                                       capsys, rng):
        """THE --diff acceptance: codec-on vs codec-off bench arms show
        wire.quantize only in the codec arm."""
        on = _snapshot_with_traffic("arm-int8", rng, quantize=True)
        off = _snapshot_with_traffic("arm-off", rng, quantize=False)
        bench_out = {"codec": {"int8": {"profile": on},
                               "off": {"profile": off}}}
        f = tmp_path / "bench.json"
        f.write_text(json.dumps(bench_out))
        loaded = profiler.load_profiles(str(f))
        assert set(loaded) == {"codec/int8", "codec/off"}
        assert profiler.main([str(f), "--diff", "--arm", "codec/int8",
                              "--arm-b", "codec/off", "--json"]) == 0
        d = json.loads(capsys.readouterr().out)
        assert "wire.quantize" in d["only_in_a"]
        assert "wire.quantize" not in d["only_in_b"]
        assert d["zones"]["wire.quantize"]["ms_a"] > 0

    def test_diff_over_one_source_requires_both_arms(self, tmp_path, rng):
        snap = _snapshot_with_traffic("arm-x", rng, quantize=False)
        f = tmp_path / "one.json"
        f.write_text(json.dumps({"profile": snap}))
        assert profiler.main([str(f), "--diff"]) == 2

    def test_empty_source_exits_2(self, tmp_path):
        f = tmp_path / "empty.json"
        f.write_text(json.dumps({"nothing": "here"}))
        assert profiler.main([str(f)]) == 2

    def test_load_profiles_reads_flight_dump_and_run_dir(self, tmp_path,
                                                         rng):
        snap = _snapshot_with_traffic("arm-d", rng, quantize=False)
        (tmp_path / "flight-x.json").write_text(
            json.dumps({"role": "worker", "events": [], "profile": snap}))
        (tmp_path / "raw.json").write_text(json.dumps(snap))
        (tmp_path / "junk.json").write_text(json.dumps([1, 2, 3]))
        loaded = profiler.load_profiles(str(tmp_path))
        assert set(loaded) == {"flight-x", "raw"}


# ------------------------------------------------------------- acceptance
def _make_cfg(**kw):
    from asyncframework_tpu.solvers import SolverConfig

    defaults = dict(
        num_workers=2, num_iterations=400, gamma=0.5, taw=2 ** 31 - 1,
        batch_rate=0.3, bucket_ratio=0.0, printer_freq=100, seed=42,
        calibration_iters=4, run_timeout_s=60.0,
    )
    defaults.update(kw)
    return SolverConfig(**defaults)


class TestDCNAcceptance:
    def test_delta_int8_run_attributes_five_wire_zones(self, devices8):
        """THE in-process acceptance: a delta-pull + int8-push run over
        real sockets decomposes into the five wire zones, each
        separately attributed and non-zero."""
        from asyncframework_tpu.conf import global_conf
        from asyncframework_tpu.parallel import ps_dcn

        global_conf().set("async.pull.mode", "delta")
        profiler.install("t-dcn", hz=197.0)
        d = 256
        ps = ps_dcn.ParameterServer(_make_cfg(), d, 256,
                                    device=devices8[0], port=0).start()
        try:
            cl = ps_dcn.PSClient("127.0.0.1", ps.port, pull_mode="delta",
                                 push_codec="int8")
            rng = np.random.default_rng(CHAOS_SEED)
            for i in range(15):
                ts, _w, _avg, _cal = cl.pull(0)
                # one-hot pushes keep the model delta genuinely sparse
                # (the test_dataplane pattern): XDELTA pays only when
                # nnz*8 < d*4, and a dense push changes every coordinate
                g = np.zeros(d, np.float32)
                g[int(rng.integers(0, d))] = 0.5
                cl.push(0, ts, g)
            assert cl.pull_wenc.get("xdelta", 0) > 0, cl.pull_wenc
            cl.bye()
        finally:
            ps.stop()
        snap = profiler.active().snapshot()
        for z in _FIVE_WIRE_ZONES:
            assert snap["zones"].get(z, {}).get("ns", 0) > 0, (
                z, sorted(snap["zones"]))
            assert snap["zones"][z]["calls"] > 0, z
        # and the sampler ran alongside (statistical: just non-empty)
        assert snap["samples"] > 0
        assert snap["stacks"]

    def _worker(self, port, tmp, flight_dir):
        env = dict(os.environ)
        env.update({
            "PS_ROLE": "worker", "PS_PORT": str(port),
            "PS_WORKER_ID": "0", "PS_NUM_WORKER_PROCS": "1",
            "PS_NUM_ITER": "1000000", "PS_EVAL": "0",
            "JAX_PLATFORMS": "cpu",
            "PS_METRICS": "1",
            "ASYNCTPU_ASYNC_METRICS_PORT": "0",
            "ASYNCTPU_ASYNC_FLIGHT_DIR": flight_dir,
            "ASYNCTPU_ASYNC_FLIGHT_FLUSH_S": "0.2",
            "ASYNCTPU_ASYNC_PROF_ENABLED": "1",
            "ASYNCTPU_ASYNC_PROF_HZ": "97",
            "ASYNCTPU_ASYNC_PULL_MODE": "delta",
            "ASYNCTPU_ASYNC_CODEC_PUSH": "int8",
        })
        return subprocess.Popen(
            [sys.executable, str(CHILD)], env=env,
            stdout=subprocess.PIPE,
            stderr=open(os.path.join(tmp, "w0.stderr.log"), "w"),
            text=True,
        )

    def test_two_process_status_then_sigkill_flight_profile(
            self, tmp_path, devices8):
        """THE two-process acceptance + the chaos rider in one run: a
        real worker child (delta pulls, int8 pushes, profiling on)
        serves a per-role zone decomposition on its /api/status with
        the wire zones separately non-zero; then a seeded SIGKILL, and
        the harvested flight dump carries a non-empty profile snapshot.
        Rides every bin/chaos_sweep.py seed."""
        from asyncframework_tpu.conf import global_conf
        from asyncframework_tpu.metrics.observer import ClusterObserver
        from asyncframework_tpu.parallel import ps_dcn

        global_conf().set("async.pull.mode", "delta")
        flight_dir = str(tmp_path / "flight")
        cfg = _make_cfg(num_workers=8, num_iterations=10 ** 6, gamma=1.2,
                        printer_freq=50, calibration_iters=20,
                        run_timeout_s=120.0)
        profiler.install("ps", hz=97.0)  # PS side of the two-process run
        ps = ps_dcn.ParameterServer(cfg, 24, 4096, device=devices8[0],
                                    port=0).start()
        obs = ClusterObserver(interval_s=0.0, history_dir="",
                              flight_dirs=[flight_dir])
        worker = None
        try:
            worker = self._worker(ps.port, str(tmp_path), flight_dir)
            first = json.loads(worker.stdout.readline())
            mport = first["metrics_port"]
            assert mport, "child never announced its telemetry port"
            # seeded progress gate: enough pushes that every codec and
            # delta path has run on both sides
            need = 40 + (CHAOS_SEED % 30)
            deadline = time.monotonic() + 120.0
            while time.monotonic() < deadline:
                if sum(ps.accepted_by_wid.values()) >= need:
                    break
                time.sleep(0.02)
            else:
                pytest.fail("run never reached the seeded progress gate")
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{mport}/api/status",
                    timeout=5.0) as r:
                status = json.loads(r.read())
            wz = status["profile"]["zones"]
            # worker side: frame pump both ways + int8 quantize -- the
            # zones where a WORKER actually burns wire cycles.  The
            # XOR/CRC work of this run lives on the PS (dense D=24
            # training pushes keep XDELTA from paying, so the worker
            # never decodes a delta -- the PS still encodes and CRCs
            # every have-pull).
            for z in ("wire.encode", "wire.decode", "wire.quantize"):
                assert wz.get(z, {}).get("ns", 0) > 0, (z, sorted(wz))
            assert status["profile"]["role"].startswith("worker")
            # PS side of the SAME run: all five wire zones, separately
            # attributed and non-zero (frame pump, delta XOR encode,
            # version CRC, int8 decode_grad)
            pz = profiler.active().snapshot()["zones"]
            for z in _FIVE_WIRE_ZONES:
                assert pz.get(z, {}).get("ns", 0) > 0, (z, sorted(pz))
            # one flush cadence so the dump on disk is fresh, then kill
            time.sleep(0.5)
            os.kill(worker.pid, signal.SIGKILL)
            worker.wait(timeout=30.0)
            assert obs.harvest_flight() >= 1, (
                f"no dump harvested from {flight_dir}: "
                f"{os.listdir(flight_dir) if os.path.isdir(flight_dir) else 'missing'}")
            dumps = [d for d in obs.history.flight_dumps().values()
                     if d.get("pid") == worker.pid]
            assert dumps, "no flight dump from the SIGKILLed child"
            prof = dumps[0].get("profile")
            assert isinstance(prof, dict) and prof.get("zones"), (
                "flight dump carries no profile snapshot")
            assert prof["samples"] > 0
            assert any(z.startswith("wire.") for z in prof["zones"])
            # the harvest also folded it into the profile store
            assert obs.history.profile_snapshots()
        finally:
            if worker is not None and worker.poll() is None:
                worker.kill()
                worker.wait(timeout=10.0)
            if worker is not None and worker.stdout:
                worker.stdout.close()
            ps.stop()
