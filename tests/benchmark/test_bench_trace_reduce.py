"""The reduction from a profiler trace to busy/idle, module time and gaps.

Checked twice: on hand-made events, where every number can be worked out
on paper, and on a small trace recorded on the v5e in PR 22
(``fixtures/mnist8m-asgd.steady.xplane.pb``: a traced run of
``mnist8m-asgd.steady``, seed 2, cut with TensorFlow's ``xplane_pb2`` to the
chip's ``XLA Ops`` and ``XLA Modules`` lines and the host tracer's lines
between 1.0 s and 2.5 s of the profiler window, names kept and event stats
dropped, so that it stays under 200 kB).  The numbers asserted for it come
from a second reduction made straight from the protobuf, without
``ProfileData`` and without this module: 5,188 op events from 786,509.75 ns
to 1,494,907,693.42 ns after the cut, union 1,434,200,738.5 ns.
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import trace_reduce as tr  # noqa: E402

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures",
                       "mnist8m-asgd.steady.xplane.pb")


def test_union_merges_overlaps_and_keeps_order():
    assert tr.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5), (3, 4)]
    assert tr.union([]) == []


def _events():
    # chip 0: ops cover [0,1] u [2,4] of a [0,5] window set by chip 1;
    # chip 1: one op over [4,5]
    chip0 = {
        "ops": [("fusion.1", 0.0, 1.0), ("gather.2", 2.0, 3.5),
                ("fusion.1", 3.0, 4.0)],
        "modules": [("jit_step(11)", 0.0, 1.0), ("jit_step(11)", 2.0, 4.0),
                    ("jit_apply(12)", 3.9, 4.0)],
    }
    chip1 = {"ops": [("copy.3", 4.0, 5.0)], "modules": []}
    host = [("ps-updater", "PjitFunction(apply)", 1.1, 1.9),
            ("python", "PjitFunction(step)", 1.0, 1.3),
            ("executor-1", "block_until_ready", 0.0, 3.9)]
    return {"chips": {0: chip0, 1: chip1}, "host": host}


def test_reduce_on_hand_made_events():
    out = tr.reduce(_events())
    assert out["window_s"] == pytest.approx(5.0)
    assert out["chips"][0]["busy_s"] == pytest.approx(3.0)
    assert out["chips"][1]["busy_s"] == pytest.approx(1.0)
    assert out["busy_s"] == pytest.approx(2.0)  # mean over the chips
    assert out["idle_share"] == pytest.approx(0.6)
    assert out["chips"][0]["longest_gap_s"] == pytest.approx(1.0)
    step = out["modules"]["jit_step"]
    assert step["count"] == 2 and step["median_s"] == pytest.approx(1.5)
    assert out["modules"]["jit_apply"]["total_s"] == pytest.approx(0.1)
    ops = dict(out["device_ops"])
    assert ops["fusion.1"] == pytest.approx(2.0)
    assert out["device_ops"][0][0] == "fusion.1"  # most time first
    gaps = dict(out["idle_gaps"])
    # chip 0's gaps [1,2] and [4,5]: the wait overlaps the first most (the
    # whole gap goes to it), nothing overlaps the second; chip 1's gap [0,4]
    # is the wait's too; seconds are means over the two chips
    assert gaps["block_until_ready"] == pytest.approx((1.0 + 4.0) / 2)
    assert gaps[tr.HOST_IDLE] == pytest.approx(1.0 / 2)


def test_reduce_cut_to_a_window_clips_ops_and_drops_cut_programs():
    # [0.5, 4.5] of the same events: chip 0's ops cover [0.5,1] u [2,4],
    # chip 1's one op is clipped to [4,4.5]; the first step starts before
    # the window and does not count, the second and the apply do
    out = tr.reduce(_events(), window=(0.5, 4.5))
    assert out["window_s"] == pytest.approx(4.0)
    assert out["chips"][0]["busy_s"] == pytest.approx(0.5 + 2.0)
    assert out["chips"][1]["busy_s"] == pytest.approx(0.5)
    assert out["chips"][1]["longest_gap_s"] == pytest.approx(3.5)
    assert out["idle_share"] == pytest.approx(1.0 - 1.5 / 4.0)
    assert out["modules"]["jit_step"]["count"] == 1
    assert out["modules"]["jit_step"]["median_s"] == pytest.approx(2.0)
    assert out["modules"]["jit_apply"]["count"] == 1
    ops = dict(out["device_ops"])
    assert ops["fusion.1"] == pytest.approx(0.5 + 1.0)
    assert ops["copy.3"] == pytest.approx(0.5)
    # [0.5, 3.95]: the second step and the apply end after it; chip 1 ran
    # nothing there
    out = tr.reduce(_events(), window=(0.5, 3.95))
    assert out["modules"] == {}
    assert out["chips"][1]["busy_s"] == 0.0
    assert "copy.3" not in dict(out["device_ops"])
    # a window wider than the events changes nothing; one outside them, or
    # one in which nothing ran, reduces to nothing
    assert tr.reduce(_events(), window=(-1.0, 9.0)) == tr.reduce(_events())
    assert tr.reduce(_events(), window=(6.0, 7.0)) is None
    assert tr.reduce(_events(), window=(1.2, 1.8)) is None


def test_no_device_op_reduces_to_nothing():
    assert tr.reduce({"chips": {}, "host": []}) is None
    assert tr.reduce({"chips": {0: {"ops": [], "modules": []}}, "host": []}) is None


def test_module_name_strips_the_run_id():
    assert tr.module_name("jit_step(5213984398)") == "jit_step"
    assert tr.module_name("jit_step") == "jit_step"


# ------------------------------------------------- the trace from the chip


@pytest.fixture(scope="module")
def chip_trace():
    return tr.reduce_file(FIXTURE)


def test_fixture_busy_and_idle_match_the_protobuf(chip_trace):
    assert set(chip_trace["chips"]) == {0}
    # ProfileData hands out whole nanoseconds: 2.4 us over 5,188 events
    assert chip_trace["window_s"] == pytest.approx(1.4941211837, abs=1e-8)
    assert chip_trace["busy_s"] == pytest.approx(1.4342007385, abs=1e-5)
    assert chip_trace["idle_share"] == pytest.approx(0.040104, abs=1e-5)
    assert chip_trace["chips"][0]["longest_gap_s"] == pytest.approx(0.05654996, abs=1e-7)


def test_fixture_cut_to_a_window_keeps_whole_steps_only():
    # on ProfileData's clock the fixture's events run from 1.05 s to 2.55 s
    # after the profiler's start: one second in the middle
    cut = tr.reduce_file(FIXTURE, window=(1.3, 2.3))
    assert cut["window_s"] == pytest.approx(1.0)
    # 16.56 ms a step: 60 fit a second, less the two cut at the ends and
    # the 56.5 ms gap, which lies inside
    assert cut["modules"]["jit_step"]["count"] == 56
    assert cut["modules"]["jit_step"]["median_s"] == pytest.approx(16.5587e-3, abs=1e-6)
    assert cut["busy_s"] == pytest.approx(0.940538, abs=1e-5)
    assert cut["idle_share"] == pytest.approx(0.059462, abs=1e-5)
    assert cut["chips"][0]["longest_gap_s"] == pytest.approx(0.05654996, abs=1e-7)


def test_fixture_module_times_are_the_steps_and_the_applies(chip_trace):
    step = chip_trace["modules"]["jit_step"]
    assert step["count"] == 86
    assert step["median_s"] == pytest.approx(16.558789e-3, abs=1e-8)
    assert step["total_s"] == pytest.approx(1.424126743, abs=1e-6)
    apply = chip_trace["modules"]["jit_apply"]
    assert apply["count"] == 83
    assert apply["median_s"] == pytest.approx(1.46375e-6, abs=1e-9)


def test_fixture_breakdown_names_the_compaction_and_the_relayout(chip_trace):
    ops = chip_trace["device_ops"]
    assert len(ops) == 10 and all(len(n) <= tr.OP_NAME_CHARS for n, _s in ops)
    # the nonzero compaction over the 1,012,500-row mask, then the copy of
    # the whole bf16 shard from column-major to row-major, every step
    assert ops[0][0].startswith("%fusion.2 = s32[103064]")
    assert ops[0][1] == pytest.approx(0.770935, abs=1e-5)
    assert ops[1][0].startswith("%copy.5 = bf16[1012500,784]{1,0")
    assert ops[1][1] == pytest.approx(0.448820, abs=1e-5)
    gaps = dict(chip_trace["idle_gaps"])
    assert gaps[tr.HOST_IDLE] == pytest.approx(0.05654996, abs=1e-7)
    assert gaps["PjitFunction(apply)"] == pytest.approx(0.00152622, abs=1e-7)


def test_metric_readers_take_their_numbers_from_the_trace(chip_trace):
    from benchmark import manifest as manifest_mod, roofline

    man = manifest_mod.Manifest()
    run = {
        "peaks": roofline.peaks("TPU v5 lite"),
        "plan": {"batch_rate": 0.1},
        "data": {"kind": "dense", "shard_rows": [1_012_500] * 8, "d": 784,
                 "itemsize": 2},
    }
    assert man.metric_reader("step_device_ms").read(run, chip_trace) == \
        pytest.approx(16.558789, abs=1e-5)
    assert man.metric_reader("device_idle").read(run, chip_trace) == \
        pytest.approx(4.0104, abs=1e-3)
    # 160.2 MB needed a step over 16.56 ms is 9.67 GB/s of 819
    assert man.metric_reader("step_roofline").read(run, chip_trace) == \
        pytest.approx(1.1812, abs=1e-3)
    for name in ("step_device_ms", "device_idle", "step_roofline"):
        assert man.metric_reader(name).read(run, None) is None
