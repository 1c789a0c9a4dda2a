"""Relaycast distribution plane (ISSUE 12): peer-relayed versioned model
distribution.

The correctness spine:

- the tree is a pure function of (replica count, fanout): every node
  computes the same parent with zero coordination, child sets partition
  the replicas, depth is logarithmic;
- a relayed model is ALWAYS a version the PS actually published: every
  hop re-validates the version CRC (full peer payloads included -- a
  peer is never authoritative), and any mismatch re-homes the child to
  the root (direct SUBSCRIBE, the existing safe path);
- epoch fencing gates every hop: a stale-epoch fetch is REJECT_FENCED,
  and a parent serving versions from a superseded epoch is refused
  client-side -- a deposed peer can never poison the subtree;
- PS egress is O(fanout): with the tree on, subscribe bytes at the PS
  grow with the root's child count, not the replica count (the direct-
  SUBSCRIBE control is the N x baseline);
- a SIGKILLed interior node degrades to root traffic for its subtree,
  never to staleness or torn models (the chaos acceptance, seeded, on
  REAL OS processes -- rides every bin/chaos_sweep.py seed).
"""

import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from asyncframework_tpu.conf import set_global_conf
from asyncframework_tpu.metrics import reset_totals
from asyncframework_tpu.net import frame as _frame
from asyncframework_tpu.net import faults, wiredelta
from asyncframework_tpu.net.retry import reset_breakers
from asyncframework_tpu.parallel import ps_dcn
from asyncframework_tpu.relaycast import (
    ROOT,
    RelayNode,
    RelaySource,
    children_of,
    depth_of,
    parent_index,
)
from asyncframework_tpu.relaycast import metrics as rmetrics
from asyncframework_tpu.serving.replica import ModelReplica
from asyncframework_tpu.solvers import SolverConfig

pytestmark = pytest.mark.relay

REPO = Path(__file__).parent.parent
CHAOS_SEED = int(os.environ.get("ASYNC_CHAOS_SEED", "7"))


def make_cfg(**kw):
    defaults = dict(
        num_workers=2, num_iterations=10_000, gamma=0.5, taw=2 ** 31 - 1,
        batch_rate=0.3, bucket_ratio=0.0, printer_freq=100, seed=42,
        calibration_iters=4, run_timeout_s=60.0,
    )
    defaults.update(kw)
    return SolverConfig(**defaults)


@pytest.fixture(autouse=True)
def _clean_state():
    reset_totals()
    reset_breakers()
    faults.clear()
    yield
    reset_totals()
    reset_breakers()
    faults.clear()
    set_global_conf(None)


def start_ps(devices, cfg=None, d=64, n=256):
    cfg = cfg or make_cfg()
    ps = ps_dcn.ParameterServer(cfg, d, n, device=devices[0],
                                port=0).start()
    return ps, d


def push_once(cl, wid, d, g=None, scale=0.05, seed_rng=None):
    ts, _w, _avg, _cal = cl.pull(wid)
    if g is None:
        rng = seed_rng or np.random.default_rng(0)
        g = (scale * rng.normal(size=d)).astype(np.float32)
    cl.push(wid, ts, np.asarray(g, np.float32))


def fetch_raw(port, have=None, ep=None, rport=None):
    """One raw RELAY_FETCH frame against a node."""
    hdr = {"op": "RELAY_FETCH", "rid": 99}
    if have is not None:
        hdr["have"] = have
    if ep is not None:
        hdr["ep"] = ep
    if rport is not None:
        hdr["rport"] = rport
    sock = _frame.connect(("127.0.0.1", port))
    try:
        _frame.send_msg(sock, hdr)
        return _frame.recv_msg(sock)
    finally:
        sock.close()


# ------------------------------------------------------------------ the plan
class TestTreePlan:
    @pytest.mark.parametrize("n,k", [(1, 1), (2, 2), (8, 2), (9, 2),
                                     (27, 3), (100, 4), (5, 8)])
    def test_plan_is_a_partitioned_forest(self, n, k):
        roots = [i for i in range(n) if parent_index(i, k) == ROOT]
        assert roots == list(range(min(k, n)))
        seen = set(roots)
        for i in range(n):
            kids = children_of(i, n, k)
            assert len(kids) <= k
            for c in kids:
                assert parent_index(c, k) == i
                assert c not in seen  # each node has ONE parent
                seen.add(c)
        assert seen == set(range(n))  # every replica is in the forest

    @pytest.mark.parametrize("n,k", [(64, 2), (64, 4), (1000, 4)])
    def test_depth_is_logarithmic(self, n, k):
        import math

        max_depth = max(depth_of(i, k) for i in range(n))
        assert max_depth <= math.ceil(math.log(n + 1, k)) + 1

    def test_bad_inputs(self):
        with pytest.raises(ValueError):
            parent_index(-1, 2)
        with pytest.raises(ValueError):
            parent_index(3, 0)


# ------------------------------------------------------------------ the node
def _publish(node, w, ts, crc=None, epoch=0, clock=None, done=False):
    wire = np.asarray(w, np.float32).tobytes()
    node.publish(ts, wire, crc if crc is not None else wiredelta.crc(wire),
                 clock if clock is not None else ts, ts, 0.0, done,
                 epoch=epoch)


class TestRelayNode:
    def test_empty_node_answers_err(self):
        node = RelayNode(rid=0, port=0, compress=False).start()
        try:
            hdr, _ = fetch_raw(node.port)
            assert hdr["op"] == "ERR"
        finally:
            node.stop()

    def test_fetch_shapes_full_then_nm_then_delta(self, rng):
        node = RelayNode(rid=0, port=0, compress=False).start()
        try:
            w1 = rng.normal(size=64).astype(np.float32)
            _publish(node, w1, ts=1)
            hdr, payload = fetch_raw(node.port)
            assert hdr["op"] == "RELAY_MODEL" and hdr["wenc"] == "full"
            got = wiredelta.decode("full", payload, 0, None, None)
            assert got.tobytes() == w1.tobytes()
            assert wiredelta.crc(got) == hdr["crc"]
            # same version + have -> header-only NOT_MODIFIED
            hdr, payload = fetch_raw(node.port, have=1)
            assert hdr["wenc"] == "nm" and payload == b""
            # sparse change -> xdelta against the stored basis
            w2 = w1.copy()
            w2[5] += 0.25
            _publish(node, w2, ts=2)
            hdr, payload = fetch_raw(node.port, have=1)
            assert hdr["wenc"] == "xdelta" and hdr["nnz"] == 1
            got = wiredelta.decode("xdelta", payload, 1, w1, hdr["crc"])
            assert got is not None and got.tobytes() == w2.tobytes()
        finally:
            node.stop()

    def test_dense_change_ships_xfull_and_compresses(self, rng):
        from asyncframework_tpu.net import wirecodec

        node = RelayNode(rid=0, port=0, compress=True).start()
        try:
            w1 = rng.normal(size=1024).astype(np.float32)
            w2 = (w1 * (1 + 1e-4 * rng.normal(size=1024))).astype(
                np.float32)
            _publish(node, w1, ts=1)
            _publish(node, w2, ts=2)
            hdr, payload = fetch_raw(node.port, have=1)
            assert hdr["wenc"] == "xfull"
            assert hdr.get("cz") == "zs"
            assert len(payload) * 2 <= w1.nbytes  # the >= 2x cut
            raw = wirecodec.decompress_model_part(hdr, payload)
            got = wiredelta.decode("xfull", raw, 0, w1, hdr["crc"])
            assert got is not None and got.tobytes() == w2.tobytes()
        finally:
            node.stop()

    def test_publish_is_monotone(self, rng):
        node = RelayNode(rid=0, port=0, compress=False)
        w1, w2 = (rng.normal(size=8).astype(np.float32) for _ in range(2))
        _publish(node, w2, ts=5)
        _publish(node, w1, ts=3)  # late straggler must not roll back
        assert node.current().ts == 5

    def test_store_evicts_oldest(self, rng):
        node = RelayNode(rid=0, port=0, versions=2, compress=False)
        for ts in (1, 2, 3):
            _publish(node, rng.normal(size=8).astype(np.float32), ts=ts)
        assert node.basis_for(1) is None
        assert node.basis_for(3) is not None

    def test_fence_admission_on_fetch_and_offer(self, rng):
        node = RelayNode(rid=0, port=0, compress=False).start()
        try:
            _publish(node, rng.normal(size=8).astype(np.float32), ts=1,
                     epoch=2)
            assert node.epoch == 2
            # stale-epoch fetch -> REJECT_FENCED with the newest epoch
            hdr, _ = fetch_raw(node.port, ep=1)
            assert hdr["op"] == "REJECT_FENCED" and hdr["epoch"] == 2
            assert rmetrics.relay_totals().get("fenced_hops", 0) == 1
            # current epoch serves; newer epoch advances our belief
            hdr, _ = fetch_raw(node.port, ep=2)
            assert hdr["op"] == "RELAY_MODEL"
            hdr, _ = fetch_raw(node.port, ep=3)
            assert hdr["op"] == "RELAY_MODEL"
            assert node.epoch == 3
            # unstamped op (fencing-off client) is always served
            hdr, _ = fetch_raw(node.port)
            assert hdr["op"] == "RELAY_MODEL"
        finally:
            node.stop()

    def test_children_learned_from_fetch_and_offered(self, rng):
        parent = RelayNode(rid=0, port=0, compress=False,
                           fanout=2).start()
        offers = []
        child = RelayNode(rid=1, port=0, compress=False,
                          on_offer=lambda: offers.append(1)).start()
        try:
            _publish(parent, rng.normal(size=8).astype(np.float32), ts=1)
            fetch_raw(parent.port, rport=child.port)
            assert ("127.0.0.1", child.port) in parent.children()
            # fanout-bounded LRU: two newer registrants displace the
            # oldest entries; a later fetch from the real child renews
            # its slot (registration IS the renewal), displacing one of
            # them in turn -- a registrant that stopped fetching can
            # never squat a slot a live child keeps renewing
            fetch_raw(parent.port, rport=65000)
            fetch_raw(parent.port, rport=65001)
            assert len(parent.children()) == 2
            assert ("127.0.0.1", child.port) not in parent.children()
            fetch_raw(parent.port, rport=child.port)
            assert ("127.0.0.1", child.port) in parent.children()
            _publish(parent, rng.normal(size=8).astype(np.float32), ts=2)
            delivered = parent.offer_children()
            assert delivered == 1  # the real child; the fake one strikes
            assert offers == [1]
            assert child.offered_ts == 2
        finally:
            parent.stop()
            child.stop()

    def test_stale_parent_reply_never_rolls_served_model_back(
            self, devices8, rng):
        """Review fix: monotone RETURN, not just monotone store.  A
        child that re-homed to the root and serves v2 polls a parent
        still holding v1; the parent's (CRC-valid!) v1 FULL reply must
        not be handed to the replica -- the source answers v2 from its
        own store."""
        ps, d = start_ps(devices8)
        parent = RelayNode(rid=0, port=0).start()
        node = RelayNode(rid=1, port=0)
        try:
            cl = ps_dcn.PSClient("127.0.0.1", ps.port, pull_mode="full")
            push_once(cl, 0, d)
            # parent validates and stores v1
            RelaySource("127.0.0.1", ps.port, parent).subscribe(0)
            # the child, currently re-homed, gets v2 from the root
            src = RelaySource("127.0.0.1", ps.port, node,
                              parent=("127.0.0.1", parent.port), rid=1,
                              retry_parent_s=0.0)
            push_once(cl, 0, d)
            src._parent_dark_until = time.monotonic() + 60
            got2 = src.subscribe(1)
            assert got2[0] == 2
            # cooloff expires; the parent (still at v1) answers the next
            # poll -- subscribe must return v2's bytes, not v1's
            src._parent_dark_until = 0.0
            got3 = src.subscribe(1)
            assert got3[0] == 2
            assert got3[1].tobytes() == got2[1].tobytes()
            assert rmetrics.relay_totals().get("stale_replies", 0) == 1
        finally:
            parent.stop()
            node.stop()
            ps.stop()


# ---------------------------------------------------------------- the source
class TestRelaySource:
    def test_parent_chain_is_byte_exact(self, devices8, rng):
        """root-child and grandchild sources deliver the PS's bytes
        identically through the relay hop."""
        ps, d = start_ps(devices8)
        n0 = RelayNode(rid=0, port=0).start()
        n1 = RelayNode(rid=1, port=0).start()
        try:
            s0 = RelaySource("127.0.0.1", ps.port, n0)
            s1 = RelaySource("127.0.0.1", ps.port, n1,
                             parent=("127.0.0.1", n0.port), rid=1)
            cl = ps_dcn.PSClient("127.0.0.1", ps.port, pull_mode="full")
            seed_rng = np.random.default_rng(1)
            for v in range(1, 6):
                push_once(cl, 0, d, seed_rng=seed_rng)
                got0 = s0.subscribe(0)
                got1 = s1.subscribe(1)
                assert got0[0] == got1[0] == v
                assert got0[1].tobytes() == got1[1].tobytes()
            assert s1.via_parent >= 4  # boot round may fall to root
            assert s1.pull_wenc["full"] + s1.pull_wenc.get("xfull", 0) \
                + s1.pull_wenc["xdelta"] + s1.pull_wenc["nm"] >= 5
        finally:
            n0.stop()
            n1.stop()
            ps.stop()

    def test_dead_parent_rehomes_to_root_with_cooloff(self, devices8,
                                                      rng):
        ps, d = start_ps(devices8)
        node = RelayNode(rid=1, port=0)
        try:
            # parent endpoint nobody listens on
            src = RelaySource("127.0.0.1", ps.port, node,
                              parent=("127.0.0.1", 1), rid=1,
                              retry_parent_s=30.0)
            cl = ps_dcn.PSClient("127.0.0.1", ps.port, pull_mode="full")
            push_once(cl, 0, d)
            got = src.subscribe(1)
            assert got is not None and got[0] == 1
            assert rmetrics.relay_totals().get("rehomes", 0) == 1
            assert src.via_root == 1
            # cooloff: the next round goes straight to root, no re-dial
            push_once(cl, 0, d)
            got = src.subscribe(1)
            assert got[0] == 2
            assert rmetrics.relay_totals().get("rehomes", 0) == 1
        finally:
            node.stop()
            ps.stop()

    def test_empty_parent_falls_back_without_cooloff(self, devices8,
                                                     rng):
        ps, d = start_ps(devices8)
        parent = RelayNode(rid=0, port=0).start()  # alive, no model
        node = RelayNode(rid=1, port=0)
        try:
            src = RelaySource("127.0.0.1", ps.port, node,
                              parent=("127.0.0.1", parent.port), rid=1,
                              retry_parent_s=30.0)
            cl = ps_dcn.PSClient("127.0.0.1", ps.port, pull_mode="full")
            push_once(cl, 0, d)
            got = src.subscribe(1)
            assert got[0] == 1 and src.via_root == 1
            assert rmetrics.relay_totals().get("rehomes", 0) == 0
            # parent catches up; the NEXT round uses it (no cooloff)
            _publish(parent, got[1], ts=1)
            push_once(cl, 0, d)
            _publish(parent,
                     RelaySource("127.0.0.1", ps.port,
                                 RelayNode(rid=9, port=0)
                                 ).subscribe(9)[1], ts=2)
            got = src.subscribe(1)
            assert got[0] == 2 and src.via_parent == 1
        finally:
            parent.stop()
            node.stop()
            ps.stop()

    def test_corrupt_parent_bytes_rehome_never_serve(self, devices8,
                                                     rng):
        """A parent whose stored bytes rot serves nothing: CRC refuses
        both the delta and the full refetch, the child re-homes."""
        ps, d = start_ps(devices8)
        parent = RelayNode(rid=0, port=0).start()
        node = RelayNode(rid=1, port=0)
        try:
            cl = ps_dcn.PSClient("127.0.0.1", ps.port, pull_mode="full")
            push_once(cl, 0, d)
            psrc = RelaySource("127.0.0.1", ps.port, parent)
            psrc.subscribe(0)
            # rot the stored wire bytes behind the recorded CRC
            cur = parent.current()
            bad = bytearray(cur.wire)
            bad[0] ^= 0xFF
            cur.wire = bytes(bad)
            src = RelaySource("127.0.0.1", ps.port, node,
                              parent=("127.0.0.1", parent.port), rid=1,
                              retry_parent_s=30.0)
            got = src.subscribe(1)
            assert got[0] == 1
            # the served model came from the ROOT and is byte-correct
            snap = ps._model_snap()
            assert got[1].tobytes() == snap.w_host.tobytes()
            assert rmetrics.relay_totals().get("crc_rejects", 0) >= 1
            assert rmetrics.relay_totals().get("rehomes", 0) == 1
        finally:
            parent.stop()
            node.stop()
            ps.stop()

    def test_stale_epoch_parent_is_refused(self, devices8, rng):
        """A parent holding versions from a superseded epoch cannot
        feed a child that already knows the newer epoch."""
        ps, d = start_ps(devices8)
        parent = RelayNode(rid=0, port=0).start()
        node = RelayNode(rid=1, port=0)
        try:
            cl = ps_dcn.PSClient("127.0.0.1", ps.port, pull_mode="full")
            push_once(cl, 0, d)
            psrc = RelaySource("127.0.0.1", ps.port, parent)
            got = psrc.subscribe(0)
            # the parent's stored version carries epoch 1; the child
            # believes epoch 2 (a failover happened upstream)
            cur = parent.current()
            cur.vep = 1
            parent.epoch = 0  # parent never saw fencing: serves anyway
            node.epoch = 2
            src = RelaySource("127.0.0.1", ps.port, node,
                              parent=("127.0.0.1", parent.port), rid=1,
                              retry_parent_s=30.0)
            got2 = src.subscribe(1)
            assert got2[0] == 1  # served -- by the root, not the parent
            assert src.via_root == 1 and src.via_parent == 0
            assert rmetrics.relay_totals().get(
                "stale_epoch_rejects", 0) == 1
        finally:
            parent.stop()
            node.stop()
            ps.stop()

    def test_stale_vep_reject_skips_futile_full_refetch(self, devices8,
                                                        rng):
        """Review fix: a header-level stale-vep reject must NOT trigger
        the full refetch (the same parent rejects the full identically)
        -- only payload decode failures earn it."""
        ps, d = start_ps(devices8)
        parent = RelayNode(rid=0, port=0).start()
        node = RelayNode(rid=1, port=0)
        try:
            cl = ps_dcn.PSClient("127.0.0.1", ps.port, pull_mode="full")
            push_once(cl, 0, d)
            RelaySource("127.0.0.1", ps.port, parent).subscribe(0)
            src = RelaySource("127.0.0.1", ps.port, node,
                              parent=("127.0.0.1", parent.port), rid=1,
                              retry_parent_s=30.0)
            got = src.subscribe(1)  # healthy round: node gains a basis
            assert got[0] == 1 and src.via_parent == 1
            # the parent's stored version goes epoch-stale
            parent.current().vep = 1
            node.epoch = 2
            push_once(cl, 0, d)
            fetches_before = parent.fetches
            got = src.subscribe(1)  # re-homes to root
            assert got[0] == 2 and src.via_root == 1
            # exactly ONE fetch hit the parent (no full refetch)
            assert parent.fetches == fetches_before + 1
            assert src.delta_fallbacks == 0
        finally:
            parent.stop()
            node.stop()
            ps.stop()

    def test_offers_are_async_off_the_refresh_path(self, devices8, rng):
        """Review fix: request_offers() returns immediately and the
        fan-out lands on the node's own offer thread."""
        parent = RelayNode(rid=0, port=0, compress=False,
                           fanout=2).start()
        offers = []
        child = RelayNode(rid=1, port=0, compress=False,
                          on_offer=lambda: offers.append(1)).start()
        try:
            _publish(parent, rng.normal(size=8).astype(np.float32), ts=1)
            fetch_raw(parent.port, rport=child.port)
            t0 = time.monotonic()
            parent.request_offers()
            assert time.monotonic() - t0 < 0.1  # no inline fan-out
            deadline = time.monotonic() + 5.0
            while not offers and time.monotonic() < deadline:
                time.sleep(0.02)
            assert offers == [1]
        finally:
            parent.stop()
            child.stop()

    def test_compress_off_dense_change_ships_plain_full(self, rng):
        """Review fix: without the compression transform XFULL is
        FULL-sized anyway and only adds a basis requirement -- the
        substitution must be gated on compress."""
        node = RelayNode(rid=0, port=0, compress=False).start()
        try:
            w1 = rng.normal(size=256).astype(np.float32)
            w2 = (w1 * 1.5).astype(np.float32)
            _publish(node, w1, ts=1)
            _publish(node, w2, ts=2)
            hdr, payload = fetch_raw(node.port, have=1)
            assert hdr["wenc"] == "full"
            got = wiredelta.decode("full", payload, 0, None, None)
            assert got.tobytes() == w2.tobytes()
        finally:
            node.stop()

    def test_fenced_child_adopts_epoch_from_parent(self, devices8, rng):
        """The other direction: a STALE child is REJECT_FENCED by its
        parent, adopts the minted epoch, and self-heals through the
        root."""
        ps, d = start_ps(devices8)
        parent = RelayNode(rid=0, port=0).start()
        node = RelayNode(rid=1, port=0)
        try:
            cl = ps_dcn.PSClient("127.0.0.1", ps.port, pull_mode="full")
            push_once(cl, 0, d)
            RelaySource("127.0.0.1", ps.port, parent).subscribe(0)
            parent.epoch = 5
            node.epoch = 1  # deposed view
            src = RelaySource("127.0.0.1", ps.port, node,
                              parent=("127.0.0.1", parent.port), rid=1,
                              retry_parent_s=30.0)
            got = src.subscribe(1)
            assert got is not None and got[0] == 1
            assert node.epoch == 5  # adopted the minted epoch
        finally:
            parent.stop()
            node.stop()
            ps.stop()


# --------------------------------------------------------- egress + offers
class TestEgressScaling:
    N_REPLICAS = 8
    VERSIONS = 6

    def _drive(self, devices, relay: bool):
        """N in-process replica sources, driven in topo order per
        version; returns the PS's SUBSCRIBE model-payload bytes."""
        ps, d = start_ps(devices, d=256)
        cl = ps_dcn.PSClient("127.0.0.1", ps.port, pull_mode="full")
        nodes, sources = [], []
        try:
            for rid in range(self.N_REPLICAS):
                node = RelayNode(rid=rid, port=0).start()
                p = parent_index(rid, 2)
                parent = (None if (not relay or p == ROOT)
                          else ("127.0.0.1", nodes[p].port))
                nodes.append(node)
                sources.append(RelaySource(
                    "127.0.0.1", ps.port, node, parent=parent, rid=rid))
            seed_rng = np.random.default_rng(2)
            wires = set()
            for v in range(self.VERSIONS):
                push_once(cl, 0, d, seed_rng=seed_rng)
                for rid in range(self.N_REPLICAS):  # topo order by plan
                    got = sources[rid].subscribe(rid)
                    assert got[0] == v + 1
                    wires.add(got[1].tobytes())
                assert len(wires) == v + 1  # all replicas byte-agree
            return ps.subscribe_model_bytes
        finally:
            for node in nodes:
                node.stop()
            ps.stop()

    def test_ps_egress_is_sublinear_with_relay_on(self, devices8):
        """THE acceptance: direct SUBSCRIBE is the N x control; the
        relay tree (fanout 2 -> 2 root children of 8 replicas) cuts PS
        subscribe egress to roughly the root-children share."""
        direct = self._drive(devices8, relay=False)
        reset_totals()
        relayed = self._drive(devices8, relay=True)
        assert direct > 0
        assert relayed < 0.5 * direct, (relayed, direct)


class TestRootOfferPath:
    def test_ps_offers_wake_relay_replicas(self, devices8, rng):
        """A relay replica with a LONG poll interval still tracks the
        model closely: the PS's offer loop announces each version and
        the replica fetches on the offer, not the poll."""
        ps, d = start_ps(devices8)
        rep = ModelReplica("127.0.0.1", ps.port, rid=0,
                           refresh_interval_s=30.0,  # poll ~ never
                           relay_port=0).start()
        try:
            cl = ps_dcn.PSClient("127.0.0.1", ps.port, pull_mode="full")
            # first refresh registers the rport with the PS
            deadline = time.monotonic() + 10
            while rep._served is None and time.monotonic() < deadline:
                time.sleep(0.05)
            assert rep._served is not None
            push_once(cl, 0, d)
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                served = rep._served
                if served is not None and served.ts >= 1:
                    break
                time.sleep(0.05)
            assert rep._served.ts >= 1, "offer never woke the replica"
            assert ps.relay_offers >= 1
        finally:
            rep.stop()
            ps.stop()


# ----------------------------------------------------------- chaos (seeded)
class TestInteriorKillAcceptance:
    @pytest.mark.chaos
    def test_sigkill_interior_node_children_rehome_to_root(
            self, devices8, tmp_path):
        """THE chaos acceptance (rides every chaos_sweep seed): a real
        3-process relay chain r0 <- r1 <- r2; r1 is SIGKILLed at a
        seeded point mid-distribution.  r2 must re-home to the root
        within the retry window and keep serving CRC-valid, current-
        epoch models -- never a torn or stale one."""
        rng_seed = np.random.default_rng(CHAOS_SEED)
        kill_after_version = int(rng_seed.integers(3, 7))
        ps, d = start_ps(devices8)
        procs = []
        try:
            env = dict(os.environ)
            env["JAX_PLATFORMS"] = "cpu"
            env["PYTHONPATH"] = str(REPO)
            env["ASYNCTPU_ASYNC_SERVE_REFRESH_INTERVAL_S"] = "0.02"
            env["ASYNCTPU_ASYNC_RELAY_PARENT_RETRY_S"] = "1.0"
            relay_ports = []
            for rid in range(3):
                cmd = [sys.executable, "-m",
                       "asyncframework_tpu.serving.cli", "replica",
                       "--ps", f"127.0.0.1:{ps.port}",
                       "--host", "127.0.0.1", "--rid", str(rid),
                       "--relay-port", "0"]
                if rid > 0:
                    cmd += ["--relay-parent",
                            f"127.0.0.1:{relay_ports[rid - 1]}"]
                p = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                                     stderr=subprocess.DEVNULL,
                                     env=env, cwd=str(REPO), text=True)
                procs.append(p)
                line = p.stdout.readline()
                assert line, f"replica {rid} never announced"
                relay_ports.append(json.loads(line)["relay_port"])
            ps_client = ps_dcn.PSClient("127.0.0.1", ps.port,
                                        pull_mode="full")
            crc_by_ts = {}
            seed_rng = np.random.default_rng(CHAOS_SEED + 1)
            killed = False
            for v in range(1, 13):
                push_once(ps_client, 0, d, seed_rng=seed_rng)
                snap = ps._model_snap()
                crc_by_ts[snap.ts] = snap.crc
                if v == kill_after_version and not killed:
                    os.kill(procs[1].pid, signal.SIGKILL)
                    killed = True
                time.sleep(0.25)
            assert killed
            # r2 (the killed node's child) must converge to the current
            # version within the re-home window
            deadline = time.monotonic() + 15.0
            final_ts = ps._clock
            status = None
            while time.monotonic() < deadline:
                hdr, _ = fetch_raw(relay_ports[2])
                if hdr.get("op") == "RELAY_MODEL" \
                        and int(hdr["ts"]) >= final_ts:
                    status = hdr
                    break
                time.sleep(0.2)
            assert status is not None, \
                f"r2 never reached ts {final_ts} after interior kill"
            # CRC assert: what r2 re-serves is exactly what the PS
            # published for that version -- never torn
            ts = int(status["ts"])
            assert ts in crc_by_ts
            assert int(status["crc"]) == crc_by_ts[ts]
        finally:
            for p in procs:
                try:
                    p.kill()
                except OSError:
                    pass
            ps.stop()


# --------------------------------------------------------------- replica API
class TestReplicaIntegration:
    def test_replica_status_carries_relay_section(self, devices8, rng):
        ps, d = start_ps(devices8)
        rep = ModelReplica("127.0.0.1", ps.port, rid=0,
                           refresh_interval_s=0.02,
                           relay_port=0).start()
        try:
            cl = ps_dcn.PSClient("127.0.0.1", ps.port, pull_mode="full")
            push_once(cl, 0, d)
            deadline = time.monotonic() + 10
            while rep._served is None and time.monotonic() < deadline:
                time.sleep(0.05)
            st = rep.status()
            assert "relay" in st
            assert st["relay"]["port"] == rep._relay_node.port
            assert st["relay"]["parent"] is None
        finally:
            rep.stop()
            ps.stop()

    def test_relay_off_replica_has_no_relay_surface(self, devices8):
        ps, _d = start_ps(devices8)
        rep = ModelReplica("127.0.0.1", ps.port, rid=0)
        try:
            assert rep._relay_node is None
            assert "relay" not in rep.status()
        finally:
            rep.stop()
            ps.stop()
