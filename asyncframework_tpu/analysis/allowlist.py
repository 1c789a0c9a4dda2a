"""The repo's lint suppressions.  EVERY entry carries a reason string --
``run_lint`` refuses an empty one -- and ``bin/async-lint --list-allow``
renders this file, so the allowlist is itself documentation.  There is
no inline-pragma escape hatch: a suppression that is not visible here
does not exist.

Policy (ARCHITECTURE.md "Correctness tooling"): an entry is acceptable
only when the flagged code is (a) correct for a reason the rule's
heuristic cannot see, and (b) the reason is written down well enough
that a reviewer can re-check it when the code changes.  Prefer fixing
the code; the list shrinking over time is the healthy direction.
"""

from __future__ import annotations

from typing import Tuple

from asyncframework_tpu.analysis.core import Allow

ALLOWLIST: Tuple[Allow, ...] = (
    # ------------------------------------------------------------- locks
    # The lock rule exists for SERVER hot locks (the PS model lock class:
    # many threads convoy behind one holder's I/O).  The entries below
    # are client-side locks whose entire JOB is to serialize I/O on one
    # channel; the "convoy" is one known peer thread, by design.
    Allow(
        "lock-blocking-call", "asyncframework_tpu/parallel/ps_dcn.py",
        "_win_lock:connect",
        "pipelined push window (_win_lock): reconnect+replay must be "
        "atomic against push_start sends or replayed and fresh pushes "
        "interleave out of FIFO order and ACK pairing breaks; "
        "contention is exactly two threads (sender + reaper), the "
        "documented window contract",
    ),
    Allow(
        "lock-blocking-call", "asyncframework_tpu/parallel/shardgroup.py",
        "_restart_lock:wait",
        "shard restart path (_restart_lock): serializing "
        "kill->wait->respawn per controller is the point -- two "
        "monitors relaunching the same shard concurrently would "
        "double-spawn it; only the monitor thread ever takes this lock",
    ),
    Allow(
        "lock-blocking-call", "asyncframework_tpu/parallel/shardgroup.py",
        "_restart_lock:_oneshot",
        "shard restart path (_restart_lock): the post-relaunch SETMAP "
        "epoch fan-out must complete before another restart can "
        "re-plan the map; same single-monitor-thread lock as above",
    ),
    Allow(
        "lock-blocking-call", "asyncframework_tpu/streaming/log_net.py",
        "_lock:call",
        "RemoteLogTopic._call (client channel lock): one framed "
        "connection, one in-flight op -- the lock IS the channel's "
        "serialization contract for thread-safe producers; a convoy "
        "here is callers of the same client object taking turns, "
        "which is the documented semantics",
    ),
    # ------------------------------------------------------------- conf
    Allow(
        "conf-dead-knob", "asyncframework_tpu/conf.py",
        "async.serve.replicas",
        "its one reader was the serve arm of the root-level harness "
        "that benchmark/run.py superseded, deleted by PR 27, whose "
        "issue holds conf.py's key count where it was; deploy/k8s.py "
        "takes the count as --serving N.  ROADMAP Design 7 records the "
        "debt: delete the key, or read it there, with the next conf "
        "change",
    ),
    # ---------------------------------------------------------- metrics
    Allow(
        "metrics-unregistered-totals",
        "asyncframework_tpu/metrics/registry.py", "all_totals",
        "the registry's own aggregator: it IS the walk over every "
        "registered family, registering it would recurse",
    ),
    Allow(
        "metrics-unregistered-totals",
        "asyncframework_tpu/net/retry.py", "retry_totals",
        "aggregated INTO the registered `net` family by net_totals() "
        "(same exemption as the PR 7 runtime audit): registering it "
        "separately would double-count every retry on /metrics",
    ),
)
