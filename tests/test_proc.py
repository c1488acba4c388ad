"""Multi-process cluster runtime (ISSUE 19): supervisor backoff /
crash-loop / clean-vs-crash discrimination units, orphan reaping,
ProcessDeath report shape, the spec grammar, and the tier-1
acceptance cluster — a REAL multi-process boot (mon + 2 OSDs, three
OS processes) that peers, serves a write, and reads it back
byte-identical.  The full 1/2/4/8 scaling curve rides behind
``slow`` (tests/test_chaos.py carries the SIGKILL storm scenario).
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import threading
import time

import pytest

from ceph_tpu.common.crash import build_process_report
from ceph_tpu.msg.messenger import wait_for
from ceph_tpu.proc import ClusterSpec, Supervisor
from ceph_tpu.proc.supervisor import _Child
from ceph_tpu.rados import Rados


# -- spec grammar -----------------------------------------------------------
def test_spec_plan_roundtrip(tmp_path):
    """plan() pins addresses once; save/load round-trips the layout
    byte-identically; roles() lists boot-phase order."""
    spec = ClusterSpec.plan(
        tmp_path, mons=3, osds=4, mgrs=1, mds=1, rgw=2,
        memstore=True, wal=True,
    )
    assert len(spec.mon_addrs) == 3
    assert len(set(spec.mon_addrs)) == 3  # distinct pinned ports
    assert len(spec.data["rgw_ports"]) == 2
    assert spec.data["pool_size"] == 3
    path = spec.save()
    again = ClusterSpec.load(path)
    assert again.data == spec.data
    roles = spec.roles()
    assert roles[:3] == ["mon.0", "mon.1", "mon.2"]
    assert roles[3] == "mgr.0"
    assert roles[4:8] == [f"osd.{i}" for i in range(4)]
    assert roles[8:] == ["mds.0", "rgw.0", "rgw.1"]
    assert spec.log_path("osd.3").name == "osd.3.log"
    assert spec.ready_path("mon.0").name == "mon.0.ready"
    with pytest.raises(ValueError):
        ClusterSpec.plan(tmp_path, mons=0)


def test_spec_fixed_port_seeding(tmp_path):
    """A nonzero mon_port seeds consecutive pinned ports (the vstart
    fixed-port mode)."""
    spec = ClusterSpec.plan(tmp_path, mons=3, mon_port=7700)
    assert [p for _h, p in spec.mon_addrs] == [7700, 7701, 7702]


# -- backoff schedule -------------------------------------------------------
def test_backoff_schedule_exponential_and_capped():
    """base·2^(n−1), capped — the systemd RestartSec ladder."""
    d = Supervisor.backoff_delay
    assert [d(n, 0.5, 30.0) for n in (1, 2, 3, 4, 5)] == [
        0.5, 1.0, 2.0, 4.0, 8.0,
    ]
    assert d(10, 0.5, 30.0) == 30.0  # capped
    assert d(0, 0.5, 30.0) == 0.5  # degenerate input clamps


# -- death discrimination (no real processes needed) ------------------------
class _FakeProc:
    """Stands in for a Popen the monitor already reaped."""

    def __init__(self, pid=4242):
        self.pid = pid

    def poll(self):
        return 0


def _unit_supervisor(tmp_path, **kw) -> Supervisor:
    spec = ClusterSpec.plan(
        tmp_path, mons=1, osds=0, mgrs=0, memstore=True
    )
    kw.setdefault("report_interval", 3600.0)  # no wire noise
    return Supervisor(spec, **kw)


def _fake_child(sup: Supervisor, role="test.0") -> _Child:
    child = _Child(role, [sys.executable, "-c", "pass"])
    child.proc = _FakeProc()
    child.spawned_at = time.monotonic()
    child.state = "running"
    sup.children[role] = child
    return child


def test_clean_exit_is_never_respawned_or_reported(tmp_path):
    """rc==0 means the daemon CHOSE to leave (Restart=on-failure):
    no backoff, no crash report, no restart counter."""
    sup = _unit_supervisor(tmp_path)
    child = _fake_child(sup)
    sup._on_death(child, 0)
    assert child.state == "exited"
    assert child.consecutive_crashes == 0
    assert not sup._crash_outbox
    assert sup.perf.dump()["l_proc_restarts"] == 0


def test_crash_schedules_backoff_and_files_report(tmp_path):
    """A signal death schedules a respawn after the backoff delay
    and files a ProcessDeath report naming the signal."""
    sup = _unit_supervisor(
        tmp_path, backoff_base=0.5, min_uptime=10.0
    )
    child = _fake_child(sup)
    t0 = time.monotonic()
    sup._on_death(child, -signal.SIGKILL)
    assert child.state == "backoff"
    assert child.consecutive_crashes == 1
    # first crash: respawn after ~backoff_base
    assert 0.3 <= child.respawn_at - t0 <= 0.8
    (report, resend), = sup._crash_outbox
    assert report["entity_name"] == "test.0"
    assert "SIGKILL" in report["exception"]
    assert report["meta"]["process_death"] is True
    assert resend >= 1
    # a second short-lived crash doubles the delay
    child.state = "running"
    child.spawned_at = time.monotonic()
    t0 = time.monotonic()
    sup._on_death(child, -signal.SIGSEGV)
    assert child.consecutive_crashes == 2
    assert 0.8 <= child.respawn_at - t0 <= 1.3


def test_uptime_past_min_resets_the_crash_streak(tmp_path):
    """A daemon that survived min_uptime starts a NEW streak on its
    next crash — a once-a-day crasher never reaches the cap."""
    sup = _unit_supervisor(tmp_path, min_uptime=0.05)
    child = _fake_child(sup)
    child.consecutive_crashes = 4  # history from a bad patch
    child.spawned_at = time.monotonic() - 1.0  # survived min_uptime
    sup._on_death(child, 1)
    assert child.consecutive_crashes == 1
    assert child.state == "backoff"


def test_crash_loop_cap_abandons_the_role(tmp_path):
    """More than crash_loop_cap consecutive short-lived crashes →
    the role is FAILED (no further respawns) and counted."""
    sup = _unit_supervisor(
        tmp_path, crash_loop_cap=3, min_uptime=10.0,
        backoff_base=0.01,
    )
    child = _fake_child(sup)
    for _ in range(3):
        sup._on_death(child, 1)
        assert child.state == "backoff"
        child.state = "running"
        child.spawned_at = time.monotonic()
    sup._on_death(child, 1)
    assert child.state == "failed"
    assert sup.perf.dump()["l_proc_crash_loops"] == 1


def test_crash_loop_cap_live_processes(tmp_path):
    """The same arc with REAL processes: a child argv that always
    exits 1 is respawned with backoff until the cap, then abandoned;
    restarts and crash-loops both land in the perf dump."""
    sup = _unit_supervisor(
        tmp_path, backoff_base=0.02, backoff_max=0.1,
        crash_loop_cap=2, min_uptime=10.0, poll_interval=0.02,
    )
    child = _Child(
        "loop.0", [sys.executable, "-c", "import sys; sys.exit(1)"]
    )
    sup.children["loop.0"] = child
    sup._spawn(child)
    sup._monitor = threading.Thread(
        target=sup._monitor_loop, daemon=True
    )
    sup._monitor.start()
    try:
        assert wait_for(
            lambda: sup.status()["loop.0"]["state"] == "failed", 15.0
        ), sup.status()
        st = sup.status()["loop.0"]
        assert st["consecutive_crashes"] == 3  # cap 2 → 3rd fails it
        dump = sup.perf.dump()
        assert dump["l_proc_restarts"] == 2
        assert dump["l_proc_crash_loops"] == 1
        # reports carry the exit status
        assert all(
            "exited with status 1" in r["exception"]
            for r, _n in sup._crash_outbox
        )
    finally:
        sup.stop()


def test_children_are_pinned_to_the_cpu_backend(tmp_path, monkeypatch):
    """No role owns the chip (CHIP_OWNER_ROLE is None): whatever the
    supervisor's own JAX_PLATFORMS says, a child gets ``cpu`` and its
    log says so."""
    from ceph_tpu.proc import supervisor as supmod

    assert supmod.CHIP_OWNER_ROLE is None
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    sup = _unit_supervisor(tmp_path)
    seen = tmp_path / "seen.txt"
    child = _Child(
        "osd.0",
        [
            sys.executable, "-c",
            "import os, sys; open(sys.argv[1], 'w').write("
            "os.environ['JAX_PLATFORMS'])",
            str(seen),
        ],
    )
    sup.children["osd.0"] = child
    try:
        sup._spawn(child)
        assert child.proc.wait(timeout=30) == 0
        assert seen.read_text() == "cpu"
        log = sup.spec.log_path("osd.0").read_text()
        assert "osd.0 runs with JAX_PLATFORMS=cpu" in log
    finally:
        sup.stop()


def test_clean_exit_live_process_not_respawned(tmp_path):
    """A real child exiting 0 stays down: state 'exited', zero
    restarts, empty outbox."""
    sup = _unit_supervisor(tmp_path, poll_interval=0.02)
    child = _Child("ok.0", [sys.executable, "-c", "pass"])
    sup.children["ok.0"] = child
    sup._spawn(child)
    sup._monitor = threading.Thread(
        target=sup._monitor_loop, daemon=True
    )
    sup._monitor.start()
    try:
        assert wait_for(
            lambda: sup.status()["ok.0"]["state"] == "exited", 10.0
        )
        time.sleep(0.1)  # give a wrong respawn a chance to happen
        assert sup.status()["ok.0"]["restarts"] == 0
        assert not sup._crash_outbox
    finally:
        sup.stop()


# -- orphan reaping ---------------------------------------------------------
def test_reap_orphans_kills_recorded_groups(tmp_path):
    """A dead supervisor's recorded children are killed by GROUP; a
    live supervisor's are left alone; the state file is consumed."""
    victim = subprocess.Popen(
        [sys.executable, "-c", "import time; time.sleep(120)"],
        start_new_session=True,
    )
    try:
        # live supervisor (our own pid): nothing reaped
        (tmp_path / "supervisor.json").write_text(
            json.dumps(
                {"pid": os.getpid(), "children": {"x.0": victim.pid}}
            )
        )
        assert Supervisor.reap_orphans(tmp_path) == []
        assert victim.poll() is None
        # dead supervisor: the child group dies
        dead = subprocess.Popen([sys.executable, "-c", "pass"])
        dead.wait()
        (tmp_path / "supervisor.json").write_text(
            json.dumps(
                {"pid": dead.pid, "children": {"x.0": victim.pid}}
            )
        )
        reaped = Supervisor.reap_orphans(tmp_path)
        assert reaped == [victim.pid]
        assert victim.wait(timeout=10) == -signal.SIGKILL
        assert not (tmp_path / "supervisor.json").exists()
        # idempotent on a missing file
        assert Supervisor.reap_orphans(tmp_path) == []
    finally:
        if victim.poll() is None:
            victim.kill()
            victim.wait()


# -- ProcessDeath report shape ----------------------------------------------
def test_build_process_report_shape():
    """Signal deaths name the signal, exits name the status; the log
    tail rides as the backtrace; schema matches build_report."""
    r = build_process_report(
        "osd.3", -signal.SIGKILL, log_tail=["a", "b"],
        extra_meta={"pid": 7},
    )
    assert r["exception"] == "ProcessDeath: killed by SIGKILL"
    assert r["entity_name"] == "osd.3"
    assert r["backtrace"] == ["a", "b"]
    assert r["meta"]["process_death"] is True
    assert r["meta"]["returncode"] == -signal.SIGKILL
    assert r["meta"]["pid"] == 7
    assert "_" in r["crash_id"] and r["timestamp_iso"]
    r = build_process_report("mgr.0", 3)
    assert r["exception"] == "ProcessDeath: exited with status 3"
    assert r["backtrace"] == []
    # unknown negative status degrades to a numbered signal
    r = build_process_report("x.0", -250)
    assert "signal 250" in r["exception"]


# -- the tier-1 acceptance cluster ------------------------------------------
def test_three_process_cluster_boot_write_read(tmp_path):
    """A REAL multi-process cluster — one mon + two OSDs, each its
    own OS process — boots, peers, serves a replicated write, and
    reads it back byte-identical through a fresh client."""
    spec = ClusterSpec.plan(
        tmp_path, mons=1, osds=2, mgrs=0, memstore=True
    )
    sup = Supervisor(spec, report_interval=3600.0)
    client = None
    try:
        sup.start(ready_timeout=90)
        st = sup.status()
        assert set(st) == {"mon.0", "osd.0", "osd.1"}
        assert all(c["state"] == "running" for c in st.values())
        pids = {c["pid"] for c in st.values()}
        assert len(pids) == 3 and os.getpid() not in pids

        client = Rados("proc-t1").connect_any(spec.mon_addrs)
        client.pool_create("t1pool", pg_num=4, size=2)
        io = client.open_ioctx("t1pool")
        payload = bytes(range(256)) * 256  # 64 KiB, every byte value
        io.write_full("t1obj", payload)
        assert io.read("t1obj") == payload

        # a second client session sees the same bytes (the read is
        # served by the daemon processes, not client-side state)
        client.shutdown()
        client = Rados("proc-t1b").connect_any(spec.mon_addrs)
        io = client.open_ioctx("t1pool")
        assert io.read("t1obj") == payload
    finally:
        if client is not None:
            client.shutdown()
        sup.stop()
    # teardown left nothing behind
    assert not (tmp_path / "supervisor.json").exists()
