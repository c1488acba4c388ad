"""Test configuration: force JAX onto a virtual 8-device CPU mesh.

Multi-chip hardware is not available in CI; sharding correctness is
validated on a virtual CPU mesh exactly as the driver's dryrun does.
Must run before the first ``import jax`` anywhere in the test process.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Product paths shard across the default mesh whenever >1 device
# exists (ops/mesh.py).  On this VIRTUAL 8-device mesh that would
# recompile a sharded program for every unique shape the suite
# touches, ballooning wall-clock far past the tier-1 budget for zero
# coverage gain — the sharded kernels are byte-identical by
# construction and proven so by tests/test_mesh.py, which opts back
# in explicitly (monkeypatch).  setdefault: an external
# CEPH_TPU_MESH=1 still forces product sharding suite-wide.
os.environ.setdefault("CEPH_TPU_MESH", "0")

# The suite runs on the CPU backend's virtual devices unless the caller
# set JAX_PLATFORMS (the driver sets it to ``cpu`` too).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# The whole suite runs with lockdep ON (the reference wires lockdep
# into every ceph::mutex in debug builds, src/common/lockdep.cc): the
# daemons' named Mutexes register order edges and an ABBA inversion
# anywhere fails the run.  CEPH_TPU_LOCKDEP=0 opts out.
if os.environ.get("CEPH_TPU_LOCKDEP", "1") != "0":
    from ceph_tpu.common import lockdep as _lockdep

    _lockdep.enable()


# The crash plane keeps a process-global pending queue for daemons
# without an mgr session (ceph_tpu/common/crash.py).  Tests share one
# process, so a crash captured by one test must not surface as
# RECENT_CRASH in another test's manager: drain the queue between
# tests.
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _isolate_global_crash_queue():
    yield
    from ceph_tpu.common import crash as _crash

    _crash.drain_pending()
    # signature-throttle history would suppress a later test's
    # intentionally-identical crash injection
    _crash.reset_throttle()


# The multi-process runtime (ceph_tpu/proc) spawns one OS process per
# daemon.  A test that fails mid-scenario can strand children that
# squat ports and CPU for the rest of the run: reap any daemon
# process that is still OUR descendant after each test.  (Scoped to
# the daemon entrypoint cmdline — never touches unrelated processes.)
def _leaked_daemon_pids() -> list[int]:
    import pathlib

    me = os.getpid()
    out = []
    for p in pathlib.Path("/proc").iterdir():
        if not p.name.isdigit():
            continue
        try:
            cmdline = (p / "cmdline").read_bytes()
            if b"ceph_tpu.proc.daemon" not in cmdline:
                continue
            stat = (p / "stat").read_text().rsplit(")", 1)[1].split()
            ppid = int(stat[1])
        except (OSError, IndexError, ValueError):
            continue
        # direct children only: setsid daemons reparent to init when
        # their supervisor dies, but their recorded parent at spawn
        # is the test process — either way the cmdline match plus
        # (ppid == us or orphaned) marks them leaked
        if ppid == me or ppid == 1:
            out.append(int(p.name))
    return out


@pytest.fixture(autouse=True)
def _reap_leaked_daemon_processes():
    yield
    import signal as _signal

    for pid in _leaked_daemon_pids():
        try:
            os.killpg(pid, _signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            try:
                os.kill(pid, _signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass


# The fault plane (msg/faults.py) lives on every messenger, and chaos
# tests legitimately leave rules/partitions behind when they fail
# mid-scenario.  Messengers can outlive their test (module-scoped
# fixtures, leaked references), so — same shape as the daemon reaper
# above — sweep every surviving injector clean between tests: one
# test's netsplit must not shadow-fail the next test's I/O.
@pytest.fixture(autouse=True)
def _clear_leaked_fault_rules():
    yield
    from ceph_tpu.msg.messenger import Messenger as _Messenger

    for m in list(_Messenger._live):
        try:
            f = m.faults
            if f.active:
                f.clear()
            f.socket_failure_every = 0
        except Exception:  # noqa: BLE001 — mid-shutdown messengers
            pass


# Round-5 loosened several wall-clock assertions because loaded CI
# boxes missed them; the strict bounds still catch real regressions
# whenever the box is actually idle.  Tests pick their bound at
# runtime: strict when the 1-minute loadavg per core is low, the
# load-tolerant fallback otherwise.
def _loadavg_trustworthy() -> bool:
    """Sandboxed kernels (gVisor-class: this CI box) hardwire
    /proc/loadavg to ``0.00 0.00 0.00 0/0 0`` — a zero TOTAL thread
    count, impossible on real Linux, while the box may be fully
    loaded.  Only trust loadavg when the kernel is actually
    accounting threads; elsewhere (no /proc) os.getloadavg() is the
    platform API and is trusted."""
    try:
        with open("/proc/loadavg") as f:
            fields = f.read().split()
        return int(fields[3].partition("/")[2]) > 0
    except (OSError, ValueError, IndexError):
        return True  # no /proc: nothing contradicts getloadavg


def strict_timing() -> bool:
    """True when this box is PROVABLY idle enough for strict timing
    bounds; unmeasurable load keeps the load-tolerant bound."""
    if not _loadavg_trustworthy():
        return False
    try:
        load = os.getloadavg()[0]
    except OSError:  # platform without getloadavg
        return False
    return load / (os.cpu_count() or 1) < 0.5
