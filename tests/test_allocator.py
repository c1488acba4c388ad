"""common/allocator.py: the one allocator policy call."""

from __future__ import annotations

import ctypes

from ceph_tpu.common import allocator


def test_keep_large_blocks_is_taken_by_glibc_and_repeats():
    assert allocator.keep_large_blocks() is True
    assert allocator.keep_large_blocks() is True
    assert allocator.KEEP_BLOCKS_BELOW <= 2**31 - 1 >= allocator.KEEP_HEAP_TOP  # C ints


def test_without_mallopt_the_process_runs_as_it_did(monkeypatch):
    class NoMallopt:
        def __getattr__(self, name):
            raise AttributeError(name)

    monkeypatch.setattr(ctypes, "CDLL", lambda _name: NoMallopt())
    assert allocator.keep_large_blocks() is False


def test_the_device_backend_is_the_policys_one_owner(monkeypatch):
    """A codec that asks for the device backend puts its process under
    the policy; the host oracle leaves the process as it was; a C
    library that refuses is said, not hidden."""
    import pytest

    from ceph_tpu.ec import backend

    calls = []
    monkeypatch.setattr(
        backend.allocator, "keep_large_blocks", lambda: calls.append(1) or True)
    backend.get_backend("numpy")
    assert calls == []
    assert backend.get_backend("jax").name == "jax"
    assert calls == [1]
    monkeypatch.setattr(backend.allocator, "keep_large_blocks", lambda: False)
    with pytest.warns(RuntimeWarning, match="allocator policy"):
        backend.get_backend("jax")
