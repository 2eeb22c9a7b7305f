"""Fixtures shared by the jax test files."""
import pytest


@pytest.fixture
def device_memory(monkeypatch):
    """``device_memory(limit)`` makes every local device report
    ``bytes_limit = limit`` from ``memory_stats()``, as a chip does (the
    CPU reports none), so ``build_trainer`` budgets what remat keeps."""
    import jax

    class Reporting:
        def __init__(self, device, limit):
            self._device, self._limit = device, limit

        def __getattr__(self, name):
            return getattr(self._device, name)

        def memory_stats(self):
            return {"bytes_limit": self._limit}

    local = jax.local_devices

    def report(limit: int) -> None:
        monkeypatch.setattr(jax, "local_devices", lambda *a, **k: [
            Reporting(d, limit) for d in local(*a, **k)])
    return report
