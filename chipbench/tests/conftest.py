import pytest


@pytest.fixture(autouse=True)
def _no_persistent_compile_cache(monkeypatch):
    """Runs here keep JAX's configuration as they found it: the harness's
    persistent compilation cache would otherwise stay switched on for every
    later test of the same process."""
    from chipbench import harness

    monkeypatch.setattr(harness, "enable_compile_cache", lambda: "off")
