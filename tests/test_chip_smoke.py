"""Process start-up rules a chip run rests on: where the compile cache
goes, and that ``chip_smoke.py`` refuses a backend that is not a TPU."""

import json
import os
import pathlib
import tempfile

import jax
import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent


@pytest.mark.parametrize("env_dir", [
    pytest.param("/placed/from/outside", id="variable-set"),
    pytest.param(None, id="variable-unset"),
])
def test_compile_cache_rule(monkeypatch, env_dir):
    from tpu_dist.utils import compile_cache

    before = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    try:
        first = compile_cache.configure()
        in_code = jax.config.jax_compilation_cache_dir
        assert compile_cache.configure() == first  # equal across two calls
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
        if env_dir is not None:
            # Whoever set the variable chose the place: jax reads it at
            # start-up, and the code sets no directory at all.
            assert first == env_dir
            assert in_code is None
        else:
            assert first == in_code == str(REPO / ".jax_cache")
            assert not first.startswith(tempfile.gettempdir())
            assert str(os.getpid()) not in first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_chip_smoke_refuses_a_cpu_backend(capsys):
    import chip_smoke

    rc = chip_smoke.main([])
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc != 0
    assert len(lines) == 1, lines  # it failed at once: no phase ran
    last = json.loads(lines[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"
