"""core/env.py: typed env helpers + the one registry."""

from __future__ import annotations

import warnings

import pytest

from mmlspark_tpu.core import env as env_mod
from mmlspark_tpu.core.env import (REGISTRY, env_flag, env_float,
                                   env_int, env_override, env_raw,
                                   env_str)

VAR = "MMLSPARK_TPU_TEST_ONLY_KNOB"


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    monkeypatch.delenv(VAR, raising=False)
    env_mod.reset_warnings()
    yield
    env_mod.reset_warnings()


def test_env_flag_truthy_falsey(monkeypatch):
    assert env_flag(VAR) is False
    assert env_flag(VAR, default=True) is True
    for v in ("1", "true", "YES", " On "):
        monkeypatch.setenv(VAR, v)
        assert env_flag(VAR) is True
        assert env_flag(VAR, default=True) is True
    for v in ("0", "false", "OFF", " no "):
        monkeypatch.setenv(VAR, v)
        assert env_flag(VAR) is False
        assert env_flag(VAR, default=True) is False


def test_env_flag_garbage_warns_once_and_defaults(monkeypatch):
    monkeypatch.setenv(VAR, "maybe")
    with pytest.warns(UserWarning, match=VAR):
        assert env_flag(VAR, default=True) is True
    # second read: warned already, silent
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert env_flag(VAR) is False


def test_env_int(monkeypatch):
    assert env_int(VAR, 7) == 7
    monkeypatch.setenv(VAR, " 42 ")
    assert env_int(VAR, 7) == 42
    monkeypatch.setenv(VAR, "zero?")
    with pytest.warns(UserWarning, match="not an integer"):
        assert env_int(VAR, 7) == 7
    env_mod.reset_warnings()
    monkeypatch.setenv(VAR, "-3")
    with pytest.warns(UserWarning, match="below the minimum"):
        assert env_int(VAR, 7, minimum=1) == 7


def test_env_float(monkeypatch):
    assert env_float(VAR, 0.2) == 0.2
    monkeypatch.setenv(VAR, " 0.35 ")
    assert env_float(VAR, 0.2) == 0.35
    monkeypatch.setenv(VAR, "lots")
    with pytest.warns(UserWarning, match="not a number"):
        assert env_float(VAR, 0.2) == 0.2
    env_mod.reset_warnings()
    monkeypatch.setenv(VAR, "-0.5")
    with pytest.warns(UserWarning, match="below the minimum"):
        assert env_float(VAR, 0.2, minimum=0.0) == 0.2


def test_env_str_and_raw(monkeypatch):
    assert env_str(VAR) is None
    assert env_str(VAR, "d") == "d"
    assert env_raw(VAR) is None
    monkeypatch.setenv(VAR, "  value ")
    assert env_str(VAR) == "  value "        # unstripped by contract
    assert env_raw(VAR) == "  value "


def test_env_override_restores(monkeypatch):
    import os
    monkeypatch.setenv(VAR, "orig")
    with env_override(VAR, "0"):
        assert os.environ[VAR] == "0"
        with env_override(VAR, None):
            assert VAR not in os.environ
        assert os.environ[VAR] == "0"
    assert os.environ[VAR] == "orig"
    monkeypatch.delenv(VAR)
    with env_override(VAR, "x"):
        assert os.environ[VAR] == "x"
    assert VAR not in os.environ


def test_env_override_restores_on_exception():
    import os
    with pytest.raises(RuntimeError):
        with env_override(VAR, "armed"):
            assert os.environ[VAR] == "armed"
            raise RuntimeError("boom")
    assert VAR not in os.environ


def test_registry_shape():
    assert len(REGISTRY) >= 14
    for name, var in REGISTRY.items():
        assert name.startswith("MMLSPARK_TPU_")
        assert var.name == name
        assert var.kind in ("flag", "int", "float", "str")
        assert var.description
    # knobs PR 3's audit found undocumented must stay declared (the
    # fifth, MMLSPARK_TPU_COMPILE_CACHE, went in PR 22: JAX's own
    # JAX_COMPILATION_CACHE_DIR places the cache)
    for name in ("MMLSPARK_TPU_FABRIC_ENDPOINT",
                 "MMLSPARK_TPU_FABRIC_TOKEN",
                 "MMLSPARK_TPU_FLASH",
                 "MMLSPARK_TPU_PALLAS_FORCE_COMPILE"):
        assert name in REGISTRY


def test_utils_env_flag_alias(monkeypatch):
    from mmlspark_tpu.core.utils import env_flag as legacy
    monkeypatch.setenv(VAR, "1")
    assert legacy(VAR) is True
    monkeypatch.setenv(VAR, "0")
    assert legacy(VAR) is False
