"""The compile-cache helper: the variable's directory when it is set, the
checkout's fixed ``.jax_cache`` otherwise, and never a directory of its
own choosing over the variable's."""
from pathlib import Path

import jax

from repro.launch import compile_cache as cc

REPO = Path(__file__).resolve().parents[1]


def _record_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_cache_dir_from_variable(monkeypatch, tmp_path):
    monkeypatch.setenv(cc.ENV, str(tmp_path))
    calls = _record_updates(monkeypatch)
    assert cc.compile_cache_dir() == str(tmp_path)
    assert cc.enable_compile_cache() == str(tmp_path)
    assert calls == []           # JAX reads the variable; nothing overrides


def test_cache_dir_fixed_in_checkout_when_unset(monkeypatch):
    monkeypatch.delenv(cc.ENV, raising=False)
    calls = _record_updates(monkeypatch)
    want = str(REPO / ".jax_cache")
    assert cc.compile_cache_dir() == want
    assert cc.enable_compile_cache() == want
    assert calls == [("jax_compilation_cache_dir", want)]
    # same path on every call: no temp name, pid or time in it
    assert cc.compile_cache_dir() == want
