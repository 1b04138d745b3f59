"""``utils.env`` knob-parsing policy and the non-durable atomic-replace
variant the wire sidecars use."""

import os

import pytest

from distributed_oracle_search_tpu.utils.atomicio import (
    atomic_replace_bytes,
)
from distributed_oracle_search_tpu.utils.env import (
    env_cast, env_flag, env_str,
)


@pytest.fixture
def knob(monkeypatch):
    def set_(val):
        if val is None:
            monkeypatch.delenv("DOS_TEST_KNOB", raising=False)
        else:
            monkeypatch.setenv("DOS_TEST_KNOB", val)
    return set_


def test_env_flag_spellings(knob):
    for raw, want in [("1", True), ("true", True), ("YES", True),
                      ("on", True), ("0", False), ("false", False),
                      ("No", False), ("off", False)]:
        knob(raw)
        assert env_flag("DOS_TEST_KNOB", not want) is want, raw


@pytest.mark.parametrize("default", [True, False])
def test_env_flag_absent_and_empty_take_default(knob, default):
    """FLAG=${UNSET_VAR} interpolation yields an EMPTY value: it must
    behave like absence, never silently flip a default-on knob off."""
    for raw in (None, "", "   "):
        knob(raw)
        assert env_flag("DOS_TEST_KNOB", default) is default


def test_env_flag_malformed_degrades_to_default(knob):
    knob("maybe")
    assert env_flag("DOS_TEST_KNOB", True) is True
    assert env_flag("DOS_TEST_KNOB", False) is False


def test_env_cast_and_str(knob):
    knob("17")
    assert env_cast("DOS_TEST_KNOB", 3, int) == 17
    knob("banana")
    assert env_cast("DOS_TEST_KNOB", 3, int) == 3
    knob("x")
    assert env_str("DOS_TEST_KNOB") == "x"
    knob(None)
    assert env_str("DOS_TEST_KNOB") is None
    assert env_str("DOS_TEST_KNOB", "d") == "d"


def test_atomic_writer_streams_and_cleans_up(tmp_path):
    from distributed_oracle_search_tpu.utils.atomicio import atomic_writer
    p = tmp_path / "parts.csv"
    with atomic_writer(str(p)) as f:
        f.write("wid,cost\n")
        f.write("0,42\n")
    assert p.read_text() == "wid,cost\n0,42\n"
    with pytest.raises(RuntimeError):
        with atomic_writer(str(tmp_path / "doomed.csv")) as f:
            f.write("partial")
            raise RuntimeError("mid-write crash")
    assert not (tmp_path / "doomed.csv").exists()
    assert [x for x in os.listdir(tmp_path) if ".tmp." in x] == []


def test_atomic_replace_is_rename_based(tmp_path):
    """Readers of a transient wire sidecar see old bytes or new bytes,
    never a prefix — and no tmp debris survives the replace."""
    p = tmp_path / "query.results"
    p.write_bytes(b"old")
    atomic_replace_bytes(str(p), b"new contents")
    assert p.read_bytes() == b"new contents"
    assert [f for f in os.listdir(tmp_path) if ".tmp." in f] == []


@pytest.mark.parametrize("env_dir", (None, "/somewhere/jax-cache"))
def test_compile_cache_dir(monkeypatch, env_dir):
    """JAX_COMPILATION_CACHE_DIR wins and no other directory is set in
    code; without it the cache goes to the fixed in-checkout path."""
    import jax

    from distributed_oracle_search_tpu.utils import compile_cache

    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.use_compile_cache()
        assert got == compile_cache.cache_dir()
        if env_dir is None:
            root = os.path.dirname(os.path.dirname(os.path.abspath(
                __file__)))
            assert got == os.path.join(root, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
        else:
            assert got == env_dir
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
