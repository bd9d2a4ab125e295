"""utils/runtime.py: where the compile cache goes, and the launcher-parent
JSON-line convention."""

import jax
import pytest

from fl4health_tpu.utils import runtime


@pytest.fixture
def restore_cache_config():
    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_persistent_cache_min_compile_time_secs)
    yield saved
    jax.config.update("jax_compilation_cache_dir", saved[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", saved[1])


class TestConfigureCompileCache:
    def test_sets_nothing_when_the_variable_is_set(
        self, monkeypatch, tmp_path, restore_cache_config
    ):
        monkeypatch.setenv(runtime.CACHE_ENV, "/placed/from/outside")
        got = runtime.configure_compile_cache(
            tmp_path / "ignored", min_compile_time_secs=123.0)
        assert got == "/placed/from/outside"
        assert (jax.config.jax_compilation_cache_dir,
                jax.config.jax_persistent_cache_min_compile_time_secs
                ) == restore_cache_config

    def test_fixed_in_checkout_default_when_unset(
        self, monkeypatch, restore_cache_config
    ):
        monkeypatch.delenv(runtime.CACHE_ENV, raising=False)
        expected = str(runtime.REPO_ROOT / ".jax_cache")
        assert runtime.configure_compile_cache() == expected
        assert runtime.configure_compile_cache() == expected  # never moves
        assert jax.config.jax_compilation_cache_dir == expected
        assert (runtime.REPO_ROOT / "chip_smoke.py").exists()

    def test_caller_default_and_threshold(
        self, monkeypatch, tmp_path, restore_cache_config
    ):
        monkeypatch.delenv(runtime.CACHE_ENV, raising=False)
        got = runtime.configure_compile_cache(tmp_path, 0.0)
        assert got == str(tmp_path) == jax.config.jax_compilation_cache_dir
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


class TestLastJsonLine:
    def test_picks_last_valid_json(self):
        text = '{"a": 1}\nnoise\n{"b": 2}'
        assert runtime.last_json_line(text) == {"b": 2}

    def test_skips_trailing_invalid_json(self):
        assert runtime.last_json_line('{"a": 1}\n{broken') == {"a": 1}

    def test_none_when_no_json(self):
        assert runtime.last_json_line("no json here\nstill none") is None


def test_live_device_summary_names_the_backend():
    summary = runtime.live_device_summary()
    assert summary["platform"] == "cpu" and summary["accelerator"] is False
    assert summary["device_count"] == len(jax.devices())
    assert summary["peak_bf16_flops"] is None  # unknown chip: never a guess
