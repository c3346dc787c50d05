"""chip_smoke.py: the tiny CPU rehearsal walks every phase, the CLI
refuses to pass without a TPU, and the compile cache is placeable."""

import json
import os
import subprocess
import sys

import jax
import pytest

import chip_smoke  # repo root: `python -m pytest` runs from there

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_tiny_run_walks_every_phase():
    """run(tiny=True, require_tpu=False): the same phases as the chip
    run — the four-device ones on the virtual mesh — with the kernels
    in interpret mode (requested by run() itself, off-chip)."""
    assert jax.device_count() >= 4
    result = chip_smoke.run(tiny=True, require_tpu=False)
    assert result["ok"] is True
    assert result["device"] == {
        "platform": "cpu", "kind": "cpu", "count": jax.device_count()}
    assert list(result["phases"]) == [
        "train", "oracle", "serve", "serve_int8", "train_4chip",
        "serve_4chip"]
    assert all(p["ok"] for p in result["phases"].values())
    # every served engine resolved decode_impl='auto' to the kernels
    for name in ("serve", "serve_int8", "serve_4chip"):
        assert result["phases"][name]["resolved_impl"] == "pallas"
    assert result["compile_cache"] is None  # no cache from the CPU lane
    # the CLI's last stdout line: exactly `ok` and `device`, no more —
    # the driver's chip check refuses any other shape
    last = json.loads(chip_smoke.result_line(result))
    assert list(last) == ["ok", "device"] and last["ok"] is True
    assert last["device"] == result["device"]
    assert list(last["device"]) == ["platform", "kind", "count"]
    # the interpret request ended with the run
    from deepspeed_tpu.ops.pallas import interpret

    assert interpret() is False


def test_run_requires_tpu_by_default():
    with pytest.raises(RuntimeError, match="needs a TPU"):
        chip_smoke.run(tiny=True)


@pytest.mark.parametrize("argv", [[], ["--tiny"], ["--cpu"]])
def test_cli_fails_without_a_tpu(argv):
    """No option of the command-line entry lets it pass off-chip, and
    it prints no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(_REPO, "chip_smoke.py"), *argv],
        capture_output=True, text=True, timeout=300, env=env, cwd=_REPO)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


class TestCompileCache:
    """enable_compile_cache against a recording stand-in for
    jax.config.update: the CPU lane never really turns a cache on."""

    @pytest.fixture
    def updates(self, monkeypatch):
        calls = []
        monkeypatch.setattr(jax.config, "update",
                            lambda key, value: calls.append((key, value)))
        return calls

    def test_env_var_wins_and_nothing_is_set_in_code(self, monkeypatch,
                                                     updates):
        from deepspeed_tpu.platform.compile_cache import enable_compile_cache

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        assert enable_compile_cache() == "/some/dir"
        assert updates == []

    def test_default_is_the_fixed_in_checkout_path(self, monkeypatch,
                                                   updates):
        from deepspeed_tpu.platform.compile_cache import enable_compile_cache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        path = enable_compile_cache()
        assert path == os.path.join(_REPO, ".jax_cache")
        assert updates == [("jax_compilation_cache_dir", path)]
