"""chip_smoke.py rehearsed without the chip, and the compile cache it
relies on.

The smoke's verdict needs a TPU, so here it must FAIL — after doing
everything else it does on the chip: start the serve child, read its
census, answer four requests through ``POST /distributed/queue``, check the
images, read the metrics, stop the child. The rehearsal runs on the
``tiny`` preset with ``JAX_PLATFORMS=cpu`` (guide ``on-chip-measurement``
§2.1) in a process that, like the script's own, never imports JAX — this
one has (conftest), which is the second case.
"""

import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parent.parent

_spec = importlib.util.spec_from_file_location("chip_smoke",
                                               ROOT / "chip_smoke.py")
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)

REHEARSAL = """
import json, sys
sys.path.insert(0, {root!r})
import chip_smoke
from pathlib import Path

wf = chip_smoke.load_workflow()          # the shipped graph ...
wf["1"]["inputs"]["ckpt_name"] = "tiny"  # ... on the tiny preset
wf["5"]["inputs"].update(width=32, height=32, steps=4)
line, code = chip_smoke.smoke_one_chip(wf, cpu_rehearsal=True,
                                       out_dir=Path({out!r}))
assert "jax" not in sys.modules, "the smoke's own process imported JAX"
print(line)
sys.exit(code)
"""


def test_one_chip_rehearsal_on_cpu(tmp_path):
    from conftest import TEST_XLA_CACHE

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=os.environ.get(
                   "JAX_COMPILATION_CACHE_DIR", TEST_XLA_CACHE))
    env.pop("XLA_FLAGS", None)          # one device, as on the one-chip box
    proc = subprocess.run(
        [sys.executable, "-c",
         REHEARSAL.format(root=str(ROOT), out=str(tmp_path / "smoke"))],
        env=env, capture_output=True, text=True,
        timeout=600)                    # its own limit: the suite has none
    out = proc.stdout.strip().splitlines()
    assert proc.returncode == 1, proc.stdout[-3000:] + proc.stderr[-3000:]
    # the last line has the contract's shape, and says what it ran on
    assert json.loads(out[-1]) == {
        "ok": False, "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    # ... and it is only the platform that failed it: every phase ran
    assert "FAILED" not in proc.stderr, proc.stderr[-3000:]
    assert sum("seed" in l and "s wall" in l for l in out) == 4, out
    assert any("weights: model tiny held in float32" in l for l in out), out
    assert any("persistent cache:" in l for l in out), out
    assert any("stopped (code 0)" in l for l in out), out     # by pid
    images = sorted((tmp_path / "smoke" / "output").glob("*.png"))
    assert len(images) == 4


def test_a_parent_that_imported_jax_is_rejected(tmp_path):
    """One process per chip: this process has imported JAX (conftest), so
    it may hold the chip, and a serve child started from it would fail or
    hang. The smoke refuses before it starts one."""
    assert "jax" in sys.modules
    with pytest.raises(chip_smoke.SmokeFailure, match="imported JAX"):
        with chip_smoke.serve(tmp_path / "smoke"):
            pytest.fail("a child was started")
    assert not (tmp_path / "smoke").exists()


@pytest.fixture
def cache_config():
    """enable_compile_cache sets process-global jax config; put back what
    conftest chose, and hand the test a record of every config update."""
    from comfyui_distributed_tpu.utils import compile_cache as cc

    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_persistent_cache_min_compile_time_secs,
             cc._active)
    updates = []
    real_update = jax.config.update

    def spy(name, value):
        updates.append((name, value))
        real_update(name, value)

    jax.config.update = spy
    try:
        yield updates
    finally:
        jax.config.update = real_update
        jax.config.update("jax_compilation_cache_dir", saved[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          saved[1])
        cc._active = saved[2]


def test_four_chip_rehearsal_on_virtual_devices(cache_config, monkeypatch):
    """``--chips 4`` on four of conftest's virtual CPU devices, tiny
    geometry: both mesh legs run and agree with their single-device runs;
    the verdict fails on the platform alone and counts the devices JAX
    reports."""
    from conftest import TEST_XLA_CACHE

    # the suite's own cache: with the variable set nothing is set in code
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", TEST_XLA_CACHE)
    line, code = chip_smoke.smoke_four_chips(
        sdxl_preset="tiny", image_hw=32, wan_tiny=True)
    assert code == 1
    assert json.loads(line) == {
        "ok": False, "device": {"platform": "cpu", "kind": "cpu",
                                "count": len(jax.devices())}}


class TestCompileCachePlacement:
    """One directory, placed from outside (utils/compile_cache.py)."""

    def test_env_var_set_means_no_directory_is_set_in_code(
            self, cache_config, monkeypatch, tmp_path):
        from comfyui_distributed_tpu.utils import compile_cache as cc

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "x"))
        # an explicit path does not beat the variable either
        d = cc.enable_compile_cache(str(tmp_path / "ignored"))
        assert d == str(tmp_path / "x") == cc.active_cache_dir()
        assert (tmp_path / "x").is_dir()
        assert not (tmp_path / "ignored").exists()
        assert "jax_compilation_cache_dir" not in dict(cache_config)
        assert cc.cache_dir_default() == str(tmp_path / "x")

    def test_unset_means_the_checkout(self, cache_config, monkeypatch):
        from comfyui_distributed_tpu.utils import compile_cache as cc

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        d = cc.enable_compile_cache()
        assert d == str(ROOT / ".cache" / "xla") == cc.active_cache_dir()
        assert dict(cache_config)["jax_compilation_cache_dir"] == d
        assert jax.config.jax_compilation_cache_dir == d
        # nothing of the machine, the user or the moment is in the path
        assert cc.cache_dir_default() == d
        # the git-ignored place: what is cached is never committed
        assert ".cache/" in (ROOT / ".gitignore").read_text().split()

    def test_the_names_a_program_was_traced_with_are_part_of_its_key(
            self, cache_config, monkeypatch, tmp_path):
        """A cached executable keeps the op names it was compiled with:
        with JAX's default key a program whose operations did not change
        would show another checkout's (or no) ``cdt.<layer>`` scopes in a
        profile (seen on the chip, PR 34). The names, not the callers'
        frames: the warm-up pass and a request reach one program by
        different paths and must find one entry
        (``test_warmup.test_warm_restart_skips_recompilation``)."""
        from comfyui_distributed_tpu.utils import compile_cache as cc

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "x"))
        cc.enable_compile_cache()
        assert dict(cache_config)[
            "jax_compilation_cache_include_metadata_in_key"] is True
        assert jax.config.jax_compilation_cache_include_metadata_in_key
        assert dict(cache_config)["jax_traceback_in_locations_limit"] == 1
        assert jax.config.jax_traceback_in_locations_limit == 1

    def test_unwritable_is_an_error_not_silence(self, cache_config,
                                                monkeypatch, tmp_path):
        from comfyui_distributed_tpu.utils import compile_cache as cc

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        blocker = tmp_path / "a_file"
        blocker.write_text("")
        before = cc.active_cache_dir()
        with pytest.raises(OSError):
            cc.enable_compile_cache(str(blocker / "cache"))
        assert cc.active_cache_dir() == before
        assert "jax_compilation_cache_dir" not in dict(cache_config)
