"""core/preflight.py: what every entry point does before the backend
starts — compile-cache placement, the CPU-only-when-asked rule, and the
device record (ISSUE 22)."""
import os
import subprocess
import sys

import jax
import pytest

from byol_tpu.core import preflight

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def config_updates(monkeypatch):
    """Record jax.config.update calls instead of applying them."""
    calls = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.__setitem__(k, v))
    return calls


class TestCompileCachePlacement:
    def test_env_set_means_no_directory_set_in_code(self, monkeypatch,
                                                    config_updates):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x/cache")
        assert preflight.place_compile_cache() == "/x/cache"
        assert "jax_compilation_cache_dir" not in config_updates
        # the thresholds stay: small programs are cached too
        assert config_updates[
            "jax_persistent_cache_min_compile_time_secs"] == 1.0
        assert config_updates[
            "jax_persistent_cache_min_entry_size_bytes"] == 0

    def test_unset_means_the_fixed_directory_in_the_checkout(
            self, monkeypatch, config_updates):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(REPO, ".jax_cache")
        assert preflight.place_compile_cache() == want
        assert config_updates["jax_compilation_cache_dir"] == want

    @pytest.mark.parametrize("env_dir", [None, "/x/from/outside"])
    def test_two_processes_agree_on_the_directory(self, tmp_path, env_dir):
        """A second process — another cwd, another pid, later — resolves
        the same directory, and it is the one JAX itself will use."""
        env = {k: v for k, v in os.environ.items()
               if k != "JAX_COMPILATION_CACHE_DIR"}
        env["PYTHONPATH"] = REPO
        if env_dir:
            env["JAX_COMPILATION_CACHE_DIR"] = env_dir
        code = ("import jax; from byol_tpu.core import preflight; "
                "d = preflight.place_compile_cache(); "
                "assert jax.config.jax_compilation_cache_dir == d, "
                "(jax.config.jax_compilation_cache_dir, d); print(d)")
        seen = [subprocess.run([sys.executable, "-c", code], cwd=cwd,
                               env=env, capture_output=True, text=True,
                               check=True).stdout.strip()
                for cwd in (REPO, str(tmp_path))]
        assert seen[0] == seen[1] == (env_dir
                                      or os.path.join(REPO, ".jax_cache"))

    def test_the_helper_is_the_only_place_a_directory_is_set(self):
        hits = subprocess.run(
            ["grep", "-rln", "--include=*.py", "jax_compilation_cache_dir",
             "byol_tpu", "bench.py", "chip_smoke.py", "train.py", "evidence",
             "scripts", "tools"], cwd=REPO, capture_output=True,
            text=True).stdout.split()
        assert hits == ["byol_tpu/core/preflight.py"]


class TestRequireTpu:
    def test_passes_when_the_cpu_was_asked_for(self, monkeypatch):
        # the harness runs under JAX_PLATFORMS=cpu: that IS a request
        assert preflight.cpu_requested()
        monkeypatch.setattr(
            jax, "default_backend",
            lambda: pytest.fail("no need to touch the backend"))
        preflight.require_tpu("t")

    def test_refuses_any_other_backend_when_nothing_was_asked(
            self, monkeypatch):
        monkeypatch.setattr(preflight, "cpu_requested", lambda: False)
        for backend in ("cpu", "gpu"):
            monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
            with pytest.raises(SystemExit, match="not 'tpu'") as exc:
                preflight.require_tpu("prog")
            assert str(exc.value.code).startswith("prog:")

    def test_passes_on_a_tpu(self, monkeypatch):
        monkeypatch.setattr(preflight, "cpu_requested", lambda: False)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        preflight.require_tpu("t")


def test_describe_device_is_what_jax_reports():
    d = preflight.describe_device()
    assert d == {"platform": jax.devices()[0].platform,
                 "kind": jax.devices()[0].device_kind,
                 "count": len(jax.devices())}
    assert d["platform"] == "cpu" and d["count"] == 8   # the test mesh


def test_run_header_device_is_validated():
    from byol_tpu.observability.events import (SCHEMA_VERSION,
                                               validate_event)
    base = {"v": SCHEMA_VERSION, "kind": "run_header", "t": 0.0,
            "config": {}, "jax_version": "0", "backend": "tpu"}
    validate_event(dict(base))                      # optional field
    validate_event(dict(base, device={"platform": "tpu",
                                      "kind": "TPU v5 lite", "count": 1}))
    for bad in ("tpu", {"platform": "tpu"}, {"kind": "x", "count": 1}):
        with pytest.raises(ValueError, match="run_header.device"):
            validate_event(dict(base, device=bad))
