"""chip_smoke.py contract, as far as a CPU can check it: no TPU means a
non-zero exit before any model code, the explicit rehearsal drives every
phase at tiny widths, and a phase that fails fails the run.  The
compile-cache helper's placement rule is pinned next to it."""
import json
import os
import subprocess
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _run(*args, **env):
    # four virtual devices so the rehearsal reaches the four-chip phase
    full_env = {**os.environ, "JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
                **env}
    return subprocess.run([sys.executable, SMOKE, *args], cwd=ROOT,
                          env=full_env, capture_output=True, text=True,
                          timeout=300)


def test_refuses_to_start_without_a_tpu():
    res = _run()
    assert res.returncode == 2
    assert res.stdout == ""          # no result line, nothing to mistake
    assert "no TPU" in res.stderr and "'cpu'" in res.stderr
    # it stopped before building anything: no phase ever reported
    assert "phase" not in res.stderr


def test_rehearsal_runs_every_phase_and_says_it_is_not_a_chip_run():
    res = _run("--rehearse-on-cpu")
    assert res.returncode == 0, res.stderr[-2000:]
    recs = [json.loads(ln) for ln in res.stdout.splitlines()
            if ln.startswith("{")]
    by_phase = {r["phase"]: r for r in recs if "phase" in r}
    assert set(by_phase) == {"start", "train", "serve", "four_chips"}
    assert all("NOT a chip run" in r["rehearsal"] for r in recs)
    assert by_phase["train"]["capture"] == {"compiles": 1, "hits": 5,
                                            "fallback": None}
    assert by_phase["serve"]["unexpected_compiles"] == 0
    assert by_phase["serve"]["aot_compiles_watched"] >= \
        by_phase["serve"]["programs"]
    assert by_phase["four_chips"]["mesh"] == {"dp": 2, "mp": 2}
    # a CPU run carries no device metric under any name
    for r in recs:
        assert not {"warm_step_ms", "tokens_per_s", "mfu_model",
                    "first_call_s", "ms_per_generated_token"} & set(r)
    final = recs[-1]
    assert final["ok"] is True and final["device"]["platform"] == "cpu"


def test_a_failing_phase_fails_the_run():
    # PT_CAPTURE=0 switches capture off: the loop still trains, eagerly,
    # but "one compile, every other step a replay" no longer holds — the
    # train phase must fail and nothing after it may run or report ok
    res = _run("--rehearse-on-cpu", PT_CAPTURE="0")
    assert res.returncode != 0
    assert "capture did not hold" in res.stderr
    assert '"phase": "serve"' not in res.stdout
    assert '"ok": true' not in res.stdout


class TestCompileCachePlacement:

    @pytest.fixture(autouse=True)
    def _restore(self):
        prev = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", prev)

    def test_env_set_leaves_config_untouched(self, monkeypatch):
        from paddle_tpu.device import place_compile_cache
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/where")
        jax.config.update("jax_compilation_cache_dir", None)
        assert place_compile_cache() == "/some/where"
        assert jax.config.jax_compilation_cache_dir is None

    def test_env_unset_places_it_in_the_checkout(self, monkeypatch):
        from paddle_tpu.device import place_compile_cache
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(ROOT, ".jax_cache")
        assert place_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
