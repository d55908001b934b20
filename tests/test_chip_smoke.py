"""CPU rehearsal of ``chip_smoke.py``'s phases.

The script itself runs only on a TPU. This drives its engine phases at
``.reduced()`` size with interpret-mode kernels, so a change that breaks
their control flow or their counting checks fails here, before a chip run.
It says nothing about the chip. The compile-cache placement that the
entry points call at start is checked here too.
"""
import dataclasses
import importlib.util
from pathlib import Path

import jax
import pytest

from repro.configs import get_config
from repro.launch import compile_cache
from repro.models.api import get_model

_PATH = Path(__file__).resolve().parents[1] / "chip_smoke.py"
_spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)

# the smoke's engine, shrunk to a CPU-sized slot cache and store
ENGINE = dataclasses.replace(
    chip_smoke.ENGINE, max_batch=4, max_len=128, n_pages=64, prefill_chunk=16
)
LENGTHS = dict(n_requests=6, prompt_mean=32, decode_mean=8)


@pytest.fixture(scope="module")
def model():
    cfg = get_config(chip_smoke.ARCH).reduced()
    api = get_model(cfg)
    return cfg, api, api.init(jax.random.PRNGKey(0))


def test_serve_phase_checks_pass(model):
    cfg, api, params = model
    r = chip_smoke.serve_phase(cfg, ENGINE, seed=0, api=api, params=params, **LENGTHS)
    assert r["finished"] == list(range(LENGTHS["n_requests"]))
    assert r["tokens_decoded"] == r["budget"] > 0
    assert r["near_hits"] + r["far_hits"] == r["kernel_ids"] > 0
    assert r["dispatches_per_step"] == 1.0


def test_verify_phase_reads_both_tiers_exactly(model):
    cfg, api, params = model
    v = chip_smoke.verify_phase(
        cfg, ENGINE, seed=0, n_requests=2, prompt_mean=32, decode_mean=4,
        api=api, params=params,
    )
    assert v["tiered_max_err"] == 0.0
    assert v["near_hits"] > 0 and v["far_hits"] > 0


def test_serve_phase_fails_on_a_miscount(model, monkeypatch):
    """A budget the engine does not meet is a hard failure, not a print."""
    cfg, api, params = model
    monkeypatch.setattr(chip_smoke, "granted_budget", lambda *a: -1)
    with pytest.raises(chip_smoke.SmokeFailure, match="granted budgets"):
        chip_smoke.serve_phase(cfg, ENGINE, seed=0, api=api, params=params, **LENGTHS)


def test_cpu_backend_is_refused(capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert "'cpu'" in str(exc.value.code)
    assert capsys.readouterr().out == ""  # no result line


@pytest.fixture
def config_updates(monkeypatch):
    """Record jax.config updates instead of applying them: tests never
    turn the persistent compile cache on."""
    updates = []
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    return updates


def test_compile_cache_defaults_to_the_checkout(monkeypatch, config_updates):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(_PATH.parent / ".jax_cache")
    assert config_updates == [("jax_compilation_cache_dir", path)]


def test_compile_cache_env_var_wins(monkeypatch, config_updates, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert config_updates == []  # JAX reads the variable itself
