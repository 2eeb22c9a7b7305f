"""The training CLI (``python -m repro.launch.train``) end to end on the
CPU, and the persistent compilation cache every entry point enables."""
import functools
import math
import os
import re

import pytest

jax = pytest.importorskip("jax")
from jax.experimental.compilation_cache import compilation_cache  # noqa: E402

from repro import runtime  # noqa: E402
from repro.checkpoint import all_steps  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.launch.train import main  # noqa: E402

pytestmark = [pytest.mark.jax]


@pytest.fixture
def restore_cache_config():
    """Entry points set the process-wide cache directory: put it back."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("sync_mode", ["bucketed", "barrier"])
def test_train_cli_runs_the_steps_it_was_asked_for(
        tmp_path, monkeypatch, capsys, restore_cache_config, sync_mode):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    # a monitor that calls every step after the first a straggler, so
    # the done line's report count is known
    monkeypatch.setattr(runtime, "StepMonitor",
                        functools.partial(runtime.StepMonitor, threshold=0.0))
    ckpt = tmp_path / "ckpt"
    summary = main(["--arch", "mamba2-130m", "--smoke", "--steps", "2",
                    "--sync-mode", sync_mode, "--ckpt-dir", str(ckpt)])
    assert summary["restarts"] == 0 and summary["final_step"] == 1
    assert len(summary["loss_history"]) == 2
    assert len(summary["step_times"]) == 2
    assert all(math.isfinite(x) for x in summary["loss_history"])
    assert all_steps(str(ckpt)) == [1]
    done = capsys.readouterr().out.splitlines()[-1]
    assert re.fullmatch(r"done: 2 steps in \d+\.\ds, restarts=0, "
                        r"stragglers=1 \(step-time 1\), "
                        r"loss \d+\.\d{3} -> \d+\.\d{3}", done), done


def test_compile_cache_uses_the_env_dir_when_set(
        tmp_path, monkeypatch, restore_cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_compile_cache_falls_back_to_the_checkout(
        monkeypatch, restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert path == os.path.join(root, ".jax_cache")


def test_done_line_counts_the_attention_paths(
        tmp_path, monkeypatch, capsys, restore_cache_config):
    """The tally of ``sdpa`` dispatches, beside the monitor's counts: on
    the CPU a llama's causal self-attention takes the blockwise path."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    main(["--arch", "deepseek-7b", "--smoke", "--steps", "1", "--seq", "256",
          "--batch", "2", "--ckpt-dir", str(tmp_path / "ckpt")])
    done = capsys.readouterr().out.splitlines()[-1]
    assert re.search(r", attention \(xla_flash \d+\), loss ", done), done


def test_done_line_names_the_tiers_remat_keeps(
        tmp_path, monkeypatch, capsys, restore_cache_config, device_memory):
    """``model.REMAT`` on the ``done:`` line, where the device reports its
    memory: on the CPU's attention path the smoke llama names only its
    MLP products."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    device_memory(10 ** 12)
    main(["--arch", "deepseek-7b", "--smoke", "--steps", "1", "--seq", "256",
          "--batch", "2", "--sync-mode", "barrier",
          "--ckpt-dir", str(tmp_path / "ckpt")])
    done = capsys.readouterr().out.splitlines()[-1]
    assert re.search(r", remat: mlp 0\.\d\d GB, loss ", done), done
