"""chip_smoke.py and the no-hidden-CPU contract, checked off the chip.

  - the smoke's body runs its full traffic and every non-platform check
    at 512 rows on the CPU backend (how it is debugged before chip time
    is spent);
  - without an accelerator ``chip_smoke.py``, ``bench.py``'s device modes
    and ``bench_scaling.py`` exit non-zero and print no result, naming
    the platform jax found;
  - the compile cache lands where ``JAX_COMPILATION_CACHE_DIR`` says, or
    in one fixed directory of the checkout.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

import chip_smoke  # repo root is on sys.path (conftest)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, cwd=REPO, **env):
    full_env = {
        k: v for k, v in os.environ.items()
        if k not in ("JAX_COMPILATION_CACHE_DIR", "XLA_FLAGS")
    }
    full_env.update(JAX_PLATFORMS="cpu", **env)
    return subprocess.run(
        [sys.executable, *args], cwd=cwd, env=full_env,
        capture_output=True, text=True, timeout=300,
    )


def test_smoke_body_passes_at_512_rows_on_cpu(tmp_path, monkeypatch):
    # a placed cache: the helper then sets nothing in this test process
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    result = chip_smoke.run_smoke(rows=512, require_chip=False)

    line = json.loads(json.dumps(result))  # the JSON line main() prints
    assert line["platform"] == "cpu"
    assert line["device_count"] == 8  # the conftest's virtual mesh
    assert line["rows"] == 512 and line["seed"] == 0
    assert line["compile_cache_dir"] == str(tmp_path / "cache")
    assert "failures" not in line
    first, second = line["smoke_observations"]
    for obs in (first, second):
        assert len(obs["warm_tick_ms"]) == chip_smoke.WARM_TICKS
        assert obs["reconciles"] == 2
        assert len(obs["event_us"]) + obs["reconciles"] == chip_smoke.EVENTS
        assert obs["cold_rounds"] > 0 and obs["cold_solve_ms"] > 0
        assert min(obs["assigned_frac"]) >= 0.97
    # same trace, same process: the second pass compiles nothing new
    assert second["executables"] == 0 and second["compile_s"] == 0.0
    # flush-before-ack stayed on: one checkpoint per acknowledged tick
    ticks = 1 + chip_smoke.WARM_TICKS + 1 + chip_smoke.EVENTS
    assert first["ckpt_flushes_total"] == ticks
    assert second["ckpt_flushes_total"] == 2 * ticks


def test_smoke_requires_the_chip(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    with pytest.raises(chip_smoke.SmokeFailure, match="platform 'cpu'"):
        chip_smoke.run_smoke(rows=512, require_chip=True)


def test_smoke_script_fails_without_an_accelerator():
    proc = _run(["chip_smoke.py"])
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "jax found platform 'cpu'" in proc.stderr


def test_smoke_script_fails_alone_in_a_directory(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = _run(["chip_smoke.py"], cwd=str(tmp_path))
    assert proc.returncode != 0
    assert proc.stdout == ""


@pytest.mark.parametrize("args", [[], ["engine=jax", "n=512"]])
def test_bench_device_modes_fail_without_a_tpu(args):
    proc = _run(["bench.py", *args])
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "jax found platform 'cpu'" in proc.stderr


def test_bench_scaling_fails_without_a_tpu_unless_cpu_is_named():
    proc = _run(["bench_scaling.py", "--artifact", ""])
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "--cpu" in proc.stderr


_PLACE = (
    "import jax\n"
    "from protocol_tpu.utils.platform import place_compile_cache\n"
    "before = jax.config.jax_compilation_cache_dir\n"
    "print(place_compile_cache())\n"
    "print(before)\n"
    "print(jax.config.jax_compilation_cache_dir)\n"
)


def test_compile_cache_placed_from_outside_is_left_to_jax(tmp_path):
    placed = str(tmp_path / "elsewhere")
    proc = _run(["-c", _PLACE], JAX_COMPILATION_CACHE_DIR=placed)
    assert proc.returncode == 0, proc.stderr
    # jax read the variable itself; the helper reports it and sets nothing
    assert proc.stdout.split() == [placed, placed, placed]


def test_compile_cache_default_is_one_fixed_directory():
    outs = [_run(["-c", _PLACE]) for _ in range(2)]
    for proc in outs:
        assert proc.returncode == 0, proc.stderr
    want = os.path.join(REPO, ".jax_cache")
    assert outs[0].stdout.split() == [want, "None", want]
    assert outs[1].stdout == outs[0].stdout
