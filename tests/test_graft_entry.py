"""``__graft_entry__.dryrun_multichip``: the entry point CI and the
driver call, in a child process (it pins its own platform and device
count, which a process that has already touched jax cannot)."""

import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent


@pytest.mark.parametrize("n", [2, 4, 8])
def test_dryrun_multichip(n):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c",
         f"import __graft_entry__ as g; g.dryrun_multichip({n})"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-4000:]
    last = out.stdout.strip().splitlines()[-1]
    assert last.startswith(f"dryrun_multichip({n}): ")
    assert "bit-equal to one device" in last
