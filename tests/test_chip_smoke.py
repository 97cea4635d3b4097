"""chip_smoke.py off the chip: it refuses to report success without a TPU
or without the repository, its phases pass their own checks at a tiny size
(Pallas kernels in interpret mode), and the four-device partitioned build
is bit-exact against the one-device build on virtual CPU devices."""
import importlib.util
import os
import shutil
import subprocess
import sys

import pytest

from _subproc import REPO, run_with_devices

SMOKE = os.path.join(REPO, "chip_smoke.py")


def _smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod  # dataclasses resolve their module
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("alone", [False, True])
def test_smoke_fails_without_tpu_or_repo(tmp_path, alone):
    script = SMOKE
    if alone:  # a directory holding chip_smoke.py and nothing of the repo
        script = shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable, str(script)], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout


def test_smoke_phases_pass_at_tiny_size():
    cs = _smoke()
    dep = cs.Deployment(columns=64, universe=1 << 12, block=32, draws=256,
                        merge_columns=32)
    idx = cs.run_phases(dep)
    assert len(idx) == dep.columns


def test_four_chip_build_bit_exact_on_virtual_devices():
    out = run_with_devices(f"""
import importlib.util, sys
spec = importlib.util.spec_from_file_location("chip_smoke", {SMOKE!r})
cs = sys.modules["chip_smoke"] = importlib.util.module_from_spec(spec)
spec.loader.exec_module(cs)
print(cs.four_chip_build(rows=32, universe=1 << 14, seed=3))
""", n_devices=4)
    assert "bit-exact" in out
