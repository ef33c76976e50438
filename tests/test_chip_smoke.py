"""chip_smoke.py off the chip: it refuses to run without a TPU, keeps the
chip path free of modules that reconfigure devices at import, and its
one-chip phases pass end to end at reduced widths on the CPU backend."""
import functools
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(*args, **env):
    return subprocess.run([sys.executable, *args], cwd=ROOT,
                          capture_output=True, text=True, timeout=300,
                          env={**os.environ, **env})


def test_refuses_without_tpu():
    res = _run("chip_smoke.py", JAX_PLATFORMS="cpu")
    assert res.returncode != 0
    assert "no TPU" in res.stderr
    assert '"ok"' not in res.stdout


def test_chip_path_never_imports_dryrun():
    """launch/dryrun.py forces 512 host devices as it is imported."""
    code = ("import sys; sys.path[:0] = ['src', '.']; import chip_smoke, "
            "repro.launch.serve, repro.launch.train; "
            "print('repro.launch.dryrun' in sys.modules)")
    res = _run("-c", code, JAX_PLATFORMS="cpu")
    assert res.returncode == 0, res.stderr
    assert res.stdout.split() == ["False"]


def test_one_chip_phases_at_reduced_widths(smoke, monkeypatch, tmp_path):
    import repro.serving
    from repro.configs import get_config
    from repro.launch.mesh import make_host_mesh
    monkeypatch.setattr(smoke, "SLOTS", 4)
    monkeypatch.setattr(smoke, "MAX_SEQ", 96)
    monkeypatch.setattr(smoke, "MAX_NEW", 6)
    monkeypatch.setattr(smoke, "PROMPT_LENS", (8, 40))
    monkeypatch.setattr(smoke, "SCRATCH", tmp_path)
    monkeypatch.setattr(smoke, "serve_cfg", lambda: get_config(
        "phi3-mini-3.8b").reduced().with_(param_dtype="bfloat16",
                                          remat="none"))
    # reduced pools are far below the 64 MiB production spill granule
    monkeypatch.setattr(repro.serving, "TenantSpec", functools.partial(
        repro.serving.TenantSpec, spill_granule=1024))
    meter = smoke.Meter()
    smoke.phase_serve_and_offload(meter, make_host_mesh(1, 1))
    smoke.phase_train(meter, steps=2, batch=2, seq=16, full_size=False)
