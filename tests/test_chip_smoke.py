"""chip_smoke.py's phases on the CPU at a reduced width: the served path
and its f32 reference agree, the chained stage is freshened before its
first run, and four shards on four virtual devices each hold their own
weights and executable and match the one-device logits.  (The script's
main refuses any backend but the TPU; these tests call the phases.)"""
import importlib.util
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


def _load_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _reduced_qwen():
    from repro.configs import get_config
    return get_config("qwen2-0.5b").reduced()


def test_serve_phase_reduced_width_matches_reference():
    smoke = _load_smoke()
    report = smoke.serve_phase(_reduced_qwen(), seed=3, batch=4, seq=32,
                               n_batches=2, n_rows=3)
    assert report["downstream_inline"] == 0
    for key in ("logits[stage0]", "logits[stage1]", "logits[rows]"):
        chk = report[key]
        assert chk["ok"] and chk["max_abs_diff"] <= chk["tol"], (key, chk)


def test_check_logits_flags_a_wrong_forward():
    import numpy as np
    smoke = _load_smoke()
    ref = np.random.default_rng(0).normal(size=(4, 1, 512)).astype(np.float32)
    assert smoke.check_logits(ref + 1e-3, ref)["ok"]
    assert not smoke.check_logits(ref[::-1], ref)["ok"]


def test_main_refuses_a_cpu_backend(capsys):
    smoke = _load_smoke()
    assert smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert "not a TPU" in err and '"ok"' not in out


CHILD = textwrap.dedent(r"""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import importlib.util
    import jax
    spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[1])
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    from repro.configs import get_config
    cfg = get_config("qwen2-0.5b").reduced()
    report = smoke.replica_phase(cfg, 0, 4, batch=2, seq=16)
    devices = jax.local_devices()
    assert len(devices) == 4
    for k in range(4):
        assert report[k]["device"] == str(devices[k]), (k, report[k])
        assert report[k]["max_abs_diff"] == 0.0
    print("PLACEMENT_OK")
""")


def test_replica_phase_pins_shard_k_to_device_k():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [str(ROOT / "src"), os.environ.get("PYTHONPATH", "")]))
    proc = subprocess.run([sys.executable, "-c", CHILD,
                           str(ROOT / "chip_smoke.py")],
                          capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "PLACEMENT_OK" in proc.stdout
