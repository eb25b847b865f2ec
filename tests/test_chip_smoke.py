"""CPU rehearsal of ``chip_smoke.py``: its phase functions on the
reduced TinyLlama config, with the Pallas kernels on the path in
interpret mode, plus its refusal to run anywhere but on a TPU."""
import dataclasses
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.configs import get_config

ROOT = Path(__file__).resolve().parents[1]

SMALL = dict(n_requests=3, min_prompt=6, max_prompt=20, max_new=5,
             max_batch=2, page_size=8, prefill_chunk=16,
             prefill_buckets=(8, 16), calib_seqs=4, calib_len=32,
             int8_requests=2, int8_max_new=4, int8_splits=2)


def _smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod     # dataclasses resolve it by name
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_phases_on_cpu(tpu_kernels):
    smoke = _smoke()
    cfg = dataclasses.replace(get_config("tinyllama-1.1b").reduced(),
                              dtype="bfloat16")
    lines = []
    rep = smoke.run_single(cfg, smoke.Plan(**SMALL), seed=0,
                           log=lines.append)
    assert [ln[:3] for ln in lines] == ["[b]", "[c]", "[d]", "[e]", "[e]"]
    assert set(rep["kernel_errors"]) == {
        "paged_decode", "paged_decode_split4", "paged_decode_int8",
        "paged_prefill"}
    assert max(rep["kernel_errors"].values()) < smoke.BF16_TOL
    # interpret mode lowers the kernels to plain HLO: no Mosaic calls
    assert rep["tpu_custom_calls"] == 0
    assert rep["cold"]["tokens"] == SMALL["n_requests"] * SMALL["max_new"]
    assert len(rep["warm"]["first_token_s"]) == SMALL["n_requests"]
    assert rep["int8"]["tokens"] == (SMALL["int8_requests"]
                                     * SMALL["int8_max_new"])


def test_chip_smoke_divergences():
    """Runs that part are judged by their own logits at the first
    divergent step: within tolerance, beyond it, or never recorded."""
    import numpy as np
    from repro.serving import Request
    smoke = _smoke()
    p = np.arange(1, 5, dtype=np.int32)
    a = [Request(rid=0, prompt=p, out_tokens=[3, 4]),
         Request(rid=1, prompt=p, out_tokens=[5, 6, 7])]
    b = [Request(rid=0, prompt=p, out_tokens=[3, 4]),
         Request(rid=1, prompt=p, out_tokens=[5, 9, 7])]
    lg = np.linspace(-4.0, 4.0, 16, dtype=np.float32)
    near = {(1, 1): lg + 0.5 * smoke.BF16_TOL}
    d, = smoke.divergences(a, b, near, {(1, 1): lg})
    assert (d["rid"], d["step"]) == (1, 1)
    assert d["max_err"] <= d["tol"] == smoke.BF16_TOL * 4.0
    far = {(1, 1): lg + 10 * smoke.BF16_TOL}
    d, = smoke.divergences(a, b, far, {(1, 1): lg})
    assert d["max_err"] > d["tol"]
    with pytest.raises(AssertionError, match="no logits"):
        smoke.divergences(a, b, {}, {(1, 1): lg})
    assert smoke.divergences(a, a, {}, {}) == []


SHARDED = r"""
import dataclasses, sys
sys.path.insert(0, sys.argv[1])
import repro.kernels
repro.kernels.use_kernels = lambda: True
import chip_smoke
from repro.configs import get_config
cfg = dataclasses.replace(get_config("tinyllama-1.1b").reduced(),
                          dtype="bfloat16")
plan = chip_smoke.Plan(n_requests=4, min_prompt=6, max_prompt=20,
                       max_new=4, max_batch=4, page_size=8,
                       prefill_chunk=16, prefill_buckets=(8, 16),
                       calib_seqs=4, calib_len=32)
lines = []
print("PARTED", chip_smoke.run_sharded(cfg, plan, 0, 4, lines.append))
print("LINES", [ln[:9] for ln in lines])
"""


def test_chip_smoke_sharded_phase_on_cpu():
    """The ``--four-chip`` phase on four forced CPU host devices."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    env.pop("REPRO_ENGINE", None)
    r = subprocess.run([sys.executable, "-c", SHARDED, str(ROOT)],
                       env=env, capture_output=True, text=True,
                       timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-4000:]
    assert "PARTED []" in r.stdout
    assert "LINES ['[sharded]', '[sharded]', '[sharded]']" in r.stdout


def test_chip_smoke_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       env=env, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr
    assert '"ok"' not in r.stdout
