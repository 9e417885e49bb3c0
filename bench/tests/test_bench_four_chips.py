"""The four-chip path on four virtual CPU devices: a sound sharded run is
correct, and one with the exchange between chips left out is not."""
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def test_exchange_left_out_fails(tiny_root):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_COMPILATION_CACHE_DIR=str(tiny_root / "cache"))
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "four_chips.py"), str(tiny_root),
         "tiny.mlm.4chip", str(2**31 + 11)],
        env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    sound, fault = out["sound"], out["no_exchange"]
    assert sound["device"]["count"] == 4
    assert sound["correct"], sound["checks"]
    assert not fault["correct"], fault["checks"]
