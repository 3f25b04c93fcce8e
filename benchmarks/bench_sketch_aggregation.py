"""Sec. 3.2 collective-compression claim, measured structurally.

FetchSGD's aggregation claim: cross-client traffic per round is
O(rows x cols), independent of model dimension d.  We lower the mesh
train step for the paper's model at several sketch sizes and count the
data-axis collective bytes in the partitioned HLO, comparing against the
dense-psum baseline (aggregate='dense').  Runs on a small host-device
mesh inside a subprocess (device count must be set before jax init).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json
sys.path.insert(0, "src")
import jax
from repro import configs
from repro.core import fetchsgd as F
from repro.launch import analysis, shapes, steps
from repro.launch.mesh import make_mesh

mesh = make_mesh((4, 2), ("data", "model"))
cfg = configs.get_smoke("gpt2s-federated")
shape = shapes.ShapeSpec("t", "train", 128, 8)
out = {}
for name, agg, cols in (("sketch_64k", "sketch", 1 << 16),
                        ("sketch_256k", "sketch", 1 << 18),
                        ("dense", "dense", 1 << 16)):
    fs = F.FetchSGDConfig(rows=5, cols=cols, k=1024)
    b = steps.make_train_step(cfg, shape, mesh, fs, aggregate=agg)
    with mesh:
        compiled = b.fn.lower(*b.inputs).compile()
    out[name] = analysis.collective_bytes(compiled.as_text())
print(json.dumps(out))
"""


def run() -> list[tuple[str, float, str]]:
    t0 = time.time()
    # the child counts HLO bytes on a forced host mesh: keep it on the CPU,
    # off the accelerator the parent process may already hold
    proc = subprocess.run([sys.executable, "-c", _SCRIPT],
                          capture_output=True, text=True, timeout=1200,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"})
    us = (time.time() - t0) * 1e6
    if proc.returncode != 0:
        return [("sec32_sketch_aggregation", us,
                 "FAILED:" + proc.stderr.strip().splitlines()[-1][:120])]
    data = json.loads(proc.stdout.strip().splitlines()[-1])
    rows = []
    for name, coll in data.items():
        rows.append((f"sec32_collectives_{name}", us / 3,
                     f"coll_bytes={coll.get('total', 0)}"))
    return rows
