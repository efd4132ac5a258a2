"""Dry-run machinery regression test: lower+compile one small cell on an
8-device fake mesh in a subprocess (the full production sweep lives in
results/dryrun_final; this guards the *mechanism*)."""
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import sys
sys.path.insert(0, os.path.join(os.path.dirname(r"{repo}"), "{repo}", "src"))

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_cell
from repro.launch.roofline import collective_bytes, roofline_terms

mesh = jax.make_mesh((2, 4), ("data", "model"))

cell = get_cell("deepfm", "serve_p99")
args = cell.input_specs()
specs = cell.in_shardings(False)


def fix(tree):
    def conv(s):
        # remap 16-way specs onto the tiny mesh by replication fallback
        return NamedSharding(mesh, P(*[None] * len(s)))
    return jax.tree_util.tree_map(
        conv, tree, is_leaf=lambda x: isinstance(x, P)
    )

with jax.set_mesh(mesh):
    lowered = jax.jit(cell.step_fn, in_shardings=fix(specs)).lower(*args)
    compiled = lowered.compile()
ca = compiled.cost_analysis()
if isinstance(ca, (list, tuple)):
    ca = ca[0]
cb = collective_bytes(compiled.as_text())
t = roofline_terms(
    flops_per_device=float(ca.get("flops", 0.0)),
    bytes_per_device=float(ca.get("bytes accessed", 0.0)),
    collective_bytes_per_device=float(cb["total"]),
)
assert t["dominant"] in ("compute_s", "memory_s", "collective_s")
assert float(ca.get("flops", 0.0)) > 0
print("MINI_DRYRUN_PASS", t["dominant"])
"""


@pytest.mark.slow
def test_dryrun_mechanism_on_mini_mesh(tmp_path):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = tmp_path / "mini_dryrun.py"
    script.write_text(SCRIPT.replace("{repo}", repo))
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, str(script)],
        capture_output=True, text=True, timeout=900, env=env,
    )
    sys.stdout.write(proc.stdout)
    sys.stderr.write(proc.stderr[-2000:])
    assert proc.returncode == 0
    assert "MINI_DRYRUN_PASS" in proc.stdout
