"""The 4 -> 2 revocation cell end to end on four virtual CPU devices (a
child process: the device count is fixed when JAX starts)."""
import json
import os
import subprocess
import sys

import chip_bench_support as sup

CHILD = """
import json, pathlib, sys
sys.path.insert(0, {tests!r})
import chip_bench_support as sup
result = sup.run(pathlib.Path({base!r}), "xlstm-350m.revoke-4to2", seconds=1.0)
print(json.dumps(result))
"""


def test_revoke_cell_reshards_live_and_is_correct(tmp_path):
    base = sup.reduced_copy(tmp_path)
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    code = CHILD.format(tests=str(sup.REPO / "tests" / "chip_bench"), base=str(base))
    out = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert set(result["metrics"]) == {"resume_s", "setup_s"}
    assert result["device"]["count"] == 4
    assert result["correct"], result["checks"]
    assert "revocations 0" not in out.stderr
