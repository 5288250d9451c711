"""chip_smoke.py rehearsed on the CPU at reduced widths.

The script itself refuses to run without a TPU; its phases are plain
functions, so the tests drive them here with the reduced configurations
(and the paged kernel in interpret mode) to catch a broken path before
any chip time is spent. Nothing here is a device measurement.
"""
import json
import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    sys.path.insert(0, str(REPO))
    try:
        import chip_smoke
    finally:
        sys.path.remove(str(REPO))
    return chip_smoke


def test_chip_smoke_refuses_to_run_without_a_tpu():
    res = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")],
        capture_output=True, text=True, timeout=300, cwd=str(REPO),
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert res.returncode != 0
    assert "no TPU found" in res.stderr
    assert '"ok"' not in res.stdout


def test_one_chip_phases_at_reduced_width(smoke):
    from repro.dist import ElasticMeshManager

    man = ElasticMeshManager()
    serve = smoke.serve_phase(man, 0, reduced=True)
    assert serve["tokens_generated"] == smoke.REQUESTS * smoke.NEW_TOKENS
    assert serve["lane0_min_corr"] > smoke.CORR_MIN
    assert serve["weights_dtype"] == "bfloat16"
    kernel = smoke.kernel_phase(0, reduced=True, interpret=True)
    assert kernel["max_abs_err"] < 2e-2
    train = smoke.train_phase(man, 0, reduced=True)
    assert train["useful_steps"] == smoke.TRAIN_STEPS
    json.dumps([serve, kernel, train])  # every phase line is JSON


FOUR_CHIP_SCRIPT = textwrap.dedent(
    """
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, sys.argv[1])
    import chip_smoke
    from repro.dist import ElasticMeshManager

    man = ElasticMeshManager()
    serve = chip_smoke.four_chip_serve(man, 0, reduced=True)
    assert serve["plans"] == ["2x2", "2x1"], serve
    assert serve["params_bytes"] == sum(serve["params_bytes_per_device"].values())
    train = chip_smoke.four_chip_train(man, 0, reduced=True)
    assert train["reshard_bytes"] > 0, train
    print("FOUR_CHIP_OK")
    """
)


def test_four_chip_path_on_virtual_devices():
    res = subprocess.run(
        [sys.executable, "-c", FOUR_CHIP_SCRIPT, str(REPO)],
        capture_output=True, text=True, timeout=600, cwd=str(REPO),
        env={**os.environ, "PYTHONPATH": str(REPO / "src"), "JAX_PLATFORMS": "cpu"},
    )
    assert "FOUR_CHIP_OK" in res.stdout, res.stdout + res.stderr
