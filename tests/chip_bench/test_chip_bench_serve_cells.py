"""The serving cells end to end on the CPU, at reduced widths, called as
functions: every end-to-end metric of the cell is reported, nothing
compiles inside the window, and the served tokens agree with the plain
reference."""
import pytest

import chip_bench_support as sup


@pytest.fixture(scope="module")
def base(tmp_path_factory):
    return sup.reduced_copy(tmp_path_factory.mktemp("serve"))


@pytest.mark.parametrize("cell", ["qwen3-4b.chat", "qwen3-4b.decode"])
def test_serving_cell_runs_and_is_correct(base, cell, capsys):
    result = sup.run(base, cell)
    want = {m["name"] for m in sup.spec()["end_to_end"] if sup.bench.applies(m, cell)}
    assert set(result["metrics"]) == want
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "checks"
    assert "bench compiles_in_window 0" in capsys.readouterr().out
