"""The one-chip training cell end to end on the CPU, at reduced widths."""
import chip_bench_support as sup


def test_train_cell_runs_and_is_correct(tmp_path, capsys):
    base = sup.reduced_copy(tmp_path)
    result = sup.run(base, "xlstm-350m.train")
    assert set(result["metrics"]) == {"tok_s", "setup_s"}
    assert result["correct"], result["checks"]
    assert set(result["checks"]) == {"loss_gap", "grad_gap", "update_gap"}
    assert "bench compiles_in_window 0" in capsys.readouterr().out
