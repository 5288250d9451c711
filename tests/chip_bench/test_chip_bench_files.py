"""A configuration, a traffic mix, a limits file, a per-layer metric and a
model family are added as new files only, with no edit to a file that is
there, and the harness finds them by the names in BENCHMARK.json and runs
them."""
import json
import shutil

import chip_bench_support as sup

READER = '''"""Decode steps per request in the window (a test metric)."""


def read(trace, counts, cell):
    if not counts.get("requests"):
        return None
    return len(counts["steps"]) / counts["requests"]
'''

FLOPS_READER = '''"""Model FLOPs of a decoded token, from the cell's own family (a test metric)."""
import counters


def read(trace, counts, cell):
    return 2 * counters.matmul_params(cell.config, cell.base)
'''


def test_cell_added_as_files_runs(tmp_path):
    base = sup.reduced_copy(tmp_path)
    conf = json.loads((base / "configs" / "qwen3-4b.json").read_text())
    conf.update(name="qwen3-tiny", num_hidden_layers=1, intermediate_size=96)
    (base / "configs" / "qwen3-tiny.json").write_text(json.dumps(conf))
    tr = json.loads((base / "traffic" / "decode.json").read_text())
    tr.update(clients=2, per_client=2, output={"dist": "fixed", "value": 12})
    (base / "traffic" / "decode-two.json").write_text(json.dumps(tr))
    (base / "limits" / "qwen3-tiny.decode-two.json").write_text('{"logit_gap": 1.0}')
    (base / "metrics" / "engine.steps_per_request.py").write_text(READER)

    spec = sup.spec()
    spec["configs"].append({"name": "qwen3-tiny", "source": "https://example.org/tiny",
                            "file": "benchmarks/chip/configs/qwen3-tiny.json",
                            "reduced": [], "why": "a test"})
    spec["workloads"].append({"name": "qwen3-tiny.decode-two", "config": "qwen3-tiny",
                              "traffic": "decode-two", "chips": 1, "why": "a test"})
    spec["per_layer"].append({"name": "engine.steps_per_request", "unit": "steps",
                              "better": "lower", "source": "program_counter",
                              "layer": "Engine (serve/engine.py)", "moves": "tok_s",
                              "workloads": ["qwen3-tiny.decode-two"]})
    for m in spec["end_to_end"]:
        if m["name"] == "tok_s":
            m["workloads"].append("qwen3-tiny.decode-two")

    plain = sup.run(base, "qwen3-tiny.decode-two", spec_=spec)
    assert plain["correct"] and {"tok_s", "setup_s"} <= set(plain["metrics"])
    traced = sup.run(base, "qwen3-tiny.decode-two", spec_=spec, trace=True)
    assert set(traced["metrics"]) == {"engine.steps_per_request"}
    assert traced["metrics"]["engine.steps_per_request"]["value"] > 0
    assert "busy_s" in traced["device"] and "breakdown" in traced


def test_family_added_as_files_runs(tmp_path):
    """A new family is ``reference/<family>.py`` (here a copy of Qwen3's
    under another name), found in the cell's own directory by the drivers,
    the weights and the counters."""
    base = sup.reduced_copy(tmp_path)
    before = {p: p.read_bytes() for p in base.rglob("*") if p.is_file()}
    shutil.copy(base / "reference" / "qwen3.py", base / "reference" / "twin.py")
    conf = json.loads((base / "configs" / "qwen3-4b.json").read_text())
    conf.update(name="twin-tiny", reference="twin")
    (base / "configs" / "twin-tiny.json").write_text(json.dumps(conf))
    tr = json.loads((base / "traffic" / "decode.json").read_text())
    tr.update(clients=2, per_client=2)
    (base / "traffic" / "decode-twin.json").write_text(json.dumps(tr))
    (base / "limits" / "twin-tiny.decode-twin.json").write_text('{"logit_gap": 0.1}')
    (base / "metrics" / "model.flops_per_token.py").write_text(FLOPS_READER)

    spec = sup.spec()
    spec["configs"].append({"name": "twin-tiny", "source": "https://example.org/twin",
                            "file": "benchmarks/chip/configs/twin-tiny.json",
                            "reduced": [], "why": "a test"})
    spec["workloads"].append({"name": "twin-tiny.decode-twin", "config": "twin-tiny",
                              "traffic": "decode-twin", "chips": 1, "why": "a test"})
    spec["per_layer"].append({"name": "model.flops_per_token", "unit": "FLOP",
                              "better": "lower", "source": "program_counter",
                              "layer": "Model step", "moves": "tok_s",
                              "workloads": ["twin-tiny.decode-twin"]})
    for m in spec["end_to_end"]:
        if m["name"] == "tok_s":
            m["workloads"].append("twin-tiny.decode-twin")

    plain = sup.run(base, "twin-tiny.decode-twin", spec_=spec)
    assert plain["correct"], plain["checks"]
    assert {"tok_s", "setup_s"} <= set(plain["metrics"])
    traced = sup.run(base, "twin-tiny.decode-twin", spec_=spec, trace=True)
    d, qd, kd, f, V = 64, 4 * 16, 2 * 16, 128, 256
    per_layer = 2 * d * qd + 2 * d * kd + 3 * d * f
    assert traced["correct"], traced["checks"]
    assert traced["metrics"]["model.flops_per_token"]["value"] == 2 * (2 * per_layer + d * V)
    assert all(p.read_bytes() == data for p, data in before.items())
