"""A cell added through files alone: a configuration file, a traffic file
and an entry in BENCHMARK.json, and for a serving model its two model
files; no code changes."""
import json
import shutil

import pytest

from chipbench import run as harness
from chipbench import spec

TINY_ER = {"system": "sssp", "n": 128, "p": 0.5, "places": 4,
           "policy": "HYBRID", "k": 2, "arbitration": "fused",
           "topk_backend": "auto", "limits": {"dist_rel_gap": 1e-4}}
TWO_GRAPHS = {"kind": "closed_loop", "entry": "run_sssp",
              "graphs_resident": 2, "graphs_per_call": 1}


def write_bench(root):
    (root / "chipbench" / "configs").mkdir(parents=True)
    (root / "chipbench" / "traffic").mkdir(parents=True)
    (root / "chipbench" / "configs" / "tiny_er.json").write_text(
        json.dumps(TINY_ER))
    (root / "chipbench" / "traffic" / "two_graphs.json").write_text(
        json.dumps(TWO_GRAPHS))
    bench = {
        "command": ["python3", "-m", "chipbench.run"], "paths": ["chipbench"],
        "run_seconds": 1,
        "configs": [{"name": "tiny_er", "source": "test", "reduced": [],
                     "file": "chipbench/configs/tiny_er.json", "why": "test"}],
        "workloads": [{"name": "tiny_er.solo", "config": "tiny_er",
                       "traffic": "two_graphs", "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "sssp_graphs_per_s", "unit": "graphs/s",
             "better": "higher", "bound": 0.05, "source": "host_clock",
             "workloads": ["tiny_er.solo"]},
            {"name": "serve_tokens_per_s", "unit": "tokens/s",
             "better": "higher", "bound": 0.05, "source": "host_clock",
             "workloads": ["elsewhere"]},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [
            {"name": "sssp_phases_per_graph", "unit": "phases",
             "better": "lower", "source": "program_counter",
             "layer": "scheduler ops", "moves": "sssp_graphs_per_s",
             "workloads": ["tiny_er.solo"]},
            {"name": "serve_mfu", "unit": "%", "better": "higher",
             "source": "host_clock", "layer": "model",
             "moves": "serve_tokens_per_s"}],
    }
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def test_cell_found_by_name_with_its_metrics(tmp_path):
    write_bench(tmp_path)
    cell = spec.load_cell("tiny_er.solo", tmp_path)
    assert cell.system == "sssp" and cell.config["n"] == 128
    assert [m.name for m in cell.end_to_end] == ["sssp_graphs_per_s",
                                                 "setup_s"]
    # serve_mfu moves a metric this cell does not report, so it is left out
    assert [m.name for m in cell.per_layer] == ["sssp_phases_per_graph"]


def test_added_cell_runs_and_is_correct(tmp_path):
    write_bench(tmp_path)
    res = harness.run("tiny_er.solo", 2**31 + 11, 1.0, False, rehearse=True,
                      root=tmp_path)
    assert res["correct"] is True
    assert res["counts"]["graphs"] > 0
    assert "metrics" not in res          # a CPU run reports no device metric
    assert list(res)[-1] == "checks"


def write_serve_bench(root, model_type, model_files=True):
    """A serving cell at the Qwen3 configuration's ``rehearse`` sizes under
    ``root``, whose model files are the Qwen3 ones named ``model_type``."""
    pkg, dst = spec.HERE, root / "chipbench"
    for d in ("configs", "traffic", "reference", "adapters"):
        (dst / d).mkdir(parents=True, exist_ok=True)
    config = json.loads((pkg / "configs" / "qwen3_1_7b_s8.json").read_text())
    config["model_type"] = model_type
    (dst / "configs" / "tiny_lm.json").write_text(json.dumps(config))
    shutil.copy(pkg / "traffic" / "chat_steady.json", dst / "traffic")
    for d in ("reference", "adapters") if model_files else ():
        shutil.copy(pkg / d / "qwen3.py", dst / d / f"{model_type}.py")
    serve = ["tiny_lm.chat"]
    bench = {
        "command": ["python3", "-m", "chipbench.run"], "paths": ["chipbench"],
        "run_seconds": 2,
        "configs": [{"name": "tiny_lm", "source": "test", "reduced": [],
                     "file": "chipbench/configs/tiny_lm.json", "why": "test"}],
        "workloads": [{"name": "tiny_lm.chat", "config": "tiny_lm",
                       "traffic": "chat_steady", "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "serve_ttft_mean_ms", "unit": "ms", "better": "lower",
             "bound": 0.2, "source": "host_clock", "workloads": serve},
            {"name": "serve_tpot_p95_ms", "unit": "ms", "better": "lower",
             "bound": 0.1, "source": "host_clock", "workloads": serve},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [
            {"name": "serve_mfu", "unit": "%", "better": "higher",
             "source": "host_clock", "layer": "model",
             "moves": "serve_tpot_p95_ms", "workloads": serve}],
    }
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def package_files():
    return {p: p.stat().st_mtime_ns for p in spec.HERE.rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


@pytest.mark.parametrize("model_type", ["qwen3", "qwen3_renamed"])
def test_added_serving_model_runs_and_is_correct(tmp_path, model_type):
    """Qwen3's files under a new root, under its own name and under a
    name the package has no file for: found by ``model_type`` alone."""
    write_serve_bench(tmp_path, model_type)
    before = package_files()
    res = harness.run("tiny_lm.chat", 2**31 + 13, 2.0, False, rehearse=True,
                      root=tmp_path)
    assert res["correct"] is True
    assert res["counts"]["tokens"] > 0 and res["counts"]["model_flop"] > 0
    assert res["checks"]["undelivered"]["value"] == 0
    assert package_files() == before


def test_unknown_model_type_is_refused_in_setup(tmp_path):
    write_serve_bench(tmp_path, "no_such_model", model_files=False)
    with pytest.raises(ValueError) as err:
        harness.run("tiny_lm.chat", 2**31 + 17, 2.0, False, rehearse=True,
                    root=tmp_path)
    msg = str(err.value)
    for f in ("chipbench/reference/no_such_model.py",
              "chipbench/adapters/no_such_model.py", "make_weights",
              "flops_per_token", "model_config"):
        assert f in msg
