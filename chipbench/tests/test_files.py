"""A cell added through files alone: a configuration file, a traffic file
and an entry in BENCHMARK.json; no code changes."""
import json

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
