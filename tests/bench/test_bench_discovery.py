"""A configuration, a traffic mix, a cell's limits and a per-layer metric
are found by name: adding them is adding files and entries, and no file
the benchmark already has changes."""
import hashlib
import json
import shutil

from tiny import ROOT

from bench.lib import spec


def digest(root):
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()}


def test_new_pieces_are_found_by_name(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    before = digest(tmp_path / "bench")

    b = tmp_path / "bench"
    c = json.loads((b / "configs" / "qwen3-1.7b.json").read_text())
    c["name"] = "other-model"
    (b / "configs" / "other-model.json").write_text(json.dumps(c))
    t = json.loads((b / "traffic" / "gepo_rollout.json").read_text())
    t["prompts"] = 4
    (b / "traffic" / "short_rollout.json").write_text(json.dumps(t))
    (b / "limits" / "short_rollout.other-model.json").write_text(
        json.dumps({"logp_gap": {"limit": 0.5}}))
    (b / "metrics" / "new_metric.py").write_text(
        "def read(rec):\n    return 42.0 if rec['kind'] == 'rollout' else None\n")

    bj = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bj["configs"].append({"name": "other-model", "source": "x",
                          "file": "bench/configs/other-model.json",
                          "reduced": [], "why": "test"})
    bj["workloads"].append({"name": "short_rollout.other-model",
                            "config": "other-model",
                            "traffic": "short_rollout", "chips": 1,
                            "why": "test"})
    bj["per_layer"].append({"name": "new_metric", "unit": "%",
                            "better": "higher", "source": "program_counter",
                            "layer": "scheduler (repro.sampling)",
                            "moves": "rollout_tokens_per_s",
                            "workloads": ["short_rollout.other-model"]})
    bj["end_to_end"][1]["workloads"].append("short_rollout.other-model")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bj))

    cell = spec.load_cell("short_rollout.other-model",
                          bench_json=tmp_path / "BENCHMARK.json",
                          bench_dir=b)
    assert cell.config["name"] == "other-model"
    assert cell.traffic["prompts"] == 4 and cell.kind == "rollout"
    assert cell.limits == {"logp_gap": 0.5}
    assert [m["name"] for m in cell.per_layer] == ["new_metric"]
    assert {m["name"] for m in cell.end_to_end} == {"rollout_tokens_per_s",
                                                    "setup_s"}
    reader = spec.metric_reader("new_metric", bench_dir=b)
    assert reader.read({"kind": "rollout"}) == 42.0
    assert spec.kind_module(cell.kind, bench_dir=b).run
    after = digest(b)
    assert {k: v for k, v in after.items() if k in before} == before


def test_every_committed_piece_is_found():
    bj = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bj["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.limits
        spec.kind_module(cell.kind)
        for m in cell.per_layer:
            assert hasattr(spec.metric_reader(m["name"]), "read")
