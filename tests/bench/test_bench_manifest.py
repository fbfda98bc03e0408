"""The harness finds every piece of a cell by name: a new configuration,
traffic mix and metric need only new files and new entries."""
import json
import shutil

import pytest

import tiny
import manifest as M


def test_shipped_cells_resolve():
    man = M.load_manifest()
    for w in man["workloads"]:
        cell = M.load_cell(w["name"])
        assert cell.chips == 1
        assert [m.name for m in cell.end_to_end][-1] == "setup_s" or \
            "setup_s" in [m.name for m in cell.end_to_end]
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.end_to_end + cell.per_layer:
            assert callable(m.reader.read)
    names = {m["name"] for m in man["per_layer"]}
    assert {"admit_ms_p50", "admit_ms_p50.closed", "ttft_p95_ms.closed",
            "chunk_ms_p50.tput", "chunk_ms_p50.short",
            "paged_attn_roofline", "step_mfu"} == names


def test_unknown_device_kind_is_an_error():
    assert M.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        M.load_peaks("cpu")


def test_new_config_mix_and_metric_by_files_alone(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(M.BENCH_DIR, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    man = M.load_manifest()
    # a throwaway configuration: the tiny smollm sizes under a new name
    cfg = json.loads((root / "bench/configs/smollm-360m.json").read_text())
    cfg["name"] = "toy-lm"
    cfg["model"].update(tiny.TINY_MODEL["smollm-360m"])
    (root / "bench/configs/toy-lm.json").write_text(json.dumps(cfg))
    shutil.copy(root / "bench/configs/smollm-360m.py",
                root / "bench/configs/toy-lm.py")
    # a throwaway mix of an existing generator kind, and a metric
    (root / "bench/traffic/toy-mix.json").write_text(json.dumps(dict(
        tiny.TINY_TRAFFIC["agent-prefix"], kind="closed_backlog")))
    (root / "bench/metrics/toy_count.py").write_text(
        "def read(run):\n    return 42.0\n")
    man["configs"].append({"name": "toy-lm", "source": "test",
                           "file": "bench/configs/toy-lm.json",
                           "reduced": [], "why": "test"})
    man["workloads"].append({"name": "toy.cell", "config": "toy-lm",
                             "traffic": "toy-mix", "chips": 1, "why": "t"})
    man["per_layer"].append({"name": "toy_count", "unit": "1",
                             "better": "higher", "source": "program_counter",
                             "layer": "test", "moves": "tokens_per_s",
                             "workloads": ["toy.cell"]})
    man["end_to_end"][0]["workloads"].append("toy.cell")
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    cell = M.load_cell("toy.cell", root=str(root))
    assert cell.config["model"]["d_model"] == 64
    assert cell.traffic["clients"] == 4
    assert cell.generator.__file__.endswith("closed_backlog.py")
    per = {m.name: m for m in cell.per_layer}
    assert per["toy_count"].reader.read(None) == 42.0
    assert [m.name for m in cell.end_to_end] == ["tokens_per_s", "setup_s"]
    gen = cell.generator.build(cell.traffic, 5, 512, 4, 1.0)
    assert len(gen.initial()) == 4
    shapes = cell.model.weight_shapes(cell.config)
    assert shapes["layers"]["attn"]["wk"][1] == (2, 64, 2, 16)
    # the shipped cells are untouched by the new entries
    assert M.load_cell("smollm360m.agent-prefix", root=str(root)) \
        .config["model"]["d_model"] == 960
