import json

from chipbench import spec


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    """A later change adds a cell by adding files and entries: a config,
    a traffic mix and a per-layer metric, none of them known to the
    harness's code, are all found by the names BENCHMARK.json gives."""
    bench_dir = tmp_path / "chipbench"
    for sub in ("configs", "traffic", "metrics"):
        (bench_dir / sub).mkdir(parents=True)
    (bench_dir / "configs" / "brand-new.json").write_text(json.dumps(
        {"name": "brand-new", "n_docs": 1234}))
    (bench_dir / "traffic" / "bursty.json").write_text(json.dumps(
        {"loop": "open", "rate_rps": 3.0}))
    (bench_dir / "metrics" / "queue_depth.p90.py").write_text(
        "def read(run):\n    return run['answer']\n")
    bench = {
        "configs": [{"name": "brand-new", "source": "x", "reduced": [],
                     "file": "chipbench/configs/brand-new.json", "why": "x"}],
        "workloads": [{"name": "brand-new.bursty", "config": "brand-new",
                       "traffic": "bursty", "chips": 1, "why": "x"}],
        "end_to_end": [
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"},
            {"name": "latency_p50_s", "unit": "s", "better": "lower",
             "bound": 0.1, "source": "host_clock",
             "workloads": ["brand-new.bursty"]}],
        "per_layer": [
            {"name": "queue_depth.p90", "unit": "req", "better": "lower",
             "source": "program_counter", "layer": "engine",
             "moves": "latency_p50_s"},
            {"name": "elsewhere", "unit": "%", "better": "higher",
             "source": "device_trace", "layer": "kernels",
             "moves": "latency_p50_s", "workloads": ["other.cell"]}],
    }
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    loaded = spec.load_benchmark(tmp_path)
    cell = spec.find_cell(loaded, "brand-new.bursty")
    assert spec.load_config(loaded, cell["config"], tmp_path)["n_docs"] == 1234
    assert spec.load_traffic(cell["traffic"], bench_dir)["rate_rps"] == 3.0
    layers = spec.per_layer_for(loaded, cell["name"])
    assert [m["name"] for m in layers] == ["queue_depth.p90"]
    read = spec.load_reader("queue_depth.p90", bench_dir)
    assert read({"answer": 7}) == 7
    assert [m["name"] for m in spec.end_to_end_for(loaded, cell["name"])] \
        == ["setup_s", "latency_p50_s"]


def test_the_committed_benchmark_names_files_that_exist():
    bench = spec.load_benchmark()
    for cell in bench["workloads"]:
        spec.load_config(bench, cell["config"])
        spec.load_traffic(cell["traffic"])
        for m in spec.per_layer_for(bench, cell["name"]):
            assert callable(spec.load_reader(m["name"]))
        # every cell reports set-up, one more end-to-end metric and a layer
        e2e = [m["name"] for m in spec.end_to_end_for(bench, cell["name"])]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert spec.per_layer_for(bench, cell["name"])

