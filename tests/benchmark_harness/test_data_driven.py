"""A later PR adds a cell, a configuration, a mix and a per-layer metric as
new files plus entries, never by editing a file that is there: shown on a
copy of the benchmark. And BENCHMARK.json keeps to the contract's limits."""
import json
import os
import re

from bh_util import REPO, copy_benchmark, rehearse

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _bench(root=REPO) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def test_new_cell_config_mix_and_metric_are_only_new_files(tmp_path):
    root = copy_benchmark(tmp_path)
    before = {}
    for d, _, files in os.walk(os.path.join(root, "benchmarks")):
        for f in files:
            path = os.path.join(d, f)
            with open(path, "rb") as fh:
                before[path] = fh.read()
    here = os.path.join(root, "benchmarks")
    # a configuration: the one-chip serving config under another name
    with open(os.path.join(here, "configs", "mistral7b-serve-1chip.json")) as f:
        config = json.load(f)
    config["rehearsal"]["engine"]["max_batch_size"] = 2
    with open(os.path.join(here, "configs", "other-serve.json"), "w") as f:
        json.dump(config, f)
    # a mix: one arrival per slot, fixed lengths, a shared system prompt
    with open(os.path.join(here, "traffic", "steady-shared.json"), "w") as f:
        json.dump({"generator": "open_loop", "arrivals": "stratified",
                   "rate_rps": 3.0, "warmup_s": 1.0,
                   "request_timeout_s": 60.0, "max_context_tokens": 128,
                   "shared_prefix_tokens": 32,
                   "prompt_tokens": {"dist": "const", "value": 12},
                   "output_tokens": {"dist": "const", "value": 5}}, f)
    # a per-layer metric: a reader of its own
    with open(os.path.join(here, "layer_metrics",
                           "requests_completed.py"), "w") as f:
        f.write("def read(ctx):\n"
                "    return float(sum(1 for r in ctx['records'] if r.ok))\n")
    bench = _bench(root)
    bench["configs"].append({
        "name": "other-serve", "source": config["source"],
        "file": "benchmarks/configs/other-serve.json",
        "reduced": ["num_hidden_layers"], "why": "test"})
    bench["workloads"].append({
        "name": "steady-shared-other", "config": "other-serve",
        "traffic": "steady-shared", "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "requests_completed", "unit": "requests", "better": "higher",
        "source": "host_clock", "layer": "load generator",
        "moves": "setup_s", "workloads": ["steady-shared-other"]})
    for m in bench["per_layer"]:
        if m["name"] == "prefix_hit_tok_share":
            m["workloads"].append("steady-shared-other")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    line = rehearse("steady-shared-other", root=root, trace=1)
    assert line["correct"] and line["failed"] == 0
    assert set(line["metrics"]) == {"requests_completed",
                                    "prefix_hit_tok_share"}
    assert line["metrics"]["requests_completed"]["unit"] == "requests"
    for path, data in before.items():
        with open(path, "rb") as fh:
            assert fh.read() == data, f"{path} was edited"


def test_benchmark_json_keeps_to_the_contract(bench_root):
    """On the tree and on a copy with a fifth cell appended."""
    b = _bench(bench_root)
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51 and isinstance(b["run_seconds"], int)
    assert 2 <= len(b["workloads"]) <= 24
    # the contract's caps: the benchmark reached 128 per-layer entries
    # unnoticed once (PR 50), because nothing asserted this
    assert 1 <= len(b["per_layer"]) <= 128
    assert 1 <= len(b["end_to_end"]) <= 16 and len(b["configs"]) <= 24
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in b[k]]
    for n in names + [w["traffic"] for w in b["workloads"]] + \
            [k for c in b["configs"] for k in c["reduced"]]:
        assert NAME.match(n), n
    for k in ("configs", "workloads"):
        assert len({x["name"] for x in b[k]}) == len(b[k])
    metrics = b["end_to_end"] + b["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in b["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        # a per-layer metric is reported only where the metric it moves is
        cells = set(m.get("workloads", [w["name"] for w in b["workloads"]]))
        moved = set(e2e[m["moves"]].get(
            "workloads", [w["name"] for w in b["workloads"]]))
        assert cells <= moved, m["name"]
    used = {w["config"] for w in b["workloads"]}
    assert used == {c["name"] for c in b["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in b["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(1 for w in b["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(b["workloads"]) // 4)
    for w in b["workloads"]:
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        cell_metrics = [m for m in metrics
                        if w["name"] in m.get("workloads", [w["name"]])]
        assert sum(1 for m in cell_metrics if m in b["end_to_end"]) >= 2
        assert any(m in b["per_layer"] for m in cell_metrics)
    for c in b["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in b["paths"]))
        with open(os.path.join(bench_root, c["file"])) as f:
            cfg = json.load(f)
        assert set(c["reduced"]) == set(cfg["reduced"])
    files = [c["file"] for c in b["configs"]]
    assert len(set(files)) == len(files)    # no other configuration's file


def test_every_named_file_exists(bench_root):
    b = _bench(bench_root)
    here = os.path.join(bench_root, "benchmarks")
    for w in b["workloads"]:
        assert os.path.exists(os.path.join(here, "traffic",
                                           f"{w['traffic']}.json"))
    for m in b["end_to_end"]:
        assert os.path.exists(os.path.join(here, "e2e_metrics",
                                           f"{m['name']}.py"))
    for m in b["per_layer"]:
        assert os.path.exists(os.path.join(here, "layer_metrics",
                                           f"{m['name']}.py"))
    for root, _, files in os.walk(here):
        for f in files:
            if "__pycache__" not in root:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", f), f
