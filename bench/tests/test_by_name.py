"""A later change adds a configuration, a traffic mix, a cell and a per-layer
metric by adding files: in a scratch copy of the benchmark, the harness
finds each of them by the name ``BENCHMARK.json`` gives it."""
import json
import shutil

import harness
import run


def test_new_cell_mix_and_metric_are_found_by_name(tmp_path):
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".out", "__pycache__"))
    shutil.copy(harness.REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    root = tmp_path / "bench"
    mix = harness.load_traffic("chat", root)
    mix.update(name="chat.short", prompt_tokens=dict(mix["prompt_tokens"], max=128))
    (root / "traffic" / "chat.short.json").write_text(json.dumps(mix))
    cell = harness.load_cell("granite-3-2b.exact.offline", root)
    cell.update(name="granite-3-2b.exact.chat-short", traffic="chat.short",
                rate_rps=0.5)
    (root / "cells" / f"{cell['name']}.json").write_text(json.dumps(cell))
    (root / "metrics" / "requests_admitted.py").write_text(
        "def read(rec):\n"
        "    return float(sum(r['admit'] is not None for r in rec.reqs.values()))\n")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["workloads"].append({k: cell[k] for k in
                               ("name", "config", "traffic", "chips", "why")})
    bench["per_layer"].append({
        "name": "requests_admitted", "unit": "requests", "better": "higher",
        "source": "program_counter", "layer": "scheduler admission",
        "moves": "ttft_p90_s", "workloads": [cell["name"]]})
    for name in ("ttft_p90_s", "itl_p99_s"):
        bench["end_to_end"].append({
            "name": name, "unit": "s", "better": "lower", "bound": 0.25,
            "source": "host_clock", "workloads": [cell["name"]]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    names = [m["name"] for m in harness.cell_metrics(bench, cell["name"], True)]
    assert names == ["requests_admitted"]
    r = run.run(cell["name"], 99, 1.5, True, rehearse=True, root=root)
    assert r["correct"], r["checks"]
    assert r["rehearsal_metrics"]["requests_admitted"]["value"] >= 1
