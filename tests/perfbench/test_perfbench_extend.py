"""A later PR adds a configuration, a traffic mix, a per-layer metric and
a cell as new files and appended entries only: done here in a temporary
copy, where no file that was there is touched, and the new cell runs."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

from perfbench import manifest

ROOT = manifest.ROOT

NEW_REDUCER = '''"""Steps of the window (a count the job made)."""


def reduce(facts, params):
    return facts.get("steps")
'''


def _digests(root):
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def test_a_cell_is_added_by_files_alone(tmp_path):
    shutil.copytree(ROOT / "perfbench", tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    before = _digests(tmp_path)
    bench_dir = tmp_path / "perfbench"

    # a configuration: its file of sizes (gpt2-medium's published ones)
    config = json.loads((bench_dir / "configs" / "gpt2-124m.json").read_text())
    config.update(n_embd=1024, n_layer=24, n_head=16, source=(
        "https://huggingface.co/openai-community/gpt2-medium/blob/main/"
        "config.json"))
    (bench_dir / "configs" / "gpt2-medium-355m.json").write_text(
        json.dumps(config))
    # a traffic mix: a data file for the one generator
    mix = json.loads((bench_dir / "traffic" / "train-b8-s1024.json")
                     .read_text())
    mix.update(batch=16, seq=512)
    (bench_dir / "traffic" / "train-b16-s512.json").write_text(json.dumps(mix))
    # a per-layer metric: its file and a reader of its own
    (bench_dir / "reducers" / "step_count.py").write_text(NEW_REDUCER)
    (bench_dir / "layer_metrics" / "train_program.steps.json").write_text(
        json.dumps({"layer": "train program", "unit": "count",
                    "better": "higher", "source": "program_counter",
                    "moves": "train_tokens_per_s_per_chip",
                    "reducer": "step_count", "params": {}}))
    # and the entries, appended
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    cell = "gpt2-medium-355m.train-b16-s512"
    bench["configs"].append({
        "name": "gpt2-medium-355m", "source": config["source"],
        "file": "perfbench/configs/gpt2-medium-355m.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({
        "name": cell, "config": "gpt2-medium-355m",
        "traffic": "train-b16-s512", "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "train_program.steps", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "train program",
        "moves": "train_tokens_per_s_per_chip", "workloads": [cell]})
    for m in bench["end_to_end"]:
        if m["name"] == "train_tokens_per_s_per_chip":
            m["workloads"].append(cell)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    after = _digests(tmp_path)
    changed = {p for p in before if after[p] != before[p]}
    assert changed == {"BENCHMARK.json"}            # entries appended, only
    assert len(after) == len(before) + 4

    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT),
               XLA_FLAGS="--xla_cpu_multi_thread_eigen=false")
    proc = subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload", cell, "--seed",
         "3", "--seconds", "1", "--trace", "1", "--rehearse"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["correct"] is True
    assert out["metrics"]["cpu_rehearsal.train_program.steps"]["value"] >= 6
    assert "cpu_rehearsal.train_program.step_ms" not in out["metrics"]
