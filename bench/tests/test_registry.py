"""A later change adds a configuration, a traffic mix (with a generator of
its own) and a per-layer metric as new files plus entries in
``BENCHMARK.json``, and the harness finds each by name, with no existing
file edited."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import registry

ROOT = Path(__file__).resolve().parents[2]


def _digest(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted((root / "bench").rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


@pytest.fixture
def copy(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return tmp_path


def _add_new_files(root: Path):
    old = json.loads((root / "bench/configs/internlm2-1.8b.s816.json")
                     .read_text())
    new_conf = dict(old, name="tiny-new.dense",
                    weights={"format": "dense"})
    (root / "bench/configs/tiny-new.dense.json").write_text(
        json.dumps(new_conf))
    # a generator module of its own: a fixed burst, then nothing
    (root / "bench/generators/burst_once.py").write_text('''
import dataclasses

@dataclasses.dataclass
class Spec:
    index: int
    due: float
    prompt_len: int
    out_len: int

class Burst:
    closed = False
    def __init__(self, mix, seed, n_slots):
        self.left = [Spec(i, 0.0, mix["prompt"]["min"], mix["output"]["min"])
                     for i in range(mix["n"])]
    def next_due(self):
        return self.left[0].due if self.left else float("inf")
    def pop_due(self, t):
        out, self.left = self.left, []
        return out
    def on_finish(self, spec, t):
        pass

def make(mix, seed, n_slots):
    return Burst(mix, seed, n_slots)
''')
    (root / "bench/traffic/burst.json").write_text(json.dumps({
        "name": "burst", "generator": "burst_once", "n": 4,
        "prompt": {"min": 16}, "output": {"min": 6},
        "warmup_s": 0.0, "trace_s": 0.5}))
    (root / "bench/metrics/steps_in_window.py").write_text(
        "def read(ctx):\n"
        "    w = ctx.window\n"
        "    return float(sum(1 for s in w.steps if w.start <= s.t0 < w.end))\n")
    bm = json.loads((root / "BENCHMARK.json").read_text())
    bm["configs"].append({"name": "tiny-new.dense", "source": "test",
                          "file": "bench/configs/tiny-new.dense.json",
                          "reduced": [], "why": "test"})
    bm["workloads"].append({"name": "tiny-new.burst",
                            "config": "tiny-new.dense", "traffic": "burst",
                            "chips": 1, "why": "test"})
    bm["per_layer"].append({"name": "steps_in_window", "unit": "steps",
                            "better": "higher", "source": "program_counter",
                            "layer": "Scheduler", "moves": "itl_p50_ms",
                            "workloads": ["tiny-new.burst"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))


def test_new_files_are_found_by_name(copy):
    before = _digest(copy)
    _add_new_files(copy)
    after = _digest(copy)
    assert all(after[k] == v for k, v in before.items())   # nothing edited
    assert registry.workload("tiny-new.burst", copy)["traffic"] == "burst"
    assert registry.config("tiny-new.dense", copy)["weights"] == {
        "format": "dense"}
    mix = registry.traffic("burst", copy)
    gen = registry.generator(mix["generator"], copy).make(mix, 1, 4)
    assert len(gen.pop_due(0.0)) == 4
    reader = registry.metric_reader("steps_in_window", copy)
    assert callable(reader.read)
    _, per_layer = registry.metrics_for("tiny-new.burst", copy)
    names = [m["name"] for m in per_layer]
    assert "steps_in_window" in names
    # a metric that lists its cells is not reported by others
    _, chat = registry.metrics_for("internlm2-s816.chat", copy)
    assert "steps_in_window" not in [m["name"] for m in chat]


def test_unknown_names_are_errors(copy):
    with pytest.raises(registry.NotFound):
        registry.workload("no-such-cell", copy)
    with pytest.raises(registry.NotFound):
        registry.traffic("no-such-mix", copy)
    with pytest.raises(registry.NotFound):
        registry.metric_reader("no_such_metric", copy)
    with pytest.raises(registry.NotFound):
        registry.peaks("TPU v0", copy)


def test_new_cell_rehearses_from_its_files_alone(copy):
    """The copy's run.py serves the new cell, at the new configuration's
    smoke sizes, with the new generator, and checks it."""
    _add_new_files(copy)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    r = subprocess.run(
        [sys.executable, str(copy / "bench/run.py"), "--workload",
         "tiny-new.burst", "--seed", "5", "--seconds", "2", "--trace", "1",
         "--rehearse"], capture_output=True, text=True, env=env,
        timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and "metrics" not in line
    assert line["attempted"] == 4 and line["failed"] == 0
