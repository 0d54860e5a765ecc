"""A whole run at a size a test can hold, with the harness's look for a
chip skipped: the sound program comes out correct, and each fault the
cell can have, and the cell's control, planted under the window, comes
out not correct."""

import json
import os
import shutil
import time

import pytest

from bench.harness import cell as runner
from bench.harness import faults, spec

# (cell, scale): 1% of social-lj's users; R-MAT scale 8
SIZES = {"social-lj.tao": 0.01, "graph500-s15.analytics": 8}
CASES = [
    ("social-lj.tao", None, True),
    ("social-lj.tao", "ack_without_apply", False),
    ("social-lj.tao", "alter_answer", False),
    ("social-lj.tao", "control_stale_reads", False),
    ("graph500-s15.analytics", None, True),
    ("graph500-s15.analytics", "ack_without_apply", False),
    ("graph500-s15.analytics", "ack_half_without_apply", False),
    ("graph500-s15.analytics", "alter_pagerank", False),
    ("graph500-s15.analytics", "control_bf16_pagerank", False),
]


@pytest.fixture(autouse=True)
def _no_persistent_cache(monkeypatch):
    # the run would turn on JAX's persistent cache for the whole process
    monkeypatch.setattr(runner, "_persistent_cache", lambda: "off")


@pytest.mark.parametrize("workload,fault,correct", CASES)
def test_planted_fault_decides_correct(workload, fault, correct):
    cell = spec.resolve(workload)
    planted = faults.FAULTS[fault] if fault else None
    out = runner.run(cell, 2**31 + 11, 1.0, False, time.perf_counter(),
                     scale=SIZES[workload], planted=planted)
    assert out["correct"] is correct, out["checks"]
    assert out["attempted"] > 0
    assert list(out)[-1] == "checks"
    for c in out["checks"].values():
        assert set(c) == {"value", "limit"}


def test_other_generator_runs_from_files_alone(tmp_path):
    """A configuration whose generator differs from its mix's usual one
    (the power-law follower graph under the analytics mix, so a directed
    graph) is a new configuration file and new entries: it runs, and the
    sound program comes out correct, with no code edited."""
    for d in ("configs", "mixes", "metrics"):
        shutil.copytree(os.path.join(spec.ROOT, "bench", d),
                        tmp_path / "bench" / d)
    b = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    cfg = spec.load_json(os.path.join(spec.ROOT, "bench", "configs",
                                      "social-lj.json"))
    g500 = spec.load_json(os.path.join(spec.ROOT, "bench", "configs",
                                       "graph500-s15.json"))
    cfg.update(name="social-directed", kernels=g500["kernels"],
               limits=g500["limits"])
    (tmp_path / "bench" / "configs" / "social-directed.json").write_text(
        json.dumps(cfg))
    b["configs"].append({"name": "social-directed", "source": "x",
                         "file": "bench/configs/social-directed.json",
                         "reduced": ["n_vertices"], "why": "x"})
    b["workloads"].append({"name": "social-directed.analytics",
                           "config": "social-directed",
                           "traffic": "analytics", "chips": 1, "why": "x"})
    for m in b["end_to_end"] + b["per_layer"]:
        if "graph500-s15.analytics" in m.get("workloads", []):
            m["workloads"].append("social-directed.analytics")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    cell = spec.resolve("social-directed.analytics", str(tmp_path))
    out = runner.run(cell, 2**31 + 13, 1.0, False, time.perf_counter(),
                     scale=0.01)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0
