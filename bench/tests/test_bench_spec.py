"""BENCHMARK.json resolves to its files by name, a cell added as files
and entries is found without a code change, and the harness refuses a
platform that is not a TPU."""

import json
import os
import re
import shutil

import numpy as np
import pytest

from bench import run as bench_run
from bench.harness import cell as runner
from bench.harness import data, device, spec

ROOT = spec.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _bench():
    return spec.load_json(os.path.join(ROOT, "BENCHMARK.json"))


@pytest.mark.parametrize("workload",
                         [w["name"] for w in _bench()["workloads"]])
def test_cell_resolves(workload):
    cell = spec.resolve(workload, ROOT)
    assert cell.config["name"] == cell.config_name
    assert runner.loop_of(cell.mix) in runner.LOOPS
    names = [m.name for m in cell.end_to_end]
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    for m in cell.end_to_end + cell.per_layer:
        assert callable(m.read)
    cfg = [c for c in _bench()["configs"] if c["name"] == cell.config_name][0]
    for key in cfg["reduced"]:
        assert key in cell.config["data"] and key in cell.config["reduced"]


def test_names_units_and_files():
    b = _bench()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in b[group]]
        assert len(set(names)) == len(names)
        assert all(NAME.match(n) for n in names)
    for m in b["end_to_end"] + b["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
        assert os.path.exists(os.path.join(ROOT, "bench", "metrics",
                                           m["name"] + ".py"))
    e2e = {m["name"] for m in b["end_to_end"]}
    for m in b["per_layer"]:
        assert m["moves"] in e2e
    for c in b["configs"]:
        assert c["file"].startswith("bench/")


def test_new_cell_is_found_from_files_alone(tmp_path):
    """A later change adds a configuration, a mix, a metric and a cell as
    new files and entries; resolving it edits no code."""
    for d in ("configs", "mixes", "metrics"):
        shutil.copytree(os.path.join(ROOT, "bench", d),
                        tmp_path / "bench" / d)
    b = _bench()
    cfg = json.load(open(os.path.join(ROOT, "bench", "configs",
                                      "social-lj.json")))
    cfg["name"] = "social-small"
    (tmp_path / "bench" / "configs" / "social-small.json").write_text(
        json.dumps(cfg))
    mix = json.load(open(os.path.join(ROOT, "bench", "mixes", "tao.json")))
    mix["read_frac"] = 0.9
    (tmp_path / "bench" / "mixes" / "tao-writes.json").write_text(
        json.dumps(mix))
    (tmp_path / "bench" / "metrics" / "reads_per_s.py").write_text(
        "def read(run):\n    return 42.0\n")
    b["configs"].append({"name": "social-small", "source": "x",
                         "file": "bench/configs/social-small.json",
                         "reduced": [], "why": "x"})
    b["workloads"].append({"name": "social-small.tao-writes",
                           "config": "social-small",
                           "traffic": "tao-writes", "chips": 1, "why": "x"})
    b["per_layer"].append({"name": "reads_per_s", "unit": "ops/s",
                           "better": "higher", "source": "host_clock",
                           "layer": "x", "moves": "ops_per_s",
                           "workloads": ["social-small.tao-writes"]})
    for m in b["end_to_end"]:
        if "workloads" in m and "social-lj.tao" in m["workloads"]:
            m["workloads"].append("social-small.tao-writes")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    cell = spec.resolve("social-small.tao-writes", str(tmp_path))
    assert cell.config["name"] == "social-small"
    assert cell.mix["read_frac"] == 0.9
    assert runner.loop_of(cell.mix) == "serve"
    assert [m.name for m in cell.per_layer] == ["reads_per_s"]
    assert cell.per_layer[0].read(None) == 42.0
    assert {m.name for m in cell.end_to_end} == {"read_p95_ms", "ops_per_s",
                                                  "setup_s"}
    # the existing cells resolve as before
    assert spec.resolve("social-lj.tao", str(tmp_path)).per_layer


@pytest.mark.parametrize("traffic", sorted(
    {w["traffic"] for w in _bench()["workloads"]}))
def test_mix_with_a_loop_no_runner_drives_is_refused(traffic):
    """A mix file that asks for an arrival loop the harness does not have
    is refused, never run as a closed loop."""
    mix = spec.load_json(os.path.join(ROOT, "bench", "mixes",
                                      f"{traffic}.json"))
    assert runner.loop_of(mix) in runner.LOOPS
    for loop in ("open", None):
        with pytest.raises(ValueError, match="loop"):
            runner.loop_of(dict(mix, loop=loop))


@pytest.mark.parametrize("config", sorted(
    {c["file"] for c in _bench()["configs"]}))
def test_config_generator_is_dispatched_by_name(config):
    """The graph comes from the generator the configuration names; an
    unknown name is refused."""
    g = spec.load_json(os.path.join(ROOT, config))["data"]
    small = 0.01 if g["generator"] == "power_law" else 8
    gr = data.graph(np.random.default_rng(7), g, small)
    src, dst = gr.edges()
    assert src.size == dst.size == gr.src.size * (2 if gr.undirected else 1)
    assert src.max() < gr.n and not np.any(src == dst)
    s, d = gr.draw(np.random.default_rng(8), 100)
    assert s.size == d.size == 100 and max(s.max(), d.max()) < gr.n
    with pytest.raises(ValueError, match="generator"):
        data.graph(np.random.default_rng(7), dict(g, generator="kronecker"),
                   small)


def test_unknown_workload_raises():
    with pytest.raises(KeyError):
        spec.resolve("no-such.cell", ROOT)


def test_refuses_a_platform_that_is_not_tpu(capsys):
    rc = bench_run.main(["--workload", "social-lj.tao", "--seed",
                         str(2**31 + 3), "--seconds", "1", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc == 1
    assert "{" not in out
    assert "needs a TPU" in err


def test_unknown_device_kind_is_an_error():
    with pytest.raises(device.NoChip):
        device.peaks("TPU v99")
    assert device.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
