"""``BENCHMARK.json`` against the benchmark's contract, the device gate,
and a cell made of new files only."""
import json
import re
import shutil
import subprocess
import sys

import pytest
from conftest import BENCH, ROOT, runner, tiny_serve

from yard import common

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BM = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_benchmark_keys_and_names():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert BM["paths"] == ["bench"] and BM["command"][1] == "bench/run.py"
    assert 1 <= BM["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BM[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in BM["end_to_end"] + BM["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BM["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert len(json.dumps(BM)) < 64 * 1024


def test_every_cell_reports_enough():
    for w in BM["workloads"]:
        cell = common.load_cell(w["name"])
        e2e = [m["name"] for m in cell.end_to_end]
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        assert cell.per_layer, w["name"]
        assert w["chips"] == 1 and len(w["why"]) <= 200
        for m in cell.per_layer:
            assert m["moves"] in e2e, (w["name"], m["name"])


def test_every_name_has_its_file():
    for c in BM["configs"]:
        cfg = common.load_json(ROOT / c["file"])
        assert (BENCH / "kinds" / f"{cfg['kind']}.py").is_file()
        assert sorted(cfg.get("reduced", [])) == sorted(c["reduced"])
    for w in BM["workloads"]:
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
    for m in BM["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()


def test_unknown_device_kind_is_an_error():
    assert common.peaks_of("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(common.BenchError, match="not in peaks.json"):
        common.peaks_of("TPU v99")


def _run(cwd, *extra):
    env = {"PATH": "/usr/bin:/bin:/usr/local/bin", "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "serve-qwen3-1.7b-poisson", "--seed", str(2**31 + 1),
         "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_off_the_chip():
    out = _run(ROOT)
    assert out.returncode == 2 and out.stdout == ""
    assert "'cpu'" in out.stderr and "TPU" in out.stderr


def test_refuses_alone_in_a_directory(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(tmp_path)
    assert out.returncode != 0 and out.stdout == ""


def test_a_cell_from_new_files_only(tmp_path, peak):
    """A configuration, a traffic mix and a per-layer metric, each a new
    file, with new entries in ``BENCHMARK.json``, make a runnable cell;
    no file that was there is edited."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p.relative_to(tmp_path): p.read_bytes()
              for p in (tmp_path / "bench").rglob("*") if p.is_file()}
    tiny = tiny_serve()
    (tmp_path / "bench/configs/tiny-lm.json").write_text(
        json.dumps(tiny.config))
    traffic = dict(tiny.traffic, arrivals={"kind": "poisson",
                                           "rate_per_s": 8.0})
    (tmp_path / "bench/traffic/tiny-slow.json").write_text(
        json.dumps(traffic))
    (tmp_path / "bench/metrics/decode_steps_per_s.py").write_text(
        '"""Decode steps per second of the window."""\n\n\n'
        'def read(w):\n'
        '    steps = w.counters.get("decode_steps", 0)\n'
        '    return steps / w.seconds if steps else None\n')
    bm = json.loads(json.dumps(BM))
    bm["configs"].append({"name": "tiny-lm", "source": "https://example.org",
                          "file": "bench/configs/tiny-lm.json",
                          "reduced": [], "why": "a CPU-sized LM"})
    bm["workloads"].append({"name": "tiny-lm.slow", "config": "tiny-lm",
                            "traffic": "tiny-slow", "chips": 1,
                            "why": "requests at 8 a second"})
    for m in bm["end_to_end"]:
        if m["name"] == "tpot_p95_ms":
            m["workloads"].append("tiny-lm.slow")
    bm["per_layer"].append({"name": "decode_steps_per_s", "unit": "1/s",
                            "better": "higher", "source": "program_counter",
                            "layer": "serving plane", "moves": "tpot_p95_ms",
                            "workloads": ["tiny-lm.slow"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))

    cell = common.load_cell("tiny-lm.slow", root=tmp_path)
    assert [m["name"] for m in cell.per_layer] == ["decode_steps_per_s"]
    run_mod = common.load_module(tmp_path / "bench/run.py", "tmp_run")
    res = runner(cell).run(cell, seed=3, seconds=1.0, trace=True,
                           peak=peak, t_start=0.0)
    line = run_mod.result_line(cell, res, {"platform": "cpu"}, True)
    assert line["correct"] and line["attempted"] == 8
    assert line["metrics"]["decode_steps_per_s"]["value"] > 0
    after = {p.relative_to(tmp_path): p.read_bytes()
             for p in (tmp_path / "bench").rglob("*") if p.is_file()
             and "__pycache__" not in p.parts}
    assert all(after[k] == v for k, v in before.items())
