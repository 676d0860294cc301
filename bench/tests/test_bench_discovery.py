"""The harness finds configurations, mixes, readers and program sides by
name, and refuses to run anywhere but on the chip."""
import json
import os
import re
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

from bench import harness, run

REPO = Path(__file__).resolve().parents[2]
TESTS = Path(__file__).resolve().parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
TOY = TESTS / "toy"  # a family of its own, laid out as under bench/
PROGRAM_SIDE = ("config", "params", "front", "pool", "warm", "control",
                "verify", "describe")


@pytest.fixture
def root(tmp_path):
    """A copy of the benchmark with new files added and none edited."""
    r = tmp_path / "bench"
    shutil.copytree(REPO / "bench", r, ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(TESTS / "alexnet-smoke.json", r / "configs" / "newcfg.json")
    shutil.copy(TESTS / "smoke_serve.json", r / "traffic" / "newmix.json")
    (r / "metrics" / "newmetric.py").write_text(
        "def read(ctx):\n    return 42.0 if ctx.calls else None\n")
    (r / "metrics" / "device_idle.special.py").write_text(
        "def read(ctx):\n    return 7.0\n")
    (r / "arrivals" / "newarrivals.py").write_text(
        "def due(mix, g, seconds):\n    return [0.0, 0.5 * seconds]\n")
    loop = (r / "loops" / "closed.py").read_text()
    (r / "loops" / "newloop.py").write_text(
        loop + "\n\ndef end_to_end(recs, window_s):\n    return {'requests': len(recs)}\n")
    mix = json.loads((TESTS / "smoke_bulk.json").read_text())
    (r / "traffic" / "newloopmix.json").write_text(json.dumps(dict(mix, loop="newloop")))
    return r


def test_new_files_are_found_by_name(root):
    conf = harness.load_config("newcfg", root)
    assert conf["in_chw"] == [3, 32, 32]
    assert harness.load_reference(conf, root).forward
    assert harness.load_traffic("newmix", root)["loop"] == "open"
    ctx = type("Ctx", (), {"calls": 3})()
    assert harness.load_reader("newmetric.serve", root)(ctx) == 42.0
    assert harness.load_reader("newmetric", root)(ctx) == 42.0
    # an exact file name wins over the name up to its first dot
    assert harness.load_reader("device_idle.special", root)(ctx) == 7.0
    with pytest.raises(FileNotFoundError):
        harness.load_reader("nosuch.serve", root)
    assert harness.load_arrivals("newarrivals", root)({}, None, 4.0) == [0.0, 2.0]
    assert harness.load_loop("newloop", root).end_to_end([1, 2], 1.0) == {"requests": 2}


def test_new_mix_brings_its_own_loop_and_arrivals(root):
    bench = harness.load_benchmark()
    bench["workloads"] += [
        {"name": "newcfg.newloop", "config": "newcfg", "traffic": "newloopmix", "chips": 1},
        {"name": "newcfg.newmix", "config": "newcfg", "traffic": "newmix", "chips": 1}]
    sess = harness.Session(bench, "newcfg.newloop", interpret=True, root=root)
    assert sess.loop.end_to_end([1], 1.0) == {"requests": 1}
    sess = harness.Session(bench, "newcfg.newmix", interpret=True, root=root)
    sess.mix = dict(sess.mix, arrivals="newarrivals")
    due, picks = harness.traffic.open_schedule(
        sess.mix, 3, 4.0, harness.load_arrivals(sess.mix["arrivals"], root))
    assert due == [0.0, 2.0] and len(picks) == 2


def test_new_cell_gets_its_own_metrics(root):
    bench = harness.load_benchmark()
    bench["workloads"].append({"name": "newcfg.newmix", "config": "newcfg",
                               "traffic": "newmix", "chips": 1})
    bench["per_layer"].append({"name": "newmetric.serve", "unit": "%",
                               "workloads": ["newcfg.newmix"]})
    names = [m["name"] for m in harness.cell_metrics(bench, "newcfg.newmix", "per_layer")]
    assert names == ["newmetric.serve"]
    e2e = [m["name"] for m in harness.cell_metrics(bench, "newcfg.newmix", "end_to_end")]
    assert "setup_s" in e2e and "latency_p95_ms" not in e2e


def test_benchmark_json_names_only_what_exists():
    bench = harness.load_benchmark()
    cells = {w["name"] for w in bench["workloads"]}
    for c in bench["configs"]:
        conf = json.loads((REPO / c["file"]).read_text())
        assert conf["name"] == c["name"] and conf["reduced"] == c["reduced"]
        assert {"source", "reduced", "assumed", "limits"} <= set(conf)
        assert (REPO / "bench" / "configs" / conf["reference"]).is_file()
        assert (REPO / "bench" / "programs" / f"{conf['family']}.py").is_file()
        program = harness.load_program(conf["family"])
        assert all(callable(getattr(program, f)) for f in PROGRAM_SIDE)
    for w in bench["workloads"]:
        harness.load_config(w["config"])
        mix = harness.load_traffic(w["traffic"])
        harness.load_loop(mix["loop"])
        if "arrivals" in mix:
            harness.load_arrivals(mix["arrivals"])
        reported = harness.cell_metrics(bench, w["name"], "end_to_end")
        assert "setup_s" in [m["name"] for m in reported] and len(reported) >= 2
        assert harness.cell_metrics(bench, w["name"], "per_layer")
    for m in bench["per_layer"] + bench["end_to_end"]:
        assert NAME.match(m["name"])
        assert set(m.get("workloads", cells)) <= cells
    for m in bench["per_layer"]:
        assert callable(harness.load_reader(m["name"]))


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "alexnet.serve",
         "--seed", "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_cpu_run_fails_without_a_result():
    p = _run(REPO)
    assert p.returncode == 2
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()


def test_benchmark_alone_does_not_run(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode == 2
    assert "program not found" in p.stderr
    assert not p.stdout.strip()


def _bench_files() -> list:
    """The files a copy of the benchmark takes from ``bench/``."""
    top = REPO / "bench"
    return sorted(p.relative_to(top) for p in top.rglob("*") if p.is_file()
                  and not {"tests", "__pycache__"} & set(p.relative_to(top).parts))


@pytest.fixture
def toy_root(tmp_path):
    """A copy of the benchmark with the toy family's files added: its
    program side, configuration, reference, mix and loop."""
    r = tmp_path / "bench"
    shutil.copytree(REPO / "bench", r, ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for f in sorted(TOY.rglob("*")):
        if f.is_file() and "__pycache__" not in f.parts:
            dst = r / f.relative_to(TOY)
            assert not dst.exists(), f"{dst} would be edited, not added"
            shutil.copy(f, dst)
    return r


def _toy_session(root, traffic="toy", **kw) -> harness.Session:
    bench = harness.load_benchmark()
    name = f"toy.{traffic}"
    bench["workloads"].append({"name": name, "config": "toy",
                               "traffic": traffic, "chips": 1})
    return harness.Session(bench, name, interpret=True, root=root, **kw)


def _plant_wrong_answer(key, fn):
    """One answer altered where the front produces it."""
    return lambda params, x: fn(params, x).at[0, 0].add(1.0)


def test_new_family_added_as_files_runs_end_to_end(toy_root):
    conf = harness.load_config("toy", toy_root)
    assert conf["family"] == "toy" and not {"convs", "in_chw"} & set(conf)
    sess = _toy_session(toy_root)
    sess.load(2**31 + 29)
    sess.warm()
    seen = sess.measure(0.3)
    assert seen.recs and seen.calls > 0 and seen.compiles == 0
    sess.free()
    numbers = sess.verify(seen)
    assert harness.passed(numbers), numbers

    r = run.execute(sess, 2**33 + 1, 0.3, t_start=time.perf_counter())
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"images_per_s", "setup_s"}
    assert set(r["check"]) == {"score_err", "unanswered"}

    sess.batcher.wrap = _plant_wrong_answer
    r = run.execute(sess, 2**33 + 2, 0.3, t_start=time.perf_counter())
    assert not r["correct"]
    assert r["check"]["score_err"]["value"] > r["check"]["score_err"]["limit"]

    for rel in _bench_files():  # nothing the benchmark had was edited
        assert (toy_root / rel).read_bytes() == (REPO / "bench" / rel).read_bytes(), rel


def test_new_family_control_is_not_correct(toy_root):
    sess = _toy_session(toy_root, control=True)
    r = run.execute(sess, 2**33 + 3, 0.3, t_start=time.perf_counter())
    assert not r["correct"]
    assert r["check"]["score_err"]["value"] > r["check"]["score_err"]["limit"]


def test_new_family_runs_under_a_shared_loop(toy_root):
    """A front with ``waiting``, ``send`` and ``serve`` needs no loop of its
    own: the toy family's second mix names the closed loop that the CNN
    cells use."""
    sess = _toy_session(toy_root, traffic="toy_closed")
    assert sess.mix["loop"] == "closed"
    r = run.execute(sess, 2**33 + 4, 0.3, t_start=time.perf_counter())
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert r["metrics"]["images_per_s"]["value"] > 0

    sess.batcher.wrap = _plant_wrong_answer
    r = run.execute(sess, 2**33 + 5, 0.3, t_start=time.perf_counter())
    assert not r["correct"]
