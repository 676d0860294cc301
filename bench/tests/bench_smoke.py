"""The smoke-sized benchmark the CPU tests drive: the real harness and
readers, with ``alexnet-smoke`` under two small mixes, in interpret mode.

``alexnet-smoke.json``'s ``logit_err`` limit, 5e-6, is set from CPU runs at
this size: the program read 6.5e-7 to 8.8e-7, the control 2.1e-5 to 2.6e-5.
"""
import shutil
from pathlib import Path

from bench import harness

HERE = Path(__file__).resolve().parent
CELLS = {"smoke.serve": "smoke_serve", "smoke.bulk": "smoke_bulk"}


def make_root(tmp: Path) -> Path:
    root = tmp / "bench"
    root.parent.mkdir(parents=True, exist_ok=True)
    shutil.copytree(harness.BENCH, root,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(HERE / "alexnet-smoke.json", root / "configs")
    for mix in CELLS.values():
        shutil.copy(HERE / f"{mix}.json", root / "traffic")
    return root


def make_bench() -> dict:
    bench = harness.load_benchmark()
    bench["workloads"] = [{"name": n, "config": "alexnet-smoke", "traffic": m,
                           "chips": 1} for n, m in CELLS.items()]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["smoke.serve" if w.endswith(".serve") else "smoke.bulk"
                              for w in m["workloads"]]
    return bench


def session(tmp: Path, name: str, **kw) -> harness.Session:
    return harness.Session(make_bench(), name, interpret=True,
                           root=make_root(tmp), **kw)
